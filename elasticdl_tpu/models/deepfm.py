"""DeepFM over host-PS embedding tables (CTR family).

Reference parity: model_zoo/deepfm_edl_embedding/deepfm_edl_embedding.py
(uses elasticdl.layers.Embedding against the PS) and the dac_ctr deepfm
variant. TPU redesign: ids are swapped for (rows, indices) before the
step (train/sparse.py), so the device-side model is pure dense math —
gather, FM interaction, MLP — all fusable by XLA.

Expected raw features: {"ids": int64 [B, F]} and labels {0,1}.
"""

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.data.example import decode_example
from elasticdl_tpu.train import metrics
from elasticdl_tpu.train.losses import sigmoid_binary_cross_entropy
from elasticdl_tpu.train.optimizers import create_optimizer
from elasticdl_tpu.train.sparse import SparseEmbeddingSpec, embedding_lookup

_logger = _logger_factory("elasticdl_tpu.models.deepfm")

EMBEDDING_DIM = 8


class DeepFM(nn.Module):
    embedding_dim: int = EMBEDDING_DIM
    hidden: tuple = (64, 32)

    @nn.compact
    def __call__(self, features, training: bool = False):
        # [B, F, d] second-order embeddings + [B, F->sum, 1] first-order
        emb = embedding_lookup(features, "deepfm_emb", combiner=None)
        linear = embedding_lookup(features, "deepfm_linear", combiner="sum")
        # FM second-order: 0.5 * ((sum v)^2 - sum v^2)
        summed = emb.sum(axis=1)
        fm = 0.5 * (jnp.square(summed) - jnp.square(emb).sum(axis=1))
        fm_term = fm.sum(axis=-1, keepdims=True)
        # deep tower over flattened field embeddings
        deep = emb.reshape((emb.shape[0], -1))
        for width in self.hidden:
            deep = nn.relu(nn.Dense(width)(deep))
        deep_term = nn.Dense(1)(deep)
        logit = linear.reshape((-1, 1)) + fm_term + deep_term
        return logit.squeeze(-1)


def custom_model():
    return DeepFM()


def loss(labels, predictions):
    return sigmoid_binary_cross_entropy(labels, predictions)


def optimizer():
    return create_optimizer("Adam", learning_rate=0.001)


# Deployable default shape: criteo-dac (reference model_zoo/dac_ctr/
# feature_config.py groups 39 raw columns). The models are field-count
# agnostic at apply time; this default sizes the id buffers.
NUM_FIELDS = 39
# Ceiling on the padded unique-id buffer for ZIPFIAN id streams: a CTR
# batch carries far fewer unique ids than batch*fields, so a buffer of
# this size moves fewer padded rows a step. This is an opt-in
# deployment tuning; the library default below stays the always-safe
# worst case so near-uniform id streams never hit the capacity
# ValueError out of the box.
MAX_ID_CAPACITY = 8192

# capacity-warning dedup (ISSUE 6 satellite): specs are constructed
# once per trainer, and a bench/worker process builds several trainers
# over its life — BENCH_r05's tail carried the identical line 3x. One
# line per distinct (capacity, batch, fields) shape per process says
# everything the repeat said.
_warned_capacities = set()


def sparse_embedding_specs(num_features=NUM_FIELDS, batch_size=64,
                           capacity=None):
    """Host-PS tables this model trains against (TPU-contract addition:
    the reference discovers elasticdl.layers.Embedding instances via
    model introspection, model_handler.py:98-102; here the module
    declares them). The capacity default is the always-safe worst case
    ``batch_size * num_features`` — any id stream fits. Zipfian CTR
    streams can opt into a smaller buffer via
    ``capacity=min(batch*fields, MAX_ID_CAPACITY)`` or
    EDL_SPARSE_ID_CAPACITY (docs/PERFORMANCE.md); overflow raises a
    clear ValueError naming the knob (train/sparse.py)."""
    from elasticdl_tpu.common.env_utils import env_int

    if capacity is None:
        capacity = env_int(
            "EDL_SPARSE_ID_CAPACITY", batch_size * num_features
        )
    shape_key = (capacity, batch_size, num_features)
    if (
        capacity < batch_size * num_features
        and shape_key not in _warned_capacities
    ):
        _warned_capacities.add(shape_key)
        _logger.info(
            "deepfm id-buffer capacity %d < worst case %d (batch %d x "
            "%d fields): fine for Zipfian id streams; a near-uniform "
            "stream will raise a capacity ValueError naming this knob",
            capacity, batch_size * num_features, batch_size, num_features,
        )
    return [
        # Small second-order init: an id the optimizer barely touched
        # contributes ~nothing through the FM/deep towers instead of
        # init-scale noise. On held-out CTR data most ids are rare, so
        # eval AUC is dominated by exactly those rows — init 0.05 cost
        # ~0.08 AUC on the planted-signal eval vs 0.001 (measured via
        # the local-executor lane).
        SparseEmbeddingSpec(
            "deepfm_emb",
            EMBEDDING_DIM,
            feature_key="ids",
            capacity=capacity,
            init_scale=0.001,
        ),
        # Wide term starts at exactly no-op (standard wide&deep
        # practice): a zero row is the correct prior for an unseen id,
        # and the first gradient step writes the signal, not a
        # correction of random noise.
        SparseEmbeddingSpec(
            "deepfm_linear", 1, feature_key="ids", capacity=capacity,
            initializer="zeros",
        ),
    ]


def dataset_fn(dataset, mode=None, metadata=None):
    def parse(payload):
        example = decode_example(payload)
        return (
            {"ids": example["ids"].astype(np.int64)},
            example["label"].astype(np.float32).reshape(()),
        )

    return dataset.map(parse)


def eval_metrics_fn():
    return {
        "auc": metrics.AUC(from_logits=True),
        "accuracy": metrics.BinaryAccuracy(from_logits=True),
    }
