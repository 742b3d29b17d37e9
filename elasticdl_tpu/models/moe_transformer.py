"""Mixture-of-experts transformer LM — the expert-parallel model family.

No reference counterpart (SURVEY.md §2.12: EP absent from the reference);
this family exercises the ``ep`` mesh axis. Every other block swaps the
dense MLP for a top-k-routed expert MLP (ops/moe.py): expert weight
tensors carry a leading expert dim sharded over ``ep``, the dispatch/
combine einsums become all-to-alls under GSPMD, and within each expert
the FFN is still tensor-parallel over ``tp``.
"""

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.data.example import decode_example
from elasticdl_tpu.models.transformer import Attention, Block
from elasticdl_tpu.ops.moe import (
    expert_capacity,
    invert_slots,
    moe_combine,
    moe_combine_compact,
    moe_dispatch,
    moe_dispatch_compact,
    top_k_routing,
    top_k_routing_compact,
)
from elasticdl_tpu.parallel.mesh import DATA_AXES
from elasticdl_tpu.parallel.sharding import ShardingRules, constrain
from elasticdl_tpu.train import metrics
from elasticdl_tpu.train.losses import sparse_softmax_cross_entropy
from elasticdl_tpu.train.optimizers import create_optimizer


class MoeMlp(nn.Module):
    """Top-k routed expert FFN (GShard dispatch, Switch aux loss).

    Two dispatch implementations with identical semantics
    (``tests/test_moe.py::test_compact_dispatch_matches_onehot``):

    - ``"onehot"`` (= ``"auto"``, the measured default) — GShard
      dispatch/combine einsums. The one-hot contraction is MXU work,
      so it scales with batch (59.8% MFU at the docs/PERF_MOE.md
      B=16 config), and under GSPMD with tokens dp-sharded and
      experts ep-sharded these einsums ARE the dp→ep all-to-alls.
    - ``"compact"`` — slot-index gathers with gather-only custom
      backwards (ops/moe.py). No (G, S, E, C) one-hots and ~10% fewer
      executed FLOPs, but XLA lowers TPU row-gathers at ~200 GB/s, so
      it measured SLOWER end-to-end than the einsums at every batch
      tried — kept as an explicit option and a measured negative
      (docs/PERF_MOE.md round 5); a Pallas gather kernel is the known
      path to make it win.
    """

    num_experts: int
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    dispatch_impl: str = "auto"
    mesh: Optional[Any] = None

    def _use_compact(self):
        return self.dispatch_impl == "compact"

    @nn.compact
    def __call__(self, x):
        groups, seq, dim = x.shape
        ff = dim * self.mlp_ratio
        capacity = expert_capacity(
            seq, self.num_experts, self.top_k, self.capacity_factor
        )
        router_logits = nn.Dense(
            self.num_experts, use_bias=False, name="router"
        )(x)
        compact = self._use_compact()
        if compact:
            gates, slot, aux_loss = top_k_routing_compact(
                router_logits, self.top_k, capacity
            )
            # one inversion scatter shared by dispatch AND combine
            j_for_slot = invert_slots(
                slot, self.num_experts * capacity
            )
            expert_in = moe_dispatch_compact(
                x, slot, self.num_experts, capacity,
                j_for_slot=j_for_slot,
            )
        else:
            combine, dispatch, aux_loss = top_k_routing(
                router_logits, self.top_k, capacity
            )
            # (E, G, C, M): the dispatch einsum is the dp→ep all-to-all.
            expert_in = moe_dispatch(x, dispatch)
        expert_in = constrain(
            expert_in, self.mesh, P("ep", DATA_AXES, None, None)
        )
        w_up = self.param(
            "w_up",
            nn.initializers.lecun_normal(),
            (self.num_experts, dim, ff),
        )
        w_down = self.param(
            "w_down",
            nn.initializers.lecun_normal(),
            (self.num_experts, ff, dim),
        )
        h = jnp.einsum("egcm,emf->egcf", expert_in, w_up.astype(x.dtype))
        h = nn.gelu(h)
        out = jnp.einsum("egcf,efm->egcm", h, w_down.astype(x.dtype))
        out = constrain(
            out, self.mesh, P("ep", DATA_AXES, None, None)
        )
        if compact:
            y = moe_combine_compact(
                out, slot, gates, j_for_slot=j_for_slot
            )
        else:
            y = moe_combine(out, combine)  # ep→dp all-to-all back
        return y, aux_loss


class MoeBlock(nn.Module):
    num_heads: int
    num_experts: int
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    attention_impl: str = "auto"
    dispatch_impl: str = "auto"
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, training=False):
        h = nn.LayerNorm(name="ln_attn")(x)
        x = x + Attention(
            self.num_heads,
            attention_impl=self.attention_impl,
            mesh=self.mesh,
            name="attn",
        )(h, training)
        h = nn.LayerNorm(name="ln_mlp")(x)
        y, aux_loss = MoeMlp(
            self.num_experts,
            mlp_ratio=self.mlp_ratio,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            dispatch_impl=self.dispatch_impl,
            mesh=self.mesh,
            name="moe_mlp",
        )(h)
        return x + y, aux_loss


class MoeTransformerLM(nn.Module):
    """Decoder-only LM with MoE FFNs in every other block.

    Training call returns ``{"logits", "aux_loss"}`` (the router
    load-balance penalty must reach the loss); eval returns bare logits
    so metrics and export see the same surface as the dense LM.
    """

    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    embed_dim: int = 512
    mlp_ratio: int = 4
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    attention_impl: str = "auto"
    dispatch_impl: str = "auto"
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        x = nn.Embed(
            self.vocab_size, self.embed_dim, name="wte"
        )(tokens.astype(jnp.int32))
        aux_total = jnp.float32(0.0)
        for i in range(self.num_layers):
            if i % 2 == 1:
                x, aux = MoeBlock(
                    self.num_heads,
                    self.num_experts,
                    mlp_ratio=self.mlp_ratio,
                    top_k=self.top_k,
                    capacity_factor=self.capacity_factor,
                    attention_impl=self.attention_impl,
                    dispatch_impl=self.dispatch_impl,
                    mesh=self.mesh,
                    name="block_%d" % i,
                )(x, training)
                aux_total = aux_total + aux
            else:
                x = Block(
                    self.num_heads,
                    mlp_ratio=self.mlp_ratio,
                    attention_impl=self.attention_impl,
                    mesh=self.mesh,
                    name="block_%d" % i,
                )(x, training)
        x = nn.LayerNorm(name="ln_f")(x)
        logits = nn.Dense(
            self.vocab_size, use_bias=False, name="lm_head"
        )(x)
        if training:
            return {
                "logits": logits,
                "aux_loss": self.aux_loss_weight * aux_total,
            }
        return logits


# ---------------------------------------------------------------------------
# Sharding rules: transformer TP rules + expert-dim ep sharding
# ---------------------------------------------------------------------------


def moe_sharding_rules():
    """Dense-block rules plus expert weights over (ep, fsdp/tp).

    w_up (E, M, F): experts over ep, FFN dim over tp (Megatron within
    the expert); w_down (E, F, M) transposed to match. The router stays
    replicated — it is tiny and on the critical path of every token.
    """
    return ShardingRules(
        rules=[
            (r"router/kernel$", P()),
            (r"w_up$", P("ep", "fsdp", "tp")),
            (r"w_down$", P("ep", "tp", "fsdp")),
            (r"(query|key|value)/kernel$", P("fsdp", "tp", None)),
            (r"out_proj/kernel$", P("tp", None, "fsdp")),
            (r"mlp_up/kernel$", P("fsdp", "tp")),
            (r"mlp_down/kernel$", P("tp", "fsdp")),
            (r"wte/embedding$", P("tp", "fsdp")),
            (r"lm_head/kernel$", P("fsdp", "tp")),
            (r".*", P()),
        ],
        default_spec=P(),
    )


def batch_spec():
    return P(DATA_AXES, "sp")


# ---------------------------------------------------------------------------
# Model-zoo contract
# ---------------------------------------------------------------------------


def custom_model(mesh=None):
    return MoeTransformerLM(
        vocab_size=32000,
        num_layers=12,
        num_heads=12,
        embed_dim=768,
        num_experts=8,
        mesh=mesh,
    )


def loss(labels, predictions):
    if isinstance(predictions, dict):
        logits = predictions["logits"]
        aux = predictions["aux_loss"]
    else:
        logits, aux = predictions, 0.0
    per_token = sparse_softmax_cross_entropy(
        labels[:, 1:], logits[:, :-1]
    )
    # aux is a scalar: adding it to every per-sample loss leaves the
    # masked mean shifted by exactly aux.
    return per_token.mean(axis=-1) + aux


def optimizer():
    return create_optimizer("AdamW", learning_rate=3e-4, weight_decay=0.01)


def sharding_rules():
    return moe_sharding_rules()


def dataset_fn(dataset, mode=None, metadata=None):
    def parse(payload):
        example = decode_example(payload)
        tokens = example["tokens"].astype(np.int32)
        return tokens, tokens

    return dataset.map(parse)


def eval_metrics_fn():
    return {"accuracy": metrics.Accuracy()}
