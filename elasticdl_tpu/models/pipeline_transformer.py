"""Pipeline-parallel transformer LM.

No reference counterpart (the reference has no layer pipelining —
SURVEY.md §2.12 lists PP as absent); this family exercises the ``pp``
mesh axis: transformer blocks are pipeline *stages* whose stacked
parameters shard ``P("pp")`` over the mesh, and the forward runs the
GPipe microbatch schedule in :mod:`elasticdl_tpu.parallel.pipeline`.

Parameter layout is topology-independent by default: blocks are stored
as one flat ``(num_layers, ...)`` stack regardless of the mesh, and
``apply`` reshapes to ``(num_stages, layers_per_stage, ...)`` inside
the jitted step. A checkpoint written on a pp=4 mesh therefore restores
bit-for-bit onto pp=2 or a single chip (the elastic-resume contract the
dense checkpoint path promises). EXCEPTION: ``device_major_params=True``
(the interleaved schedule's opt-in: no per-step cross-shard
permutation of the stage stack; not measured on a chip) stores the
stack in device-placement order pinned to the current
``(num_stages, num_chunks)``; such state lives under the pytree key
``blocks_device_major`` instead of ``blocks``, so restoring it into a
job with the other layout setting fails LOUDLY on pytree structure
instead of silently scrambling layers — convert with
``blocks_to_portable``/``blocks_from_portable`` when moving topology.

The model is a plain (non-flax) class implementing the framework's model
contract — ``init(rng, features) -> variables`` / ``apply(variables,
features, training=, rngs=)`` — because the stage loop lives in a
``shard_map`` that flax's module system has no idiom for; the embed /
final-norm / head pieces and the per-stage Block remain ordinary flax
modules so their params initialize identically to TransformerLM's.

Dropout is intentionally unsupported here (stage rng plumbing through the
pipeline schedule isn't worth the complexity; the reference's models
don't regularize via dropout either).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.models import transformer
from elasticdl_tpu.parallel.pipeline import (
    device_major_order,
    pipeline_apply,
    stack_stage_params,
)
from elasticdl_tpu.parallel.sharding import ShardingRules
from jax.sharding import PartitionSpec as P


class PipelinedTransformerLM:
    """Decoder-only LM with blocks partitioned into pipeline stages.

    ``num_layers`` total blocks are split evenly across ``num_stages``
    pipeline stages; ``num_stages`` must equal the mesh's ``pp`` extent
    (or 1 when no mesh is given — pure sequential fallback for
    single-chip runs) and must divide ``num_layers`` exactly — the model
    never silently changes depth to fit a mesh.
    """

    def __init__(
        self,
        vocab_size=32000,
        num_layers=4,
        num_stages=4,
        num_heads=8,
        embed_dim=512,
        mlp_ratio=4,
        num_microbatches=4,
        attention_impl="auto",
        mesh=None,
        num_chunks=1,
        device_major_params=False,
    ):
        if num_layers % (num_stages * num_chunks) != 0:
            raise ValueError(
                "num_layers=%d is not divisible by num_stages*num_chunks"
                "=%d; refusing to silently change model depth"
                % (num_layers, num_stages * num_chunks)
            )
        if device_major_params and (num_chunks == 1 or mesh is None):
            raise ValueError(
                "device_major_params only applies to interleaved "
                "pipelines (num_chunks > 1 with a mesh) — at V=1 the "
                "portable layout is already device-contiguous"
            )
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.num_stages = num_stages
        # interleaved virtual chunks per device (Megatron interleaved
        # schedule; parallel/pipeline.py) — divides the bubble by V
        self.num_chunks = num_chunks
        # device-major at-rest layer order: removes the interleaved
        # schedule's per-step cross-shard permutation of the stage
        # stack (parallel/pipeline.py params_layout note) at the price
        # of an (S, V)-pinned checkpoint layout — convert with
        # blocks_to_portable/blocks_from_portable at topology changes
        self.device_major_params = device_major_params
        self.num_microbatches = num_microbatches
        self.mesh = mesh
        self.embed_dim = embed_dim
        self._wte = nn.Embed(vocab_size, embed_dim, name="wte")
        self._ln_f = nn.LayerNorm(name="ln_f")
        self._head = nn.Dense(vocab_size, use_bias=False, name="lm_head")
        self._block = transformer.Block(
            dict(num_heads=num_heads, attention_impl=attention_impl),
            mlp_ratio=mlp_ratio,
            mesh=mesh,
        )

    # -- model contract ------------------------------------------------
    def init(self, rng, tokens, training=False, rngs=None):
        del training, rngs
        keys = jax.random.split(rng, self.num_layers + 3)
        wte = self._wte.init(keys[0], jnp.asarray(tokens, jnp.int32))
        x = self._wte.apply(wte, jnp.asarray(tokens, jnp.int32))
        block_params = []
        for i in range(self.num_layers):
            variables = self._block.init(keys[1 + i], x, training=False)
            block_params.append(variables["params"])
        # Flat (num_layers, ...) stack — independent of num_stages, so
        # checkpoints restore across any pp extent. With
        # device_major_params the flat order is instead the device-
        # placement order for (num_stages, num_chunks) — see
        # _layer_order.
        order = self._layer_order()
        if order is not None:
            block_params = [block_params[i] for i in order]
        stacked = stack_stage_params(block_params)
        ln_f = self._ln_f.init(keys[-2], x)
        head = self._head.init(keys[-1], x)
        return {
            "params": {
                "wte": wte["params"],
                # layout-specific key: a device-major checkpoint can
                # never be restored into a portable-layout job (or vice
                # versa) without a loud pytree-structure mismatch
                self.blocks_key: stacked,
                "ln_f": ln_f["params"],
                "lm_head": head["params"],
            }
        }

    @property
    def blocks_key(self):
        return (
            "blocks_device_major" if self.device_major_params else "blocks"
        )

    def _layer_order(self):
        """Flat layer order at rest: None = layer order (portable);
        device_major_params = layers grouped so the contiguous P("pp")
        split hands each device its interleaved chunks with no per-step
        permutation (flat position p*per_chunk + k holds layer
        order_dm[p]*per_chunk + k)."""
        if not self.device_major_params:
            return None
        per_chunk = self.num_layers // (self.num_stages * self.num_chunks)
        order = []
        for chunk in device_major_order(self.num_stages, self.num_chunks):
            order.extend(
                range(chunk * per_chunk, (chunk + 1) * per_chunk)
            )
        return order

    def blocks_to_portable(self, blocks):
        """Reorder device-major-at-rest block leaves back to flat layer
        order (the topology-portable checkpoint layout). Host-side; use
        before handing a device-major checkpoint to a job with a
        different (num_stages, num_chunks)."""
        order = self._layer_order()
        if order is None:
            return blocks
        inverse = np.argsort(order)
        return jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, inverse, axis=0), blocks
        )

    def blocks_from_portable(self, blocks):
        """Inverse of blocks_to_portable."""
        order = self._layer_order()
        if order is None:
            return blocks
        return jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, np.asarray(order), axis=0),
            blocks,
        )

    def apply(self, variables, tokens, training=False, rngs=None):
        del rngs
        params = variables["params"]
        x = self._wte.apply(
            {"params": params["wte"]}, jnp.asarray(tokens, jnp.int32)
        )

        def stage_fn(stage_params, h):
            def layer(carry, layer_params):
                out, _ = self._block.apply(
                    {"params": layer_params}, carry, training=training
                )
                return out, None

            h, _ = jax.lax.scan(layer, h, stage_params)
            return h

        if self.mesh is None:
            # Single-chip sequential fallback: scan over the flat stack.
            x = stage_fn(params[self.blocks_key], x)
        else:
            # Regroup (L, ...) -> (S*V, L/(S*V), ...) for the schedule.
            # The leading dim is pp-sharded, so the reshape splits along
            # shard boundaries (no resharding).
            chunks = self.num_stages * self.num_chunks
            per_chunk = self.num_layers // chunks
            staged = jax.tree_util.tree_map(
                lambda leaf: leaf.reshape(
                    (chunks, per_chunk) + leaf.shape[1:]
                ),
                params[self.blocks_key],
            )
            x = pipeline_apply(
                stage_fn,
                staged,
                x,
                num_microbatches=self.num_microbatches,
                mesh=self.mesh,
                num_chunks=self.num_chunks,
                params_layout=(
                    "device" if self.device_major_params else "chunk"
                ),
            )
        x = self._ln_f.apply({"params": params["ln_f"]}, x)
        return self._head.apply({"params": params["lm_head"]}, x)


def pipeline_sharding_rules():
    """Layer-stack axis over pp, everything else replicated.

    Blocks leaves are flat ``(num_layers, *param_shape)``; sharding dim 0
    over pp gives each stage exactly its own layers. Within-stage params
    are intentionally NOT fsdp/tp-sharded: the stage loop runs inside a
    ``shard_map`` manual region where GSPMD annotations are inert, so any
    other spec here would just make jit all-gather the params at the
    shard_map boundary every step.
    """
    return ShardingRules(
        rules=[
            (r"^blocks(_device_major)?/", P("pp")),
            (r"wte/embedding$", P(None, "fsdp")),
            (r"lm_head/kernel$", P("fsdp", None)),
            (r".*", P()),
        ],
        default_spec=P(),
    )


# -- model-zoo contract -----------------------------------------------------

def mesh_config(num_devices):
    from elasticdl_tpu.parallel.mesh import MeshConfig

    pp = 4 if num_devices % 4 == 0 else (2 if num_devices % 2 == 0 else 1)
    return MeshConfig(dp=num_devices // pp, pp=pp)


def custom_model(mesh=None):
    num_layers = 12
    num_stages = 1
    if mesh is not None:
        num_stages = max(mesh.shape.get("pp", 1), 1)
    if num_layers % num_stages != 0:
        raise ValueError(
            "pipeline_transformer has %d layers; mesh pp extent %d does "
            "not divide it — pick pp in {1,2,3,4,6,12}"
            % (num_layers, num_stages)
        )
    return PipelinedTransformerLM(
        vocab_size=32000,
        num_layers=num_layers,
        num_stages=num_stages,
        num_heads=12,
        embed_dim=768,
        mesh=mesh,
    )


loss = transformer.loss
optimizer = transformer.optimizer
dataset_fn = transformer.dataset_fn
eval_metrics_fn = transformer.eval_metrics_fn


def sharding_rules():
    return pipeline_sharding_rules()
