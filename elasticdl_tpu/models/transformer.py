"""Decoder-only transformer LM — the long-context model family.

No reference counterpart: the reference zoo is CTR/vision Keras models
(SURVEY.md §2.11) with no attention; this family exists to exercise the
TPU-first capabilities the rebuild adds — flash attention (Pallas),
tensor parallelism (GSPMD rules below), and sequence/context parallelism
(ring / all-to-all schedules over the ``sp`` mesh axis).

Design notes (TPU-first):
- pre-LayerNorm blocks, GELU MLP, rotary position embeddings — all
  position-wise ops GSPMD shards trivially over dp/sp.
- attention dispatches by config: single-device flash/XLA, or ring /
  ulysses shard_map schedules when the mesh has sp > 1.
- tensor parallelism is pure annotation: qkv/mlp-up kernels split their
  output dim over ``tp``, out-proj/mlp-down split their input dim, so
  XLA inserts one psum per block (Megatron layout, expressed as GSPMD
  rules instead of hand-written collectives).
- the activations' layout is stated too (``constrain``): batch over the
  data axes, sequence over ``sp``, features over ``tp`` where a kernel
  splits them. Parameter rules alone leave it to propagation, which
  under ``fsdp`` moved the batch instead of the weights.
"""

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.data.example import decode_example
from elasticdl_tpu.ops import (
    gated_delta,
    gated_norm,
    hyper_connection,
    qkv_conv,
    short_conv,
    sparse_attention,
    ssd,
)
from elasticdl_tpu.ops.attention import dot_product_attention
from elasticdl_tpu.ops.ring_attention import (
    ring_attention,
    ulysses_attention,
)
from elasticdl_tpu.ops.rotary import (  # noqa: F401 - this module's names
    rotary_embedding,
    rotate,
    yarn_frequencies,
    yarn_mscale,
)
from elasticdl_tpu.parallel.mesh import DATA_AXES
from elasticdl_tpu.parallel.sharding import ShardingRules, constrain
from elasticdl_tpu.train import metrics
from elasticdl_tpu.train.losses import sparse_softmax_cross_entropy
from elasticdl_tpu.train.optimizers import create_optimizer


# Where the activations live (parallel/sharding.py:constrain): the
# batch over the data axes and the sequence over sp, as batch_spec()
# has the tokens. The residual stream (B, S, D) keeps its features
# whole; the MLP hidden and the logits (B, S, F) split theirs over tp
# like the kernels that produce them. Under fsdp the weights then move
# to the activations and never the other way round.
RESIDUAL_SPEC = P(DATA_AXES, "sp", None)
HIDDEN_SPEC = P(DATA_AXES, "sp", "tp")


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A ``rope_scaling`` of ``type`` ``yarn`` as DeepSeek-V3's
    ``config.json`` names its keys (arXiv:2309.00071)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MixerKind:
    """What a KIND of softmax layer has of its own in a model that
    mixes kinds (``MoeTransformerLM.kind_fields``): its query heads,
    its rotary base, how many lanes of a head rotate (None: all), YaRN
    over those lanes (None: none), and the window of a sliding-window
    layer (None: the whole causal prefix). Laguna-XS.2: ``full`` 48
    heads, 500,000, 64 of 128 lanes under YaRN; ``window`` 64 heads,
    10,000, the whole head, 512."""

    num_heads: int
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None
    rope_scaling: Optional[YarnScaling] = None
    window: Optional[int] = None

    def __str__(self):
        return " ".join(filter(None, (
            "heads=%d" % self.num_heads,
            "theta=%g" % self.rope_theta,
            self.rotary_dim and "rotary=%d" % self.rotary_dim,
            self.rope_scaling and "yarn=%g" % self.rope_scaling.factor,
            self.window and "window=%d" % self.window)))


@dataclasses.dataclass(frozen=True)
class IndexerDims:
    """A learned sparse-attention indexer (DeepSeek Sparse Attention,
    the DeepSeek-V3.2-Exp report; Keye-VL-2.0's ``sa_config``):
    ``heads`` query heads of ``head_dim`` over ONE key a position, and
    the ``topk`` keys a query keeps (``ops/sparse_attention.py``)."""

    heads: int
    head_dim: int
    topk: int

    def __str__(self):
        return "indexer heads=%d dim=%d topk=%d" % (
            self.heads, self.head_dim, self.topk)


@dataclasses.dataclass(frozen=True)
class LoopedDims:
    """A looped stack (weight-shared depth; Ouro's ``total_ut_steps``):
    the model's blocks run ``passes`` times over ONE set of parameters,
    the final norm ends every pass, one gate shared by the passes gives
    every position a distribution over the ``passes`` exits, and the
    loss is the expected cross-entropy over the exits less ``beta`` x
    that distribution's entropy (``MoeTransformerLM.looped``)."""

    passes: int
    beta: float

    def __str__(self):
        return "looped passes=%d beta=%g" % (self.passes, self.beta)


class ZeroCentredRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * (1 + w)`` over the last axis, ``w``
    starting at 0 (Qwen3-Next's norm: the scale is stored as its
    distance from 1, so weight decay pulls it toward 1 and not toward
    0). Statistics and the product in float32, rounded once."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        wide = x.astype(jnp.float32)
        var = jnp.mean(wide * wide, axis=-1, keepdims=True)
        return (
            wide * jax.lax.rsqrt(var + self.epsilon) * (1.0 + scale)
        ).astype(x.dtype)


class _NormScale(nn.Module):
    """The ``scale`` leaf of an ``nn.RMSNorm`` of this name over
    ``features`` lanes, alone: what ``ops/gated_norm.py``'s kernels are
    handed where they run a delta rule's output norm in its place."""

    features: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.features,))


def make_norm(kind, eps, name):
    """The block's normalisation by name: ``layernorm`` (scale and
    bias, the GPT-NeoX block's), ``rmsnorm`` (scale only, OLMoE's) or
    ``zero_centred_rmsnorm`` (scale ``1 + w``, Qwen3-Next's). All
    compute their statistics in float32."""
    if kind == "layernorm":
        return nn.LayerNorm(epsilon=eps, name=name)
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, name=name)
    if kind == "zero_centred_rmsnorm":
        return ZeroCentredRMSNorm(epsilon=eps, name=name)
    raise ValueError(
        "norm must be 'layernorm', 'rmsnorm' or 'zero_centred_rmsnorm', "
        "got %r" % (kind,)
    )


class Attention(nn.Module):
    num_heads: int
    attention_impl: str = "auto"  # auto | xla | pallas | ring | ulysses
    mesh: Optional[Any] = None
    dropout: float = 0.0
    rope_theta: float = 10000.0
    # RMSNorm over the WHOLE query and key projections (all heads
    # together, one scale of the model's width each) before the heads
    # are split and rotated: OLMoE's QK-norm (arXiv:2409.02060, 4.2.5)
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # Each of the five below defaults to what the GPT-NeoX and OLMoE
    # blocks get, whose programs lower as they did without them.
    # A head width of its own (None: model width / heads); Qwen3-Next:
    # 16 heads of 256 over a model width of 2048
    head_dim: Optional[int] = None
    # grouped-query attention: k and v have this many heads (None: as
    # many as q), query head h reads kv head h // (heads / kv heads);
    # the flash kernel reads them uncopied
    num_kv_heads: Optional[int] = None
    # a norm of this kind (``make_norm``) over the lanes of every query
    # head and of every key head, one scale each shared by the heads,
    # before the rotation (``q_norm`` / ``k_norm``, as ``qk_norm``,
    # which norms the whole projection, names its own)
    head_norm: Optional[str] = None
    # rotary on the first ``rotary_dim`` lanes of a head (None: all).
    # A layer that rotates NOTHING says so by ``rotary`` False below,
    # not by a width here: q and k then go to the softmax as the
    # projections made them and the layer carries no position
    rotary_dim: Optional[int] = None
    # "sigmoid": the query projection is twice as wide and its second
    # half, a gate of the head's width, multiplies the attention's
    # output through a sigmoid before the output projection
    output_gate: Optional[str] = None
    # the mask's layout (``ops/flash_attention.py``: ``BlockDiffusion``,
    # ``Band``) where it is not the causal diagonal; block diffusion's
    # ``__call__`` also says by ``positions`` what each row rotates by
    mask: Optional[Any] = None
    # YaRN (``YarnScaling``) on the lanes that rotate: the blended
    # frequency table over ``rotary_dim`` (None: the head's width) and
    # cos and sin times ``yarn_mscale(factor, mscale)`` over
    # ``yarn_mscale(factor, mscale_all_dim)``, as ``rotary_embedding``
    # has them; the lanes that pass through and the softmax scale are
    # left alone (the ``transformers`` library's generic ``yarn`` rope,
    # not DeepSeek-V3's, which ``LatentAttention`` follows)
    rope_scaling: Optional[YarnScaling] = None
    # a name for the mixer's kind in a model that mixes kinds
    # (``attn_full``, ``attn_window``): the mixer's operations then lie
    # under the named scopes ``<kind_scope>/qkv``, ``/rotary``,
    # ``/flash``, ``/gate`` and ``/out_proj``, forward and backward,
    # and its attention line starts ``heads=``.
    # None: the scopes are ``attn_full/...`` (ISSUE 62: a step's time
    # is read by them in every model), the line the mixer always had
    kind_scope: Optional[str] = None
    # a learned indexer picks the keys a query attends over
    # (``IndexerDims``; ``ops/sparse_attention.py``): three projections
    # and a LayerNorm of their own (``indexer_q``, ``indexer_k``,
    # ``indexer_k_norm``, ``indexer_w``) read the DETACHED input, the
    # softmax runs over the picked keys alone, and the call returns
    # ``(out, facts)``: the indexer's KL term a sample
    # (``indexer_loss``, which alone teaches those parameters) and the
    # ``dsa_select`` event's fields. None: the mixer, the tree and the
    # program the block always had
    indexer: Optional[IndexerDims] = None
    # False: q and k are not rotated at all (granite-4.0-h's
    # ``position_embedding_type`` ``nope``: the order of the tokens
    # comes from the model's recurrent layers), and no operation lies
    # under ``<kind_scope>/rotary``. True: the rotation every older
    # model has, its tree and its program letter for letter
    rotary: bool = True
    # the softmax scale where it is not ``head width ** -0.5``
    # (granite's ``attention_multiplier``, 1 / 64 at heads of 64);
    # both the flash kernels and the XLA path take it
    sm_scale: Optional[float] = None

    def _scoped(self, part):
        """The named scope of one part of the mixer."""
        return jax.named_scope(
            "%s/%s" % (self.kind_scope or "attn_full", part))

    @nn.compact
    def __call__(self, x, training=False, positions=None):
        dim = x.shape[-1]
        head_dim = self.head_dim or dim // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        if self.output_gate not in (None, "sigmoid"):
            raise ValueError(
                "output_gate must be None or 'sigmoid', got %r"
                % (self.output_gate,))
        if self.qk_norm and (
                self.head_norm or self.head_dim or self.num_kv_heads):
            raise ValueError(
                "qk_norm norms a projection of the model's width; a head "
                "width or kv head count of its own takes head_norm")

        def dense(name, norm=None, heads=self.num_heads, width=head_dim):
            out = nn.DenseGeneral(
                (heads, width),
                axis=-1,
                use_bias=False,
                name=name,
            )(x)
            if self.qk_norm and norm:
                out = nn.RMSNorm(epsilon=self.norm_eps, name=norm)(
                    out.reshape(x.shape)
                ).reshape(out.shape)
            return out

        def head_norm(t, name):
            if self.head_norm is None:
                return t
            return make_norm(self.head_norm, self.norm_eps, name)(t)
        # (B, S, H, d) -> (B, H, S, d). The model transposes because a
        # kernel that addressed heads inside the fused (H*d) minor dim
        # lost on the v5e: XLA's transposes run near the HBM roofline,
        # strided kernel DMA and the qkv matmuls' layouts cost more.
        # q/k/v are pinned to the layout the attention call declares,
        # (B, H, S, d) with batch over the data axes and heads over tp
        # (ring / ulysses: the sequence over sp as well), so its
        # shard_map meets operands that are already where it wants them
        sp = "sp" if self.attention_impl in ("ring", "ulysses") else None
        spec = P(DATA_AXES, "tp", sp, None)
        to_bhsd = lambda t: constrain(
            t.transpose(0, 2, 1, 3), self.mesh, spec
        )
        gate = None
        with self._scoped("qkv"):
            if self.output_gate:
                q = dense("query", width=2 * head_dim)
                q, gate = q[..., :head_dim], q[..., head_dim:]
            else:
                q = dense("query", "q_norm")
            q = to_bhsd(head_norm(q, "q_norm"))
            k = to_bhsd(head_norm(
                dense("key", "k_norm", heads=kv_heads), "k_norm"))
            v = to_bhsd(dense("value", heads=kv_heads))
        if self.rotary:
            with self._scoped("rotary"):
                q, k = rotate(
                    q, k, rotary_dim=self.rotary_dim, base=self.rope_theta,
                    positions=positions, scaling=self.rope_scaling,
                    mesh=self.mesh)
        elif (self.rotary_dim is not None or self.rope_scaling
              or positions is not None or self.indexer is not None):
            raise ValueError(
                "a layer that rotates nothing (rotary=False) has no "
                "rotary_dim, no rope_scaling, no positions of its own and "
                "no indexer (which rotates its own heads)")
        if self.sm_scale is not None and (
                self.indexer is not None
                or self.attention_impl in ("ring", "ulysses")):
            raise ValueError(
                "a softmax scale of its own (sm_scale) beside an indexer "
                "or under attention_impl='ring' / 'ulysses': not built, "
                "so not run")

        facts = None
        if self.indexer is not None:
            out, facts = self._attend_selected(x, q, k, v, positions)
        elif self.attention_impl in ("ring", "ulysses"):
            if (kv_heads != self.num_heads or self.mask is not None
                    or positions is not None):
                raise ValueError(
                    "attention_impl=%r takes equal head counts, the "
                    "causal mask and the rows' own positions"
                    % (self.attention_impl,))
            schedule = (ring_attention if self.attention_impl == "ring"
                        else ulysses_attention)
            out = schedule(q, k, v, self.mesh, causal=True)
        else:
            rotary = (self.rotary_dim or head_dim) if self.rotary else 0
            note = " ".join(filter(None, (
                self.kind_scope and "heads=%d" % self.num_heads,
                self.output_gate and "gate=%s" % self.output_gate,
                (self.rotary_dim or self.rope_scaling or self.kind_scope
                 or not self.rotary) and "rotary=%d/%d" % (rotary, head_dim),
                self.rope_scaling and "yarn=%g" % self.rope_scaling.factor,
                self.sm_scale is not None and "scale=%g" % self.sm_scale,
            )))
            with self._scoped("flash"):
                out = dot_product_attention(
                    q, k, v, causal=True, impl=self.attention_impl,
                    mesh=self.mesh, spec=spec, note=note, mask=self.mask,
                    sm_scale=self.sm_scale,
                )
        with self._scoped("gate"):
            out = out.transpose(0, 2, 1, 3)  # back to (B, S, H, d)
            if gate is not None:
                out = out * jax.nn.sigmoid(gate)
        with self._scoped("out_proj"):
            out = nn.DenseGeneral(
                dim, axis=(-2, -1), use_bias=False, name="out_proj"
            )(out)
        if self.dropout:
            out = nn.Dropout(
                self.dropout, deterministic=not training
            )(out)
        return out if facts is None else (out, facts)

    def _attend_selected(self, x, q, k, v, positions):
        """``(out (B, H, S, d), facts)`` where an indexer picks each
        query's keys. The indexer reads ``stop_gradient(x)``: its
        parameters learn from ``facts["indexer_loss"]`` alone, and that
        term reaches nothing else. The scopes ``dsa/indexer_proj``,
        ``dsa/scores``, ``dsa/select``, ``dsa/attend`` and
        ``dsa/indexer_loss`` hold its operations, forward and
        backward."""
        idx = self.indexer
        if (self.attention_impl in ("ring", "ulysses")
                or self.mask is not None or positions is not None
                or self.rotary_dim is not None or self.rope_scaling
                or self.output_gate or self.dropout
                or (self.mesh is not None and self.mesh.size > 1)):
            raise ValueError(
                "%s selects keys for causal softmax attention on one "
                "device, the whole head rotated by the plain table: no "
                "other mask, ring / ulysses, partial rotary, YaRN, "
                "output gate, dropout or mesh of several devices" % (idx,))
        detached = jax.lax.stop_gradient(x)
        with jax.named_scope("dsa/indexer_proj"):
            qi = nn.DenseGeneral(
                (idx.heads, idx.head_dim), use_bias=False,
                name="indexer_q")(detached).transpose(0, 2, 1, 3)
            ki = nn.LayerNorm(epsilon=self.norm_eps, name="indexer_k_norm")(
                nn.Dense(idx.head_dim, use_bias=False, name="indexer_k")(
                    detached))
            # the whole indexer head rotates by the model's own table
            qi = rotary_embedding(qi, base=self.rope_theta)
            ki = rotary_embedding(ki[:, None], base=self.rope_theta)[:, 0]
            w = nn.Dense(idx.heads, use_bias=False, name="indexer_w")(
                detached).astype(jnp.float32) * (
                    idx.heads ** -0.5 * idx.head_dim ** -0.5)
        impl = self.attention_impl
        # for whoever asks with mutable=["intermediates"] (the
        # benchmark's reference check): the kept set and the scores of
        # a stated block of queries; nothing otherwise
        probe = self.is_mutable_collection("intermediates")
        out, kl, facts = sparse_attention.dsa_attention(
            q, k, v, qi, ki, w, idx.topk,
            impl=impl if impl in ("xla", "pallas") else "auto", probe=probe)
        for name in ("kept_bits", "kept_after", "scores_tail"):
            if name in facts:
                self.sow("intermediates", name, facts.pop(name))
        return out, {"indexer_loss": kl, **facts}


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """The widths of multi-head latent attention (DeepSeek-V2/V3,
    arXiv:2412.19437 2.1.1) as a model's ``config.json`` names them.
    ``q_lora_rank`` None (``null`` in the file) is the form without a q
    latent, Moonlight-16B-A3B's: one ``q_proj`` from the model's width.
    A rank makes the query ``RMSNorm(x W_qa) W_qb`` (DeepSeek-V3's own,
    Xing4.0's at 768). ``rotary`` False (Kimi Linear's ``mla_use_nope``
    true) leaves the ``qk_rope_head_dim`` lanes of q and of the shared
    key head where they are and rotates nothing: the layer then carries
    no position (the model's recurrent layers do)."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: Optional[int] = None
    rotary: bool = True


class LatentAttention(nn.Module):
    """Multi-head latent attention, the training form (nothing is
    absorbed). For one token x, in the compute dtype, the norm's
    statistics in float32:

        q            = x W_q                 -> H heads x (nope | rope)
                       (with a q latent: c_q = RMSNorm(x W_qa), q_lora_rank
                       wide, and q = c_q W_qb)
        c            = x W_kva               -> kv_lora_rank | rope
        c_kv, k_rope = RMSNorm(c[:rank]), c[rank:]
        k_nope | v   = c_kv W_kvb            -> H heads x (nope | v)
        q_rope, k_rope rotated over their ``rope`` lanes (left as they
        are where ``dims.rotary`` is False); k_rope is ONE head, shared
        by all H
        q = [q_nope | q_rope], k = [k_nope | k_rope]   (nope + rope wide)
        o = causal softmax(q k^T / sqrt(nope + rope)) v -> H x v -> W_o

    ``rope_scaling`` (``YarnScaling``) rotates by YaRN's frequency
    table and, as the published DeepSeek-V3 code does whenever a
    scaling is set, multiplies the softmax scale by ``yarn_mscale(
    factor, mscale_all_dim)`` squared.

    The flash kernel takes q and k of one width and v of another
    (``ops/flash_attention.py``). The scopes ``mla/q_proj``,
    ``mla/kv_down``, ``mla/kv_up``, ``mla/out_proj`` hold the five
    matmuls (``mla/q_down`` the q latent's down-projection and its
    norm, where there is one), ``mla/assemble`` what needs no FLOPs: the partial rotary,
    the broadcast of ``k_rope`` over the heads, the concatenations and
    the transposes. ``rotary_embedding`` rotates halves where the
    published code rotates interleaved pairs: with seeded weights a
    fixed permutation of the rope columns of ``W_q`` and ``W_kva``."""

    num_heads: int
    dims: LatentDims
    attention_impl: str = "auto"  # auto | xla | pallas
    mesh: Optional[Any] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    rope_scaling: Optional[YarnScaling] = None

    @nn.compact
    def __call__(self, x, training=False):
        if self.attention_impl in ("ring", "ulysses"):
            raise ValueError(
                "latent attention runs on one device's sequence; "
                "attention_impl=%r shards it" % (self.attention_impl,))
        heads, dims = self.num_heads, self.dims
        if self.rope_scaling is not None and not dims.rotary:
            raise ValueError(
                "latent attention that rotates nothing (rotary=False) "
                "has no rope_scaling")
        rank, nope = dims.kv_lora_rank, dims.qk_nope_head_dim
        rope = dims.qk_rope_head_dim
        spec = P(DATA_AXES, "tp", None, None)
        rotate = functools.partial(
            rotary_embedding, base=self.rope_theta,
            scaling=self.rope_scaling) if dims.rotary else (lambda t: t)
        sm_scale = None
        if self.rope_scaling is not None:
            sm_scale = (nope + rope) ** -0.5 * yarn_mscale(
                self.rope_scaling.factor,
                self.rope_scaling.mscale_all_dim) ** 2
        q_in = x
        if dims.q_lora_rank is not None:
            with jax.named_scope("mla/q_down"):
                q_in = nn.RMSNorm(epsilon=self.norm_eps, name="q_norm")(
                    nn.Dense(dims.q_lora_rank, use_bias=False,
                             name="q_down")(x))
        with jax.named_scope("mla/q_proj"):
            q = nn.DenseGeneral(
                (heads, nope + rope), use_bias=False, name="q_proj"
            )(q_in)
        with jax.named_scope("mla/kv_down"):
            c = nn.Dense(rank + rope, use_bias=False, name="kv_down")(x)
            c_kv = nn.RMSNorm(epsilon=self.norm_eps, name="kv_norm")(
                c[..., :rank])
        with jax.named_scope("mla/kv_up"):
            kv = nn.DenseGeneral(
                (heads, nope + dims.v_head_dim), use_bias=False,
                name="kv_up",
            )(c_kv)
        with jax.named_scope("mla/assemble"):
            q = q.transpose(0, 2, 1, 3)  # (B, H, S, nope + rope)
            q = jnp.concatenate([
                q[..., :nope], rotate(q[..., nope:]),
            ], axis=-1)
            kv = kv.transpose(0, 2, 1, 3)
            # one rotated head of keys, (B, 1, S, rope), for all H
            k_rope = rotate(c[:, None, :, rank:])
            k = jnp.concatenate([
                kv[..., :nope],
                jnp.broadcast_to(
                    k_rope, kv.shape[:3] + (rope,)),
            ], axis=-1)
            q = constrain(q, self.mesh, spec)
            k = constrain(k, self.mesh, spec)
            v = constrain(kv[..., nope:], self.mesh, spec)
        out = dot_product_attention(
            q, k, v, causal=True, impl=self.attention_impl,
            mesh=self.mesh, spec=spec, sm_scale=sm_scale,
        )
        with jax.named_scope("mla/assemble"):
            out = out.transpose(0, 2, 1, 3)  # back to (B, S, H, v)
        with jax.named_scope("mla/out_proj"):
            return nn.DenseGeneral(
                x.shape[-1], axis=(-2, -1), use_bias=False,
                name="out_proj",
            )(out)


@dataclasses.dataclass(frozen=True)
class GatedDeltaDims:
    """The sizes of a Gated DeltaNet mixer as Qwen3-Next's
    ``config.json`` names them (``linear_*``), and the chunk of the
    chunked rule (``ops/gated_delta.py``)."""

    num_key_heads: int
    num_value_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_kernel_dim: int
    chunk: int = gated_delta.DEFAULT_CHUNK


def _a_log_init(key, shape, dtype=jnp.float32):
    """log of a uniform draw in (0, 16): the published code's."""
    return jnp.log(jax.random.uniform(
        key, shape, dtype, minval=1e-4, maxval=16.0))


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet mixer (arXiv:2412.06464), Qwen3-Next's
    ``linear_attention`` layer, for one token x:

        q | k | v | z = x W_qkvz       (Hk x Dk, Hk x Dk, Hv x Dv, Hv x Dv)
        b | a         = x W_ba         (Hv each)
        [q | k | v]   = silu(causal depthwise conv over ``conv_kernel_dim``
                        tokens, no bias)
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)  (float32)
        q, k l2-normalised over their lanes (eps 1e-6), q scaled Dk^-1/2
        o = gated delta rule (``ops/gated_delta.py``: value head h reads
            key head h // (Hv / Hk))
        o = RMSNorm_Dv(o) w silu(z)    (per head, w starts at 1, float32)
        y = o W_o

    Scopes: ``gdn/in_proj`` (the two input matmuls), ``gdn/conv``,
    ``gdn/gates`` (beta, g and their transposes; on the XLA lines the
    l2 norms and the heads' transposes too), ``gdn/scan`` (the chunked
    rule, whole), ``gdn/out_norm``, ``gdn/out_proj``. What lies between
    the projection and the rule for q, k and v runs where
    ``ops/qkv_conv.py:conv_impl`` says, from the backend, the dtype,
    the heads' widths, the sequence and the mesh, no flag: on a TPU
    with heads of whole 128-lane rows the kernel pair ``qkv_conv_fwd``
    / ``qkv_conv_bwd`` under one VJP, both under ``gdn/conv`` (one read
    of ``qkvz``'s first columns where they lie, one write of q, k, v in
    the rule's layout); everywhere else the lines of
    ``conv_silu_xla`` (``gdn/conv``) and ``split_heads_xla``
    (``gdn/gates``). The log's ``linear attention conv ... impl=`` line
    says which. Likewise the output norm and its gate: where
    ``ops/gated_norm.py:gated_norm_impl`` says so the kernel pair
    ``gated_norm_fwd`` / ``gated_norm_bwd`` under ``gdn/out_norm`` (``o``
    read where the rule wrote it, z's columns read in place), the lines
    below elsewhere; the log's ``gated norm ... impl=`` line says
    which. The columns of ``in_proj_qkvz`` lie q | k | v | z where
    the published code interleaves them by key head: with seeded
    weights a fixed permutation."""

    dims: GatedDeltaDims
    norm_eps: float = 1e-6
    # the mesh the step is sharded over, if any: the rule's kernels
    # run on one device (``ops/gated_delta.py:scan_impl``)
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, training=False):
        dims = self.dims
        hk, hv = dims.num_key_heads, dims.num_value_heads
        dk, dv = dims.key_head_dim, dims.value_head_dim
        batch, seq, dim = x.shape
        key_dim, value_dim = hk * dk, hv * dv
        with jax.named_scope("gdn/in_proj"):
            qkvz = nn.Dense(
                2 * key_dim + 2 * value_dim, use_bias=False,
                name="in_proj_qkvz")(x)
            ba = nn.Dense(2 * hv, use_bias=False, name="in_proj_ba")(x)
        conv_dim = 2 * key_dim + value_dim
        impl = qkv_conv.conv_impl(
            x.dtype, dk, dv, seq, dims.conv_kernel_dim, mesh=self.mesh)
        segments = qkv_conv.rule_segments(seq, dims.chunk)
        qkv_conv.log_choice(
            hk, hv, dk, dims.conv_kernel_dim, impl, seq,
            qkv_conv.row_tile(seq // segments) if impl == "pallas" else None)
        with jax.named_scope("gdn/conv"):
            taps = self.param(
                "conv_kernel",
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (dims.conv_kernel_dim, conv_dim),
            ).astype(x.dtype)
            if impl == "pallas":
                q, k, v = qkv_conv.qkv_conv(
                    qkvz, taps, (hk, hv, dk), segments)
            else:
                qkv = qkv_conv.conv_silu_xla(qkvz, taps, conv_dim)
        with jax.named_scope("gdn/gates"):
            a_log = self.param("A_log", _a_log_init, (hv,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,))
            ba = ba.astype(jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
            if impl != "pallas":
                heads = qkv_conv.split_heads_xla
                q = heads(qkv[..., :key_dim], hk, dk, dk ** -0.5)
                k = heads(qkv[..., key_dim:2 * key_dim], hk, dk, 1.0)
                v = heads(qkv[..., 2 * key_dim:], hv, dv)
            g, beta = g.transpose(0, 2, 1), beta.transpose(0, 2, 1)
        with jax.named_scope("gdn/scan"):
            o = gated_delta.gated_delta_rule(
                q, k, v, g, beta, chunk=dims.chunk, mesh=self.mesh)
        norm_impl = gated_norm.choose(
            "norm_silu", o, qkvz, dv, hv, self.mesh, conv_dim,
            segments=segments)
        with jax.named_scope("gdn/out_norm"):
            if norm_impl == "pallas":
                o = gated_norm.gated_norm(
                    o, qkvz, _NormScale(dv, name="out_norm")(), "norm_silu",
                    dv, self.norm_eps, conv_dim, "gdn/out_norm",
                    segments=segments).reshape(batch, seq, hv, dv)
            else:
                z = qkvz[..., conv_dim:].reshape(batch, seq, hv, dv)
                o = nn.RMSNorm(epsilon=self.norm_eps, name="out_norm")(
                    o.transpose(0, 2, 1, 3))  # (B, S, Hv, Dv)
                o = (o.astype(jnp.float32)
                     * nn.silu(z.astype(jnp.float32))).astype(x.dtype)
        with jax.named_scope("gdn/out_proj"):
            return nn.DenseGeneral(
                dim, axis=(-2, -1), use_bias=False, name="out_proj")(o)


@dataclasses.dataclass(frozen=True)
class KdaDims:
    """The sizes of a Kimi Delta Attention mixer as Kimi Linear's
    ``config.json`` names them (``linear_attn_config``: ``num_heads``,
    ``head_dim``, ``short_conv_kernel_size``), the rank of its two
    low-rank gates (the published module's: ``head_dim``) and the chunk
    and the segment of the chunked rule (``ops/gated_delta.py``)."""

    num_heads: int
    head_dim: int
    conv_kernel_dim: int
    gate_rank: int
    chunk: int = gated_delta.DEFAULT_CHUNK
    segment: int = gated_delta.DEFAULT_SEGMENT


def _kda_a_log_init(key, shape, dtype=jnp.float32):
    """log of a uniform draw in (1, 16): the published module's."""
    return jnp.log(jax.random.uniform(
        key, shape, dtype, minval=1.0, maxval=16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of ``dt`` drawn log-uniformly in (0.001,
    0.1), floored at 1e-4 (Mamba2's draw, flash-linear-attention's for
    its delta-rule layers): ``softplus(dt_bias) = dt``."""
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, dtype, minval=np.log(1e-3), maxval=np.log(0.1))), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692; the
    published ``KimiDeltaAttention``), H heads of D lanes, key and value
    alike, for one token x:

        q | k | v = x W_qkv            (H x D each, no bias)
        [q | k | v] = silu(causal depthwise conv over ``conv_kernel_dim``
                      tokens, no bias)
        q, k l2-normalised over their lanes (eps 1e-6), q scaled D^-1/2
        g = -exp(A_log_h) softplus(W_fb (W_fa x) + dt_bias)   (float32)
            the LOG decay of each of a head's D key channels, a vector
            where Gated DeltaNet's is a number; ``W_fa`` d x rank,
            ``W_fb`` rank x H D, ``A_log`` (H,), ``dt_bias`` (H D,)
        beta = sigmoid(x W_b)                                 (float32, H)
        S <- Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t);
        S <- S + k_t u^T;  o_t = S^T q_t       (``ops/gated_delta.py``,
                                                 the decay's rank decides)
        o = RMSNorm_D(o) w sigmoid(W_gb (W_ga x))   (per head, float32)
        y = o W_o

    Scopes: ``kda/in_proj``, ``kda/conv`` (convolution, SiLU, l2 norms
    and the heads' split: ``ops/qkv_conv.py``, the kernel pair where
    ``conv_impl`` says so, its lines elsewhere), ``kda/gates`` (both
    low-rank gates, beta, their transposes and the layer's facts),
    ``kda/scan`` (the chunked rule, whole), ``kda/out_norm`` (the
    kernel pair of ``ops/gated_norm.py`` where ``gated_norm_impl`` says
    so, the lines elsewhere), ``kda/out_proj``. The three projections
    are one matmul whose columns lie q | k | v (the published module
    has three ``Linear``: with seeded weights the same function). Returns ``(y, facts)``:
    ``decay_mean`` / ``decay_min`` of ``exp(g)`` over tokens, heads and
    channels, ``underflow_share`` of the (chunk, head, channel) triples
    whose decay cumulated over the chunk is under ``e^-88`` (where
    ``exp(-G)`` leaves float32) and ``beta_mean``."""

    dims: KdaDims
    norm_eps: float = 1e-6
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, training=False):
        dims = self.dims
        heads, dim, taps = dims.num_heads, dims.head_dim, dims.conv_kernel_dim
        batch, seq, width = x.shape
        inner = heads * dim
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name)
        with jax.named_scope("kda/in_proj"):
            qkv = dense(3 * inner, "in_proj_qkv")(x)
        impl = qkv_conv.conv_impl(x.dtype, dim, dim, seq, taps, mesh=self.mesh)
        segments = qkv_conv.rule_segments(seq, dims.chunk, dims.segment)
        qkv_conv.log_choice(
            heads, heads, dim, taps, impl, seq,
            qkv_conv.row_tile(seq // segments) if impl == "pallas" else None)
        with jax.named_scope("kda/conv"):
            kernel = self.param(
                "conv_kernel",
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (taps, 3 * inner),
            ).astype(x.dtype)
            if impl == "pallas":
                q, k, v = qkv_conv.qkv_conv(
                    qkv, kernel, (heads, heads, dim), segments, "kda/conv")
            else:
                q, k, v = qkv_conv.qkv_conv_xla(
                    qkv, kernel, (heads, heads, dim))
        with jax.named_scope("kda/gates"):
            a_log = self.param("A_log", _kda_a_log_init, (heads,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
            f = dense(inner, "f_up")(dense(dims.gate_rank, "f_down")(x))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (f.astype(jnp.float32) + dt_bias).reshape(
                    batch, seq, heads, dim))
            beta = jax.nn.sigmoid(
                dense(heads, "b_proj")(x).astype(jnp.float32))
            z = dense(inner, "g_up")(dense(dims.gate_rank, "g_down")(x))
            g, beta = g.transpose(0, 2, 1, 3), beta.transpose(0, 2, 1)
            facts = jax.lax.stop_gradient(
                kda_gate_facts(g, beta, dims.chunk))
        with jax.named_scope("kda/scan"):
            o = gated_delta.gated_delta_rule(
                q, k, v, g, beta, chunk=dims.chunk, segment=dims.segment,
                mesh=self.mesh)
        norm_impl = gated_norm.choose(
            "norm_sigmoid", o, z, dim, heads, self.mesh, segments=segments)
        with jax.named_scope("kda/out_norm"):
            if norm_impl == "pallas":
                o = gated_norm.gated_norm(
                    o, z, _NormScale(dim, name="out_norm")(), "norm_sigmoid",
                    dim, self.norm_eps, 0, "kda/out_norm",
                    segments=segments).reshape(batch, seq, heads, dim)
            else:
                o = nn.RMSNorm(epsilon=self.norm_eps, name="out_norm")(
                    o.transpose(0, 2, 1, 3))  # (B, S, H, D)
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                    z.reshape(o.shape).astype(jnp.float32))).astype(x.dtype)
        with jax.named_scope("kda/out_proj"):
            return nn.DenseGeneral(
                width, axis=(-2, -1), use_bias=False, name="out_proj")(
                    o), facts


# below this a chunk's cumulated decay leaves float32 as ``exp(-G)``:
# the regime in which a factorised ``(K e^G)(K e^-G)^T`` dies
UNDERFLOW_LOG = -88.0


def kda_gate_facts(g, beta, chunk):
    """The ``kda_gates`` event's facts of one layer from its log decay
    ``g`` (B, H, S, D) and ``beta`` (B, H, S), float32."""
    batch, heads, seq, dim = g.shape
    whole = jnp.pad(g, ((0, 0), (0, 0), (0, -seq % chunk), (0, 0)))
    over_chunks = whole.reshape(batch, heads, -1, chunk, dim).sum(axis=3)
    decay = jnp.exp(g)
    return {
        "decay_mean": decay.mean(),
        "decay_min": decay.min(),
        "underflow_share": jnp.mean(
            (over_chunks < UNDERFLOW_LOG).astype(jnp.float32)),
        "beta_mean": beta.mean(),
    }


@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    """The sizes of a Mamba-2 mixer as granite-4.0-h's ``config.json``
    names them (``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
    ``mamba_n_groups``, ``mamba_d_conv``, ``mamba_chunk_size``), and the
    chunks of a checkpointed segment of the scan (``ops/ssd.py``).
    ``conv_bias`` and ``proj_bias`` are the published module's two
    switches: the convolution's bias is built, the projections' is
    not."""

    num_heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int
    chunk: int = ssd.DEFAULT_CHUNK
    segment: int = ssd.DEFAULT_SEGMENT
    conv_bias: bool = True


def _conv_bias_init(taps):
    """A uniform draw in +-``taps ** -0.5``: what the published module's
    ``nn.Conv1d`` draws its bias from (fan-in: a depthwise convolution's
    taps)."""
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, minval=-taps ** -0.5, maxval=taps ** -0.5)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer (arXiv:2405.21060; the ``transformers``
    library's ``GraniteMoeHybridMambaLayer``, Bamba's), H heads of P
    lanes over a state of N, ``B`` and ``C`` shared by the heads of a
    group, for one token x:

        z | xBC | dt = x W_in          (H P, H P + 2 groups N, H; no bias)
        xBC = silu(causal depthwise conv over ``conv_kernel`` tokens
              + bias);  x | B | C its three parts
        dt = softplus(dt + dt_bias);  a = -exp(A_log) dt     (float32, H)
        S <- exp(a_t) S + dt_t x_t B_t^T;  y_t = S C_t + D x_t
                                       (``ops/ssd.py``, a chunk at a time)
        y = RMSNorm_(H P)(y silu(z)) w (the gate BEFORE the norm, the
            norm over all H P lanes where there is one group, over a
            group's lanes where there are more; float32 statistics)
        out = y W_out

    Scopes: ``mamba/in_proj``, ``mamba/conv`` (convolution, bias, SiLU,
    the split), ``mamba/gates`` (softplus, ``A``, the log decay and the
    layer's facts), ``mamba/scan`` (the chunked scan and the skip,
    whole), ``mamba/out_norm`` (the kernel pair of
    ``ops/gated_norm.py`` where ``gated_norm_impl`` says so, taken by
    columns: the scan's chunks with their rows in the lanes; the lines
    elsewhere), ``mamba/out_proj``. The convolution is
    ``ops/qkv_conv.py:conv_silu_xla``'s lines with a bias (its kernel
    pair is laid out for the delta rules' heads). Returns ``(out,
    facts)``: ``dt_mean`` / ``dt_max`` of the step, ``decay_mean`` /
    ``decay_min`` of ``exp(a)`` over tokens and heads, and
    ``underflow_share`` of the (chunk, head) pairs whose decay cumulated
    over the chunk is under ``e^-88``."""

    dims: Mamba2Dims
    norm_eps: float = 1e-6
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, training=False):
        dims = self.dims
        heads, dim, state, groups = (
            dims.num_heads, dims.head_dim, dims.state, dims.groups)
        batch, seq, width = x.shape
        inner, conv_dim = heads * dim, heads * dim + 2 * groups * state
        if heads % groups:
            raise ValueError(
                "%d heads do not divide over %d groups" % (heads, groups))
        with jax.named_scope("mamba/in_proj"):
            zxbcdt = nn.Dense(
                inner + conv_dim + heads, use_bias=False, name="in_proj")(x)
        with jax.named_scope("mamba/conv"):
            taps = self.param(
                "conv_kernel",
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (dims.conv_kernel, conv_dim),
            ).astype(x.dtype)
            bias = self.param(
                "conv_bias", _conv_bias_init(dims.conv_kernel), (conv_dim,)
            ).astype(x.dtype) if dims.conv_bias else None
            xbc = qkv_conv.conv_silu_xla(
                zxbcdt[..., inner:inner + conv_dim], taps, conv_dim, bias)
            xs = xbc[..., :inner].reshape(batch, seq, heads, dim)
            b = xbc[..., inner:inner + groups * state].reshape(
                batch, seq, groups, state)
            c = xbc[..., inner + groups * state:].reshape(
                batch, seq, groups, state)
        with jax.named_scope("mamba/gates"):
            a_log = self.param("A_log", _kda_a_log_init, (heads,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
            skip = self.param("D", nn.initializers.ones, (heads,))
            dt = jax.nn.softplus(
                zxbcdt[..., inner + conv_dim:].astype(jnp.float32) + dt_bias)
            a = -jnp.exp(a_log) * dt
            facts = jax.lax.stop_gradient(
                mamba_gate_facts(dt, a, dims.chunk))
        with jax.named_scope("mamba/scan"):
            y = ssd.ssd_scan(
                xs, dt, a, b, c, skip, chunk=dims.chunk,
                segment=dims.segment, mesh=self.mesh)
        norm_impl = gated_norm.choose(
            "silu_norm", y, zxbcdt, inner // groups, groups, self.mesh,
            rows=dims.chunk)
        with jax.named_scope("mamba/out_norm"):
            scale = self.param(
                "out_norm_scale", nn.initializers.ones, (inner,))
            if norm_impl == "pallas":
                y = gated_norm.gated_norm(
                    y.reshape(batch, seq, inner), zxbcdt, scale, "silu_norm",
                    inner // groups, self.norm_eps, 0, "mamba/out_norm",
                    rows=dims.chunk)
            else:
                gated = (
                    y.astype(jnp.float32).reshape(batch, seq, inner)
                    * nn.silu(zxbcdt[..., :inner].astype(jnp.float32)))
                lanes = gated.reshape(batch, seq, groups, inner // groups)
                var = jnp.mean(lanes * lanes, axis=-1, keepdims=True)
                y = ((lanes * jax.lax.rsqrt(var + self.norm_eps)).reshape(
                    gated.shape) * scale).astype(x.dtype)
        with jax.named_scope("mamba/out_proj"):
            return nn.DenseGeneral(
                width, axis=(-2, -1), use_bias=False, name="out_proj")(
                    y.reshape(batch, seq, heads, dim)), facts


def mamba_gate_facts(dt, a, chunk):
    """The ``mamba_gates`` event's facts of one layer from its step
    ``dt`` and its log decay ``a`` (B, S, H), float32."""
    batch, seq, heads = a.shape
    whole = jnp.pad(a, ((0, 0), (0, -seq % chunk), (0, 0)))
    over_chunks = whole.reshape(batch, -1, chunk, heads).sum(axis=2)
    decay = jnp.exp(a)
    return {
        "dt_mean": dt.mean(),
        "dt_max": dt.max(),
        "decay_mean": decay.mean(),
        "decay_min": decay.min(),
        "underflow_share": jnp.mean(
            (over_chunks < UNDERFLOW_LOG).astype(jnp.float32)),
    }


@dataclasses.dataclass(frozen=True)
class ShortConvDims:
    """A gated short convolution mixer as LFM2's ``config.json`` names
    its one size: ``conv_L_cache``, the taps (3: a position reads itself
    and the two before it). The channels are the model's width;
    ``conv_bias`` true is not built."""

    taps: int


class ShortConv(nn.Module):
    """LFM2's ``conv`` mixer (``lfm2`` / ``lfm2_moe``'s ``ShortConv``),
    for the tokens x (S, d) of one sequence:

        B | C | X = x W_in            (d -> 3 d, no bias)
        y = C * conv_K(B * X)         (``ops/short_conv.py``: causal,
                                       depthwise over the d channels, K
                                       taps, no bias, no activation)
        out = y W_out                 (d -> d, no bias)

    No position enters it and no state beyond K - 1 positions. Scopes:
    ``short_conv/in_proj``, ``short_conv/gate`` (the two gates and the
    K shifted multiply-adds, forward and backward), ``short_conv/
    out_proj``. The gates run where ``ops/short_conv.py:conv_impl``
    says, from the backend, the dtype, the shapes and the mesh, no
    flag: on a TPU with channels in whole 128-lane rows the kernel pair
    ``short_conv_fwd`` / ``short_conv_bwd``, which read B, C and X
    where ``in_proj`` wrote them; everywhere else the module's lines.
    The log's ``short conv ... impl=`` line says which. The output
    projection's parameter is ``proj_out``: ``out_proj/kernel`` is the
    softmax mixers' (heads, width, d) in the sharding rules."""

    dims: ShortConvDims
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, training=False):
        dim = x.shape[-1]
        with jax.named_scope("short_conv/in_proj"):
            bcx = nn.Dense(3 * dim, use_bias=False, name="in_proj")(x)
        with jax.named_scope("short_conv/gate"):
            taps = self.param(
                "conv_kernel",
                nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (self.dims.taps, dim),
            ).astype(x.dtype)
            y = short_conv.gated_short_conv(bcx, taps, mesh=self.mesh)
        with jax.named_scope("short_conv/out_proj"):
            return nn.Dense(dim, use_bias=False, name="proj_out")(y)


@dataclasses.dataclass(frozen=True)
class HyperDims:
    """A residual path of ``streams`` streams mixed by manifold-
    constrained hyper-connections, as Xing4.0's ``config.json`` names
    the sizes: ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps`` and the
    clamp of ``H~_res`` (``mhc_h_res_clamp_min`` / ``_max``)."""

    streams: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    res_clamp: Any = (-30.0, 30.0)


# The streams (B, n, S, D): a token's n copies of the residual lie in
# n slabs, each laid out as the plain residual stream is
STREAMS_SPEC = P(DATA_AXES, None, "sp", None)
# the initial bias of the stream a sublayer reads and writes, and of
# the others (sigmoid(4) = 0.98, sigmoid(-4) = 0.018); the off-diagonal
# bias of H~_res (exp(-8) = 3e-4: H_res starts at the identity to 1e-3)
HC_BIAS_ON, HC_BIAS_OFF, HC_RES_OFF = 4.0, -4.0, -8.0
HC_GATE_INIT = 0.01


def sinkhorn(matrix, iters, eps):
    """``matrix`` (n, n, ...): positive entries, a matrix for every
    index of the trailing axes. ``iters`` times: every row over its sum
    + eps, then every column over its sum + eps. A ``lax.scan`` over the
    iterations: unrolled, the compilers' fusion passes duplicate the
    shared sums of twenty dependent steps without bound (the CPU's did
    not finish a 4 x 4 in 40 minutes), and on the chip the scan's
    ``unroll`` moves nothing (``scripts/mhc_coef.py``)."""

    def step(m, _):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        return m / (m.sum(axis=0, keepdims=True) + eps), None

    return jax.lax.scan(step, matrix, None, length=iters)[0]


class HyperConnection(nn.Module):
    """One sublayer's manifold-constrained hyper-connection (mHC,
    arXiv:2512.24880, over hyper-connections, arXiv:2409.19606). For a
    token's streams X (n x C) around a sublayer F:

        x~      = RMSNorm(vec(X))          over n C lanes, no weight
        H~_pre  = a_pre  (x~ P_pre)  + b_pre           (n)
        H~_post = a_post (x~ P_post) + b_post          (n)
        H~_res  = a_res  mat(x~ P_res) + b_res         (n x n)
        H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
        H_res = Sinkhorn(exp(clip(H~_res)))  rows, then columns, iters times
        u  = H_pre X;   y = F(norm(u))   (the block's own norm and F)
        X' = H_res X + H_post^T y

    ``__call__(streams)`` returns ``(u, write, facts)``: ``write(y)`` is
    ``X'``; ``facts`` the largest ``|row sum - 1|`` of ``H_res`` over
    the tokens and its mean diagonal. The coefficients are float32 from
    the matmul's accumulator on (the norm's division follows the
    matmul: the same value, one pass over X less); the two mixes read
    and write the streams' dtype and multiply-add in float32. The
    kernels ``p_pre`` / ``p_post`` / ``p_res`` are (n, C, .): stream m's
    rows of the (n C)-row matrix are ``p[m]``. Scopes: ``mhc/coef``,
    ``mhc/pre``, ``mhc/post``. ``select``: the stream this sublayer
    reads and writes at initialisation.

    Where ``ops/hyper_connection.py:mix_impl`` says ``pallas`` (a TPU,
    bfloat16 or float32 streams in whole 128-lane rows, a sequence the
    tile divides, one device or a region manual over ``mesh``; no
    switch) the same equations run as its ``mhc_...`` kernels: ``pre``
    makes the coefficients and ``u`` in one read of X and hands X
    through to ``write``'s ``post``, so that one backward kernel owns
    dX (that module's docstring). Everywhere else the lines below run
    as XLA fuses them. One log line says which."""

    dims: HyperDims
    select: int = 0
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, streams):
        dims, n = self.dims, self.dims.streams
        batch, held, seq, dim = streams.shape
        if held != n:
            raise ValueError(
                "a hyper-connection over %d streams got %d" % (n, held))
        f32 = jnp.float32
        kernel_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=(0, 1), out_axis=2)
        one_hot = lambda on, off: (
            lambda key, shape, dtype=f32: jnp.where(
                jnp.arange(n) == self.select % n, on, off).astype(dtype))
        res_init = lambda key, shape, dtype=f32: (
            HC_RES_OFF * (1.0 - jnp.eye(n))).astype(dtype)
        gate = nn.initializers.constant(HC_GATE_INIT)
        with jax.named_scope("mhc/coef"):
            kernel = jnp.concatenate([
                self.param("p_pre", kernel_init, (n, dim, n)),
                self.param("p_post", kernel_init, (n, dim, n)),
                self.param("p_res", kernel_init, (n, dim, n * n)),
            ], axis=-1).astype(streams.dtype)
            a_pre, a_post, a_res = (
                self.param(name, gate, ()).astype(f32)
                for name in ("a_pre", "a_post", "a_res"))
            b_pre = self.param(
                "b_pre", one_hot(HC_BIAS_ON, HC_BIAS_OFF), (n,)).astype(f32)
            b_post = self.param(
                "b_post", one_hot(0.0, HC_BIAS_OFF), (n,)).astype(f32)
            b_res = self.param("b_res", res_init, (n, n)).astype(f32)
            impl = hyper_connection.mix_impl(
                streams.dtype, n, dim, seq, self.mesh)
            hyper_connection.log_choice(
                n, dim, dims.sinkhorn_iters, impl, seq)
        if impl == "pallas":
            return self._by_kernels(
                streams, kernel, (a_pre, a_post, a_res),
                (b_pre, b_post, b_res))
        with jax.named_scope("mhc/coef"):
            wide = [streams[:, m].astype(f32) for m in range(n)]
            mean_square = sum(
                jnp.mean(w * w, axis=-1) for w in wide) / n  # (B, S)
            raw = sum(
                jnp.einsum("bsc,ck->bsk", streams[:, m], kernel[m],
                           preferred_element_type=f32)
                for m in range(n))
            raw = raw * jax.lax.rsqrt(mean_square + dims.eps)[..., None]
            # (n (n + 2), B, S): an entry of a coefficient is one small
            # array over the tokens, the sequence in the lanes
            raw = raw.transpose(2, 0, 1)
            spread = lambda b: b.reshape(b.shape + (1, 1))
            h_pre = jax.nn.sigmoid(a_pre * raw[:n] + spread(b_pre))
            h_post = 2.0 * jax.nn.sigmoid(
                a_post * raw[n:2 * n] + spread(b_post))
            h_res = sinkhorn(jnp.exp(jnp.clip(
                a_res * raw[2 * n:].reshape(n, n, batch, seq)
                + spread(b_res), *dims.res_clamp)),
                dims.sinkhorn_iters, dims.eps)
            facts = self._facts(h_res)
        with jax.named_scope("mhc/pre"):
            u = sum(
                h_pre[m][..., None] * wide[m] for m in range(n)
            ).astype(streams.dtype)

        def write(y):
            with jax.named_scope("mhc/post"):
                y_wide = y.astype(f32)
                return jnp.stack([
                    h_post[i][..., None] * y_wide + sum(
                        h_res[i, j][..., None] * wide[j] for j in range(n))
                    for i in range(n)], axis=1).astype(streams.dtype)

        return u, write, facts

    def _by_kernels(self, streams, kernel, gates, biases):
        """``__call__``'s results by ``ops/hyper_connection.py``'s
        kernels, under the same three scopes."""
        dims, n = self.dims, self.dims.streams
        with jax.named_scope("mhc/coef"):
            kt, gb = hyper_connection.operands(kernel, gates, biases)
        with jax.named_scope("mhc/pre"):
            u, carrier, coef = hyper_connection.pre(
                streams, kt, gb, (n, dims.sinkhorn_iters, dims.eps,
                                  tuple(dims.res_clamp)))
        with jax.named_scope("mhc/coef"):
            facts = self._facts(hyper_connection.h_res_of(coef, n))

        def write(y):
            with jax.named_scope("mhc/post"):
                return hyper_connection.post(carrier, y, coef)

        return u, write, facts

    def _facts(self, h_res):
        """Sows ``h_res`` (n, n, B, S) for whoever asks with
        ``mutable=["intermediates"]`` (the benchmark's reference check;
        nothing otherwise) and returns the sublayer's facts."""
        n = self.dims.streams
        self.sow("intermediates", "h_res", h_res)
        return jax.lax.stop_gradient({
            "row_err": jnp.abs(h_res.sum(axis=1) - 1.0).max(),
            "diag_mean": jnp.mean(
                jnp.stack([h_res[m, m] for m in range(n)])),
        })


def merge_hyper_facts(sublayers):
    """One block's ``mhc`` facts from its sublayers': the largest row
    error, the mean diagonal."""
    return {
        "row_err": jnp.stack([f["row_err"] for f in sublayers]).max(),
        "diag_mean": jnp.stack([f["diag_mean"] for f in sublayers]).mean(),
    }


# ``Attention``'s own fields, which a latent mixer has none of
SOFTMAX_ONLY = (
    "qk_norm", "dropout", "head_dim", "num_kv_heads", "head_norm",
    "rotary_dim", "output_gate", "mask", "kind_scope", "indexer",
    "sm_scale")


def make_attention(num_heads, latent=None, linear=None, conv=None,
                   kda=None, mamba=None, **fields):
    """The block's mixer, ``name="attn"``, by the layer's kind:
    ``ShortConv`` where the layer is a gated short convolution
    (``conv``: its ``ShortConvDims``), ``GatedDeltaNet`` where it is a
    linear-attention one (``linear``: its ``GatedDeltaDims``),
    ``KimiDeltaAttention`` where it is a Kimi Delta Attention one
    (``kda``: its ``KdaDims``), ``Mamba2Mixer`` where it is a Mamba-2
    state-space one (``mamba``: its ``Mamba2Dims``), ``LatentAttention``
    where the model names latent widths (``LatentDims``), else
    ``Attention``.
    ``fields``: ``norm_eps``, which all take; what the two softmax ones
    take (``rope_theta``, ``rope_scaling``: YaRN, each by its own
    convention); and what only ``Attention`` has (``SOFTMAX_ONLY``: the
    grouped-query fields, the mask's layout, the kind's scope). A
    Gated DeltaNet, a Kimi Delta Attention, a Mamba-2 mixer and a short
    convolution rotate nothing and mask nothing, and say so."""
    recurrent = [
        what for what, dims in (
            ("a gated short convolution", conv),
            ("a Gated DeltaNet mixer", linear),
            ("a Kimi Delta Attention mixer", kda),
            ("a Mamba-2 mixer", mamba)) if dims is not None]
    if len(recurrent) > 1:
        raise ValueError(
            "one layer is %s: make_attention takes one of conv, linear, "
            "kda and mamba" % " and ".join(recurrent))
    for what in recurrent:
        for name in ("mask", "rope_scaling", "indexer", "sm_scale"):
            if fields.get(name) is not None:
                raise ValueError("%s has no %s" % (what, name))
    if conv is not None:
        return ShortConv(conv, mesh=fields.get("mesh"), name="attn")
    if linear is not None:
        return GatedDeltaNet(
            linear, norm_eps=fields["norm_eps"], mesh=fields.get("mesh"),
            name="attn")
    if kda is not None:
        return KimiDeltaAttention(
            kda, norm_eps=fields["norm_eps"], mesh=fields.get("mesh"),
            name="attn")
    if mamba is not None:
        return Mamba2Mixer(
            mamba, norm_eps=fields["norm_eps"], mesh=fields.get("mesh"),
            name="attn")
    if latent is None:
        return Attention(num_heads, name="attn", **fields)
    for name in SOFTMAX_ONLY:
        if fields.pop(name, None):
            raise ValueError("latent attention has no %s" % name)
    if not fields.pop("rotary", True):
        raise ValueError(
            "latent attention rotates nothing by LatentDims.rotary, not "
            "by the softmax mixer's rotary")
    return LatentAttention(num_heads, latent, name="attn", **fields)


class Block(nn.Module):
    """The residual block of every language model here: a mixer, then a
    second sublayer, each behind its own norm (``ln_attn``, ``ln_mlp``);
    or ONE of the two behind the one norm ``ln`` (``only``).

    ``mixer`` is what ``make_attention`` takes beside the block's own
    ``mesh`` and ``norm_eps``, as one mapping the block does not open
    (the model's ``_mixer`` builds it). The second sublayer is the
    experts' layer ``MoeMlp(name="moe_mlp")`` where ``experts`` holds
    its fields (one mapping again, built from ``EXPERT_FIELDS``), else
    a dense MLP in the block's own scope: ``mlp_act`` "gelu" (up, GELU,
    down) or "swiglu" (silu(gate) x up, down) of width ``mlp_dim``
    (``mlp_ratio x dim`` when None), under the scope ``dense_mlp``.

    ``sandwich``: a second norm a sublayer, on its OUTPUT and inside
    the residual branch (``ln_attn_out``, ``ln_mlp_out``): ``x +
    norm_out(f(norm(x)))``, Ouro's block. False: no such norm, the tree
    and the program the block always had.

    ``hc``: a hyper-connected residual path. ``x`` is then the n
    streams (B, n, S, D) and each sublayer goes through a
    ``HyperConnection`` (``hc_attn``, ``hc_mlp``). None: ``x +
    f(norm(x))``, no module, the tree and the program the block always
    had.

    Returns ``(x, aux)``. ``aux`` is empty for a dense block on the
    plain path and holds ``mhc`` (the block's facts) under
    hyper-connections, ``dsa`` where the mixer's indexer hands out its
    facts, ``kda`` where the mixer is a Kimi Delta Attention (its
    gates' facts), ``mamba`` where it is a Mamba-2 mixer (its gates'
    facts), and the experts' own keys (``MoeMlp``) where there
    are experts.

    ``residual_scale``: both branches are multiplied by it before they
    are added (``x + s f(norm(x))``: granite's ``residual_multiplier``).
    None: ``x + f(norm(x))``, the program every block always had.

    ``only``: a layer of ONE sublayer, ``x + f(norm(x))`` behind the
    one norm ``ln`` (Nemotron-H's stack, where a layer is a mixer, an
    expert layer or attention and never two of them): ``"mixer"`` (the
    mixer alone, ``attn``; nothing of a second sublayer is built) or
    ``"second"`` (the second sublayer alone, ``moe_mlp`` or the dense
    MLP; ``mixer`` is then None and no mixer is built). None: both, the
    tree and the program the block always had."""

    mixer: Any
    experts: Optional[Any] = None
    mlp_act: str = "gelu"
    mlp_dim: Optional[int] = None
    mlp_ratio: int = 4
    dropout: float = 0.0
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    hc: Optional[HyperDims] = None
    layer_index: int = 0
    mesh: Optional[Any] = None
    sandwich: bool = False
    residual_scale: Optional[float] = None
    only: Optional[str] = None

    def _dense_mlp(self, h, training):
        """The dense second sublayer, ``(y, {})`` as the experts' is
        ``(y, aux)``."""
        width = self.mlp_dim or h.shape[-1] * self.mlp_ratio
        # one scope for the MLP's operations, forward and backward
        # (a trace reads a dense block's share of a step by it)
        with jax.named_scope("dense_mlp"):
            if self.mlp_act == "swiglu":
                gate = nn.Dense(width, use_bias=False, name="mlp_gate")(h)
                gate = constrain(gate, self.mesh, HIDDEN_SPEC)
            y = nn.Dense(width, use_bias=False, name="mlp_up")(h)
            y = constrain(y, self.mesh, HIDDEN_SPEC)
            y = nn.silu(gate) * y if self.mlp_act == "swiglu" else nn.gelu(y)
            y = nn.Dense(h.shape[-1], use_bias=False, name="mlp_down")(y)
            if self.dropout:
                y = nn.Dropout(self.dropout, deterministic=not training)(y)
            return y, {}

    @nn.compact
    def __call__(self, x, training=False, positions=None):
        if self.mlp_act not in ("gelu", "swiglu"):
            raise ValueError(
                "mlp_act must be 'gelu' or 'swiglu', got %r"
                % (self.mlp_act,))
        if self.only not in (None, "mixer", "second") or (
                (self.only == "second") != (self.mixer is None)):
            raise ValueError(
                "only=%r: None (both sublayers), 'mixer' or 'second', and "
                "a layer that is its second sublayer alone has no mixer "
                "(mixer=None)" % (self.only,))
        mixer = None if self.mixer is None else make_attention(
            mesh=self.mesh, norm_eps=self.norm_eps, **self.mixer)
        second = self._dense_mlp
        if self.experts is not None and self.only != "mixer":
            # the layer lives beside the model that names its fields,
            # and that module imports this one
            from elasticdl_tpu.models.moe_transformer import MoeMlp

            second = MoeMlp(mesh=self.mesh, name="moe_mlp", **self.experts)
        aux = {}

        def mix(h):
            # only ``Attention`` takes the rows' positions, and with an
            # indexer it hands its facts out beside its output, as a
            # Kimi Delta Attention and a Mamba-2 mixer always do
            out = mixer(h, training, *(
                () if positions is None else (positions,)))
            if isinstance(out, tuple):
                key = {KimiDeltaAttention: "kda", Mamba2Mixer: "mamba"}.get(
                    type(mixer), "dsa")
                out, aux[key] = out
            return out

        def norm(name):
            """A norm of the block under the scope ``residual/norm``
            (names only: ``observability/scopes.py``)."""
            module = make_norm(self.norm, self.norm_eps, name)

            def scoped(h):
                with jax.named_scope("residual/norm"):
                    return module(h)
            return scoped

        # a sublayer's output norm, where the block has one
        after = lambda name: norm(name) if self.sandwich else (lambda y: y)
        scaled = (lambda y: y) if self.residual_scale is None else (
            lambda y: y * self.residual_scale)

        def add(x, y, name):
            """``x`` plus a sublayer's output ``y`` through its output
            norm ``name``, under the scope ``residual/add``."""
            y = after(name)(y)
            with jax.named_scope("residual/add"):
                return x + scaled(y)

        if self.only is not None:
            if self.hc is not None or self.sandwich:
                raise ValueError(
                    "a layer of one sublayer (only=%r) under "
                    "hyper-connections (hc) or with sandwich norms "
                    "(sandwich): not built, so not run" % (self.only,))
            x = constrain(x, self.mesh, RESIDUAL_SPEC)
            h = norm("ln")(x)
            y, of_second = (
                (mix(h), {}) if self.only == "mixer"
                else second(h, training))
            return constrain(
                add(x, y, "ln_out"), self.mesh,
                RESIDUAL_SPEC), {**of_second, **aux}
        if self.hc is None:
            x = constrain(x, self.mesh, RESIDUAL_SPEC)
            x = add(x, mix(norm("ln_attn")(x)), "ln_attn_out")
            y, of_second = second(norm("ln_mlp")(x), training)
            return constrain(
                add(x, y, "ln_mlp_out"), self.mesh,
                RESIDUAL_SPEC), {**of_second, **aux}
        if self.sandwich or self.residual_scale is not None:
            raise ValueError(
                "a sandwich-normed block (sandwich) or a scaled residual "
                "branch (residual_scale) under hyper-connections (hc): "
                "not built, so not run")
        x = constrain(x, self.mesh, STREAMS_SPEC)
        u, write, attn_facts = HyperConnection(
            self.hc, 2 * self.layer_index, self.mesh, name="hc_attn")(x)
        x = write(mix(norm("ln_attn")(u)))
        u, write, mlp_facts = HyperConnection(
            self.hc, 2 * self.layer_index + 1, self.mesh, name="hc_mlp")(x)
        y, of_second = second(norm("ln_mlp")(u), training)
        aux["mhc"] = merge_hyper_facts([attn_facts, mlp_facts])
        return constrain(write(y), self.mesh, STREAMS_SPEC), {
            **of_second, **aux}


def remat_block(block_cls, remat_policy, attention_impl):
    """``block_cls`` under per-block rematerialization (jax.checkpoint)
    with the policy of that name; shared by the dense and the MoE LM.

    "full" recomputes everything. "dots" saves matmul outputs and
    recomputes only elementwise work; it also saves the flash kernel's
    (o, lse) named outputs, without which remat re-runs the forward
    flash pass inside every block's backward (flash_attention.py
    "custom_vjp wrapper" note), and the sorted MoE path's named values
    (ops/moe.py ``MOE_SAVE_NAMES``: the grouped matmuls' outputs and
    the routing, which are no ``dot_general`` and would otherwise be
    recomputed). "flash" saves ONLY the flash kernel's named outputs:
    the projections/mlp recompute like "full", but the O(S^2)
    attention forward never re-runs, the middle ground for lengths
    where "dots" exceeds HBM (16k on one chip: PERF.md Section 4). The
    chunked gated delta rule names its output too
    (``ops/gated_delta.py:GDN_OUT_NAME``) and no policy here keeps it:
    its backward rebuilds its segments' residuals either way.

    A hyper-connected block's input is the n streams, ``(B, n, S, D)``:
    what a policy saves a layer is n times as wide as a plain block's
    (117 MB at 4 x 4,096 x 3,584 bfloat16), and "dots" also keeps the
    hyper-connections' 24-wide projections (a few MB)."""
    import jax

    from elasticdl_tpu.ops.flash_attention import (
        FLASH_LSE_NAME,
        FLASH_OUT_NAME,
    )
    from elasticdl_tpu.ops.moe import MOE_SAVE_NAMES
    from elasticdl_tpu.ops.sparse_attention import DSA_SAVE_NAMES

    if remat_policy not in ("full", "dots", "flash"):
        raise ValueError(
            "remat_policy must be 'full', 'dots' or 'flash', "
            "got %r" % (remat_policy,)
        )
    if remat_policy == "dots":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                FLASH_OUT_NAME, FLASH_LSE_NAME, *MOE_SAVE_NAMES,
                *DSA_SAVE_NAMES
            ),
        )
    elif remat_policy == "flash":
        # only the pallas flash kernel tags its outputs with these
        # checkpoint_names (flash_attention.py:522-523); under any
        # other attention impl the policy would match nothing and
        # silently degrade to "full" — reject the contradiction
        # instead. "auto" stays allowed: it resolves to pallas on TPU
        # (the regime this policy exists for) and its CPU fallback to
        # xla is the documented degradation for tests.
        if attention_impl not in ("auto", "pallas"):
            raise ValueError(
                'remat_policy="flash" saves the pallas flash '
                "kernel's named outputs; attention_impl=%r "
                "never produces them (the policy would match "
                "nothing and degrade to \"full\")"
                % (attention_impl,)
            )
        # and where an indexer picks the keys, what its bisection found
        # and its own term with its cotangents (``DSA_SAVE_NAMES``)
        policy = jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT_NAME, FLASH_LSE_NAME, *DSA_SAVE_NAMES
        )
    else:
        policy = None
    return nn.remat(block_cls, static_argnums=(2,), policy=policy)


class TransformerLM(nn.Module):
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    embed_dim: int = 512
    mlp_ratio: int = 4
    dropout: float = 0.0
    attention_impl: str = "auto"
    mesh: Optional[Any] = None
    # per-block rematerialization: activations recomputed in the
    # backward pass instead of stored — the standard HBM-for-FLOPs trade
    # that makes long-sequence / deep configs fit (jax.checkpoint)
    remat: bool = False
    # remat policy: "full" recomputes everything (min memory, ~1/3 extra
    # FLOPs); "dots" saves matmul outputs and recomputes only elementwise
    # ops (LayerNorm/GELU/residual) — near-zero extra MXU work, which is
    # what keeps MFU high on memory-tight configs; "flash" saves only the
    # attention kernel's (o, lse) outputs — between the two: projections
    # recompute, the O(S^2) attention forward does not, for lengths where
    # "dots" exceeds HBM (PERF.md Section 4, `pythia1b-s16k`)
    remat_policy: str = "full"

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        # names only, as a block's (``observability/scopes.py``)
        with jax.named_scope("embed"):
            x = nn.Embed(
                self.vocab_size, self.embed_dim, name="wte"
            )(tokens.astype(jnp.int32))
            x = constrain(x, self.mesh, RESIDUAL_SPEC)
        if self.remat:
            block_cls = remat_block(
                Block, self.remat_policy, self.attention_impl
            )
        else:
            block_cls = Block
        mixer = dict(
            num_heads=self.num_heads, attention_impl=self.attention_impl,
            dropout=self.dropout)
        for i in range(self.num_layers):
            x, _ = block_cls(
                mixer,
                mlp_ratio=self.mlp_ratio,
                dropout=self.dropout,
                mesh=self.mesh,
                name="block_%d" % i,
            )(x, training)
        with jax.named_scope("final_norm"):
            x = constrain(
                nn.LayerNorm(name="ln_f")(x), self.mesh, RESIDUAL_SPEC)
        with jax.named_scope("head"):
            logits = nn.Dense(
                self.vocab_size, use_bias=False, name="lm_head"
            )(x)
            return constrain(logits, self.mesh, HIDDEN_SPEC)


# ---------------------------------------------------------------------------
# Sharding rules (tensor parallelism as pure annotation)
# ---------------------------------------------------------------------------


def transformer_sharding_rules():
    """Megatron-style TP layout + fsdp on everything big.

    qkv and mlp-up split output features over tp (their matmuls become
    local); out-proj and mlp-down split input features, after which XLA
    inserts a single psum per block. Embedding and lm_head split vocab.

    ``fsdp`` only says where a kernel is STORED. The model pins every
    activation's batch to the data axes (``RESIDUAL_SPEC``,
    ``HIDDEN_SPEC``, the attention call's spec), so whichever dimension
    of a kernel ``fsdp`` splits, the partitioner brings the weight to
    the activation: gathered whole (cast to the compute dtype first)
    before its matmul, its gradient reduced back onto the shard
    (ZeRO-3). Without those pins the split on a contracted dimension
    propagated into the residual stream: the batch was replicated and
    a full-batch activation all-reduced after every contraction
    (PERF.md, PR 24).
    """
    return ShardingRules(
        rules=[
            (r"(query|key|value)/kernel$", P("fsdp", "tp", None)),
            (r"out_proj/kernel$", P("tp", None, "fsdp")),
            (r"mlp_(gate|up)/kernel$", P("fsdp", "tp")),
            (r"mlp_down/kernel$", P("tp", "fsdp")),
            (r"wte/embedding$", P("tp", "fsdp")),
            (r"lm_head/kernel$", P("fsdp", "tp")),
            (r".*", P()),
        ],
        default_spec=P(),
    )


def batch_spec():
    """Tokens/labels (B, S): batch over data axes, sequence over sp."""
    return P(DATA_AXES, "sp")


# ---------------------------------------------------------------------------
# Model-zoo contract
# ---------------------------------------------------------------------------


def custom_model(mesh=None):
    return TransformerLM(
        vocab_size=32000,
        num_layers=12,
        num_heads=12,
        embed_dim=768,
        mesh=mesh,
    )


def loss(labels, predictions):
    # Next-token prediction: logits at t predict token at t+1. Returns a
    # per-sample vector (contract: trainer applies the batch mask).
    logits = predictions[:, :-1]
    targets = labels[:, 1:]
    per_token = sparse_softmax_cross_entropy(targets, logits)
    return per_token.mean(axis=-1)


def optimizer():
    return create_optimizer(
        "AdamW", learning_rate=3e-4, weight_decay=0.01
    )


def sharding_rules():
    return transformer_sharding_rules()


def dataset_fn(dataset, mode=None, metadata=None):
    def parse(payload):
        example = decode_example(payload)
        tokens = example["tokens"].astype(np.int32)
        # LM: the sequence is both input and label (shift happens in loss)
        return tokens, tokens

    return dataset.map(parse)


def eval_metrics_fn():
    return {"accuracy": metrics.Accuracy()}
