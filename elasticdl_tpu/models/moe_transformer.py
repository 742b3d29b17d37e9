"""Mixture-of-experts transformer LM — the expert-parallel model family.

No reference counterpart (SURVEY.md §2.12: EP absent from the reference);
this family exercises the ``ep`` mesh axis. Some blocks (every other one
by default, every one for OLMoE) swap the dense MLP for a top-k-routed
expert MLP (ops/moe.py). Two dispatches:

- one-hot with a capacity (the default): expert weight tensors carry a
  leading expert dim sharded over ``ep``, the dispatch/combine einsums
  become all-to-alls under GSPMD, and within each expert the FFN is
  still tensor-parallel over ``tp``;
- sorted and dropless (``dispatch_impl="sorted"``): the (token, choice)
  pairs ordered by expert around a grouped matmul. This is what a
  published small-expert model (OLMoE-1B-7B: 64 SwiGLU experts of width
  1024, top-8, nothing dropped) needs; the benchmark's
  ``olmoe-1b-7b-1chip`` configuration builds it on one chip, and
  ``mellum2-12b-a2.5b-ep4`` over ``--mesh ep=4``, where each rank sorts
  its own tokens and the rows are exchanged over ``ep``.
"""

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.data.example import decode_example
from elasticdl_tpu.models.transformer import (
    HIDDEN_SPEC,
    Block,
    GatedDeltaDims,
    HyperDims,
    IndexerDims,
    KdaDims,
    LatentDims,
    LoopedDims,
    Mamba2Dims,
    MixerKind,
    ShortConvDims,
    YarnScaling,
    make_norm,
    remat_block,
)
from elasticdl_tpu.ops import (
    block_diffusion,
    flash_attention,
    looped_exit,
    short_conv,
    sparse_attention,
)
from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.parallel.collectives import mesh_all_gather, mesh_psum
from elasticdl_tpu.parallel.mesh import DATA_AXES, REPLICA_AXES
from elasticdl_tpu.parallel.sharding import ShardingRules, constrain
from elasticdl_tpu.train import metrics
from elasticdl_tpu.train.losses import sparse_softmax_cross_entropy
from elasticdl_tpu.train.optimizers import create_optimizer

logger = _logger_factory("elasticdl_tpu.models.moe_transformer")


@functools.lru_cache(maxsize=None)
def _log_dispatch_once(impl, matmul, tokens, num_experts, top_k, width,
                       act, scoring, shared, held="", run=""):
    """One line per distinct expert layer shape (this runs at trace
    time), beside the compile ledger's line of the step, as
    ``ops/attention.py`` names the attention it resolved to. ``held``:
    what a layer that holds a share of its experts adds (`` held=0-31
    rows=65536 shared_gate=sigmoid``), and ``run`` after the line's
    bracket what it runs of that buffer (``; a step runs the rows that
    carry a held pair, up to rows=65536``: written at trace time, the
    line can name no count; the ``moe_routing`` event's
    ``held_rows_run`` does; over ``ep`` ``; a rank sorts its own 8192
    tokens and runs the rows it received, regroup=chunks of 4096``, and
    the event's ``received_rows_run``)."""
    logger.info(
        "moe dispatch resolved to %s (tokens=%d experts=%d top_k=%d "
        "expert_width=%d act=%s score=%s shared=%d%s, experts' matmul=%s)%s",
        impl, tokens, num_experts, top_k, width, act, scoring, shared,
        held, matmul, run,
    )


@functools.lru_cache(maxsize=None)
def _log_tiles_once(rows, num_experts, dim, width, dtype, act):
    """The Pallas grouped matmul's tiles, call by call, and the share
    of their tile work that is needed work: one line per distinct
    expert layer, after the dispatch's, only where that path runs."""
    into = moe_ops.projection_tiles(rows, dim, width, dtype)
    out = moe_ops.projection_tiles(rows, width, dim, dtype)
    num_in = 2 if act == "swiglu" else 1
    fill = (num_in + 1) / (
        num_in / moe_ops.projection_fill(dim, width, into)
        + 1 / moe_ops.projection_fill(width, dim, out))
    show = lambda tiles: " ".join(
        "%s=%s" % (call, tiles[call])
        for call in ("fwd", "d_rows", "d_weights"))
    logger.info(
        "moe experts' matmul tiles (rows=%d experts=%d dim=%d width=%d): "
        "%s %s, down %s, fill=%.2f%%",
        rows, num_experts, dim, width,
        "gate/up" if act == "swiglu" else "up", show(into), show(out),
        100 * fill,
    )


@functools.lru_cache(maxsize=None)
def _log_kinds_once(kinds):
    """One line at model build for a model that mixes kinds of layer:
    each kind with its count and what it has of its own."""
    logger.info("layer kinds: %s", ", ".join(
        "%s x%d (%s)" % kind for kind in kinds))


class MoeMlp(nn.Module):
    """Top-k routed expert FFN. Returns ``(y, aux)``: ``aux`` holds the
    layer's ``load_balancing`` and ``router_z`` losses (unweighted) and,
    on the sorted path, its ``routing`` counters (ops/moe.py
    ``routing_stats``; None on the one-hot path).

    ``dispatch_impl``:

    - ``"onehot"`` (= ``"auto"``) — GShard dispatch/combine einsums
      over a static capacity; tokens over it are dropped. The one-hot
      contraction is MXU work, and under GSPMD with tokens dp-sharded
      and experts ep-sharded these einsums ARE the dp→ep all-to-alls.
      Switch's auxiliary loss over first choices, gates renormalised.
    - ``"sorted"`` — dropless: sort the pairs by expert, gather, a
      grouped matmul over the ragged groups, gather back
      (ops/moe.py). No capacity, no one-hot, every token reaches
      exactly ``top_k`` experts. The load-balancing loss counts all
      ``top_k`` choices (OLMoE's). On a mesh whose ``ep`` axis is
      larger than 1 the layer's experts are spread over its ranks,
      ``num_experts / ep`` each, and the path runs in a region manual
      over the mesh (``_sorted_over_ep``): every rank routes and sorts
      its own tokens, the rows are exchanged over ``ep``
      (``ops/moe.py:exchange_rows``) into a buffer of ``exchange_rows``
      rows a rank (None: all the ranks' pairs, which no routing
      overfills; a stated size counts what it had no row for in
      ``routing["dropped"]``), run the Pallas grouped matmul there and
      come back; the balance loss, the counters and the balancing bias
      read the loads summed over the ranks. Such a mesh may divide
      over ``dp`` besides; an axis the region divides nothing over
      (``fsdp``, ``tp``, ``sp``, ``pp``) is refused by name, never
      served by a silent fallback.

    Experts are ``expert_act`` = ``"gelu"`` (up, GELU, down),
    ``"swiglu"`` (silu(gate) x up, down) or ``"relu2"`` (up, the square
    of its ReLU, down: two matrices and no gate, Nemotron-H's
    ``mlp_hidden_act``) of width ``expert_dim`` (``mlp_ratio x dim``
    when None). ``normalize_gates=False`` (the
    kept gates stay the softmax's own, OLMoE's ``norm_topk_prob``
    false) is the sorted path's; the one-hot path refuses it.

    The sorted path also serves DeepSeek-V3's layer (Moonlight-16B-A3B,
    arXiv:2412.19437 2.1.2): ``scoring="sigmoid"`` with ``gate_scale``
    (``routed_scaling_factor``); ``bias_update_speed`` not None keeps
    the balancing bias ``e_score_correction_bias`` (E,) in the
    non-gradient collection ``moe_state`` (``TrainState.model_state``
    carries it), adds it to the scores for the SELECTION and, in a
    training call that may write the collection, moves it by that
    speed toward the under-loaded experts, from the group sizes the
    step has just counted (``ops/moe.py:balancing_bias_update``);
    ``seq_aux`` makes ``aux["load_balancing"]`` the sequence-wise
    balance loss; ``shared_experts`` n adds one MLP of the experts' own
    body (SwiGLU or ``relu2``) and of width ``n x expert_dim`` that
    every token passes, under the scope ``moe/shared``.
    ``router_float32``: the router's product in float32 at the highest
    matmul precision (the published Nemotron-H router's; the scores and
    the top-k are float32 either way).

    A ``relu2`` layer's ``routing`` also counts ``relu2_active``, the
    share of its experts' hidden units above zero after the ReLU over
    the rows that carry a pair, and with shared experts
    ``relu2_shared_active``, the same of the shared expert's.

    ``held_experts`` ``(first, count)`` makes this one chip's share of
    a layer whose experts lie over several (the first half of expert
    parallelism; Qwen3-Next's 512 experts, 32 held): the router, the
    scores, the top-k and the gates' normalisation run over all
    ``num_experts``; ``w_gate / w_up / w_down`` have ``count`` rows;
    the pairs whose expert lives here are sorted and gathered into a
    buffer of ``held_rows`` rows (static; a held pair without a row is
    counted in ``routing["dropped"]``), and the combine adds this
    chip's experts' part and nothing for the absent ones. The buffer's
    size is memory and shapes only: a step RUNS the rows that carry a
    pair (the grouped matmuls to the last held row tile, the gathers
    and scatter-adds to the last chunk that holds one;
    ``routing["rows_run"]`` of ``routing["rows_buffer"]``), and a row
    past them may hold anything, a NaN too: ``hidden`` and ``out`` are
    read only through ``valid`` (``ops/moe.py:combine_held``, and
    ``dispatch_held`` for the gradient). No exchange, and nothing that
    stands in for one. ``shared_gate``: the shared
    experts' output passes ``sigmoid(x . w)``, a gate of one output
    (``shared_expert_gate``).
    """

    num_experts: int
    mlp_ratio: int = 4
    top_k: int = 2
    capacity_factor: float = 1.25
    dispatch_impl: str = "auto"
    mesh: Optional[Any] = None
    expert_dim: Optional[int] = None
    expert_act: str = "gelu"
    normalize_gates: bool = True
    scoring: str = "softmax"
    gate_scale: float = 1.0
    bias_update_speed: Optional[float] = None
    seq_aux: bool = False
    shared_experts: int = 0
    held_experts: Optional[Any] = None
    held_rows: Optional[int] = None
    shared_gate: bool = False
    exchange_rows: Optional[int] = None
    router_float32: bool = False

    def _expert_param(self, name, rows, cols):
        # the expert axis is a batch of kernels, not fan-in: without
        # batch_axis every expert started sqrt(E) times too small
        held = (self.num_experts if self.held_experts is None
                else self.held_experts[1])
        return self.param(
            name,
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (held, rows, cols),
        )

    def _weights(self, dim, dtype):
        """The experts' kernels in the compute dtype, input side first:
        (w_up, w_down) for GELU and ReLU squared, (w_gate, w_up, w_down)
        for SwiGLU."""
        if self.expert_act not in ("gelu", "swiglu", "relu2"):
            raise ValueError(
                "expert_act must be 'gelu', 'swiglu' or 'relu2', got %r"
                % (self.expert_act,)
            )
        ff = self.expert_dim or dim * self.mlp_ratio
        names = ["w_gate", "w_up"] if self.expert_act == "swiglu" else [
            "w_up"]
        return [
            self._expert_param(name, dim, ff).astype(dtype) for name in names
        ] + [self._expert_param("w_down", ff, dim).astype(dtype)]

    def _act(self, hidden):
        if self.expert_act == "gelu":
            return nn.gelu(hidden[0])
        if self.expert_act == "relu2":
            return jnp.square(nn.relu(hidden[0]))
        return nn.silu(hidden[0]) * hidden[1]

    def _active_share(self, hidden, valid=None):
        """Of a ``relu2`` body's hidden units (rows, width), the share
        above zero after the ReLU, float32, no gradient; over the rows
        ``valid`` marks where a buffer holds more rows than pairs (what
        a row past them holds is anyone's: ``MoeMlp``)."""
        active = jax.lax.stop_gradient(hidden) > 0
        if valid is None:
            return active.astype(jnp.float32).mean()
        active = active & valid[:, None]
        return active.sum(dtype=jnp.float32) / jnp.maximum(
            valid.sum(dtype=jnp.float32) * hidden.shape[-1], 1.0)

    @nn.compact
    def __call__(self, x, training=False):
        impl = self.dispatch_impl
        if impl not in ("auto", "onehot", "sorted"):
            raise ValueError(
                "dispatch_impl must be 'auto', 'onehot' or 'sorted', "
                "got %r" % (impl,)
            )
        if impl != "sorted" and (
            self.scoring != "softmax" or self.seq_aux
            or self.bias_update_speed is not None
        ):
            raise ValueError(
                "sigmoid scoring, the balancing bias and the "
                "sequence-wise balance loss need dispatch_impl=\"sorted\"")
        groups, seq, dim = x.shape
        with jax.named_scope("moe/router"):
            router_logits = nn.Dense(
                self.num_experts, use_bias=False, name="router",
                # (a layer without the switch builds the product it
                # always did)
                **({"dtype": jnp.float32,
                    "precision": jax.lax.Precision.HIGHEST}
                   if self.router_float32 else {})
            )(x)
        weights = self._weights(dim, x.dtype)
        one_device = jax_compat.nothing_to_partition(self.mesh)
        rows, width = groups * seq * self.top_k, weights[0].shape[-1]
        held = run = ""
        if self.held_experts is not None:
            if impl != "sorted" or not self.held_rows:
                raise ValueError(
                    "held_experts needs dispatch_impl=\"sorted\" and the "
                    "row buffer's size, held_rows")
            first, count = self.held_experts
            if not 0 <= first <= first + count <= self.num_experts:
                raise ValueError(
                    "held_experts=%r lies outside %d experts"
                    % (self.held_experts, self.num_experts))
            rows = min(self.held_rows, rows)
            held = " held=%d-%d rows=%d" % (first, first + count - 1, rows)
            run = ("; a step runs the rows that carry a held pair, up to "
                   "rows=%d" % rows)
        if self.shared_gate:
            held += " shared_gate=sigmoid"
        ranks = self._ranks() if impl == "sorted" else 1
        if ranks > 1:
            # a rank's own tokens and the rows it can receive: the
            # grouped matmuls run on those, each rank's alone
            own = groups // self.mesh.size * seq
            rows = self._received_rows(own * self.top_k)
            one_device = True
            held += " ep=%d held=%d received_rows=%d exchange=%s" % (
                ranks, self.num_experts // ranks, rows,
                moe_ops.resolve_exchange())
            run = ("; a rank sorts its own %d tokens and runs the rows "
                   "it received, regroup=chunks of %d"
                   % (own, moe_ops.held_chunk_rows(rows)))
        matmul = moe_ops.resolve_grouped_matmul(
            rows, x.dtype, one_device) if impl == "sorted" else "einsum"
        _log_dispatch_once(
            "sorted" if impl == "sorted" else "onehot", matmul,
            groups * seq, self.num_experts, self.top_k, width,
            self.expert_act, self.scoring, self.shared_experts, held, run,
        )
        if matmul == "pallas_gmm":
            _log_tiles_once(
                rows, weights[0].shape[0] // ranks, dim, width,
                x.dtype, self.expert_act)
        if impl == "sorted":
            y, aux = self._sorted(
                x, router_logits, weights, one_device, training)
        else:
            y, aux = self._onehot(x, router_logits, weights)
        with jax.named_scope("moe/router"):
            aux["router_z"] = moe_ops.router_z_loss(router_logits)
        if self.shared_experts:
            with jax.named_scope("moe/shared"):
                shared, active = self._shared(
                    x, self.shared_experts * weights[0].shape[-1])
                y = y + shared
            if active is not None and aux["routing"] is not None:
                aux["routing"]["relu2_shared_active"] = active
        return y, aux

    def _shared(self, x, width):
        """The shared experts: one MLP of the experts' own body and of
        their summed width on every token (n experts of one width side
        by side are one MLP of n times the width): ``shared_gate`` |
        ``shared_up`` | ``shared_down`` for SwiGLU, ``shared_up`` |
        ``shared_down`` for ReLU squared. Returns ``(y, the share of a
        relu2 body's hidden units above zero or None)``."""
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(
                "shared experts take the experts' body where it is "
                "SwiGLU or ReLU squared (expert_act 'swiglu', 'relu2'); "
                "a shared expert beside expert_act=%r was not built, so "
                "not run" % (self.expert_act,))
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, name=name)
        hidden = [
            constrain(dense(width, name)(x), self.mesh, HIDDEN_SPEC)
            for name in (("shared_gate", "shared_up")
                         if self.expert_act == "swiglu" else ("shared_up",))]
        y = dense(x.shape[-1], "shared_down")(self._act(hidden))
        if self.shared_gate:
            y = y * jax.nn.sigmoid(dense(1, "shared_expert_gate")(x))
        active = (
            self._active_share(hidden[0].reshape(-1, width))
            if self.expert_act == "relu2" else None)
        return y, active

    def _onehot(self, x, router_logits, weights):
        if not self.normalize_gates:
            raise ValueError(
                "normalize_gates=False needs dispatch_impl=\"sorted\": "
                "the one-hot routing (ops/moe.py:top_k_routing) always "
                "renormalises the gates it keeps"
            )
        _, seq, _ = x.shape
        capacity = moe_ops.expert_capacity(
            seq, self.num_experts, self.top_k, self.capacity_factor
        )
        with jax.named_scope("moe/router"):
            combine, dispatch, balance = moe_ops.top_k_routing(
                router_logits, self.top_k, capacity
            )
        with jax.named_scope("moe/dispatch"):
            # (E, G, C, M): the dispatch einsum is the dp→ep all-to-all.
            expert_in = constrain(
                moe_ops.moe_dispatch(x, dispatch),
                self.mesh, P("ep", REPLICA_AXES, None, None),
            )
        with jax.named_scope("moe/experts"):
            hidden = [
                jnp.einsum("egcm,emf->egcf", expert_in, w)
                for w in weights[:-1]
            ]
            out = jnp.einsum("egcf,efm->egcm", self._act(hidden), weights[-1])
            out = constrain(
                out, self.mesh, P("ep", REPLICA_AXES, None, None)
            )
        with jax.named_scope("moe/combine"):
            y = moe_ops.moe_combine(out, combine)  # ep→dp all-to-all back
        return y, {"load_balancing": balance, "routing": None}

    def _sorted(self, x, router_logits, weights, one_device, training):
        if self._ranks() > 1:
            return self._sorted_over_ep(x, router_logits, weights, training)
        groups, seq, dim = x.shape
        tokens = x.reshape(groups * seq, dim)
        logits = router_logits.reshape(groups * seq, self.num_experts)
        bias = self._balancing_bias()
        with jax.named_scope("moe/router"):
            gates, experts, probs = moe_ops.route_top_k(
                logits, self.top_k, normalize=self.normalize_gates,
                scoring=self.scoring,
                bias=None if bias is None else bias.value,
                scale=self.gate_scale,
            )
        # for whoever asks with mutable=["intermediates"] (the
        # benchmark's reference check); nothing otherwise
        self.sow("intermediates", "experts", experts.reshape(groups, seq, -1))
        share = {}
        with jax.named_scope("moe/dispatch"):
            if self.held_experts is None:
                order, inverse, group_sizes = moe_ops.sort_by_expert(
                    experts, self.num_experts
                )
                rows = moe_ops.dispatch_sorted(tokens, order, inverse)
                loads = group_sizes
            else:
                (pairs, valid, group_sizes, loads, share["held"],
                 share["dropped"]) = moe_ops.sort_held(
                    experts, self.num_experts, *self.held_experts,
                    self.held_rows)
                share["buffer_rows"] = pairs.shape[0]
                rows = moe_ops.dispatch_held(
                    tokens, pairs, valid, self.top_k)
        with jax.named_scope("moe/experts"):
            hidden = [
                moe_ops.grouped_matmul(rows, w, group_sizes, one_device)
                for w in weights[:-1]
            ]
            out = moe_ops.grouped_matmul(
                self._act(hidden), weights[-1], group_sizes, one_device
            )
            relu2 = {} if self.expert_act != "relu2" else {
                "relu2_active": self._active_share(
                    hidden[0], None if self.held_experts is None else valid)}
        with jax.named_scope("moe/combine"):
            if self.held_experts is None:
                y = moe_ops.combine_sorted(out, gates, order, inverse)
            else:
                y = moe_ops.combine_held(out, gates, pairs, valid)
        with jax.named_scope("moe/router"):
            aux = {
                "load_balancing": (
                    moe_ops.sequence_balance_loss(probs, experts, groups)
                    if self.seq_aux
                    else moe_ops.load_balancing_loss(probs, loads)
                ),
                "routing": moe_ops.routing_stats(
                    probs, loads, self.top_k, **share
                ),
            }
            aux["routing"].update(relu2)
            self._move_bias(bias, loads, training, aux["routing"])
        return y.reshape(x.shape), aux

    def _balancing_bias(self):
        """The balancing bias's variable, or None for a layer that
        keeps none."""
        if self.bias_update_speed is None:
            return None
        return self.variable(
            "moe_state", "e_score_correction_bias",
            lambda: jnp.zeros((self.num_experts,), jnp.float32),
        )

    def _move_bias(self, bias, loads, training, routing):
        """Selection and update are one step's work: the bias that
        chose this step's experts moves by the load they got, where the
        call may write the collection; its magnitude joins the
        counters."""
        if bias is None:
            return
        if (training and not self.is_initializing()
                and self.is_mutable_collection("moe_state")):
            bias.value = moe_ops.balancing_bias_update(
                bias.value, loads, self.bias_update_speed
            )
        routing["bias_abs_max"] = jnp.abs(bias.value).max()


    def _ranks(self):
        """The ranks an expert layer's experts are spread over."""
        return 1 if self.mesh is None else self.mesh.shape.get("ep", 1)

    def _received_rows(self, pairs):
        """The rows of a rank's receive buffer under ``ep``, for
        ``pairs`` (token, choice) pairs on each rank: ``exchange_rows``
        where the model states it, and never more than all the ranks'
        pairs, which no routing overfills."""
        most = self._ranks() * pairs
        return min(self.exchange_rows or most, most)

    def _sorted_over_ep(self, x, router_logits, weights, training):
        """The sorted path with the experts spread over ``ep``: a
        region manual over the mesh in which every rank routes and
        sorts its own tokens, the rows travel to the ranks that hold
        their experts (``ops/moe.py:exchange_rows``), are regrouped by
        those experts, run the grouped matmuls there and come back the
        way they went. The loads are summed over the ranks, so the
        balance loss, the counters and the balancing bias see the
        batch, not a rank's part of it."""
        mesh, ranks = self.mesh, self._ranks()
        if self.held_experts is not None:
            raise ValueError(
                "held_experts is one chip's share of a layer WITHOUT "
                "its exchange; a mesh with ep=%d spreads all %d experts "
                "and exchanges their rows: one or the other"
                % (ranks, self.num_experts))
        others = sorted(
            axis for axis, size in mesh.shape.items()
            if size > 1 and axis not in ("dp", "ep"))
        if others or self.num_experts % ranks:
            raise ValueError(
                'dispatch_impl="sorted" over ep=%d runs in a region '
                "manual over the whole mesh: %d experts have to divide "
                "over ep, and nothing divides over %s there yet "
                "(ROADMAP.md M1: ep beside fsdp on one mesh)"
                % (ranks, self.num_experts, others or "another axis"))
        groups, seq, dim = x.shape
        if groups % mesh.size:
            raise ValueError(
                "a batch of %d sequences does not divide over the %d "
                "data shards of mesh %s" % (
                    groups, mesh.size, dict(mesh.shape)))
        bias = self._balancing_bias()
        pairs = groups // mesh.size * seq * self.top_k
        buffer_rows = self._received_rows(pairs)
        # the axes whose ranks' tokens are one batch, and those of them
        # that are not the exchange's own
        axes = tuple(a for a in DATA_AXES if mesh.shape[a] > 1)
        replicas = tuple(a for a in axes if a != "ep")

        def layer(x, logits, weights, bias):
            tokens = x.reshape(-1, dim)
            logits = logits.reshape(-1, self.num_experts)
            with jax.named_scope("moe/router"):
                gates, experts, probs = moe_ops.route_top_k(
                    logits, self.top_k, normalize=self.normalize_gates,
                    scoring=self.scoring, bias=bias, scale=self.gate_scale,
                )
            with jax.named_scope("moe/dispatch"):
                order, inverse, group_sizes = moe_ops.sort_by_expert(
                    experts, self.num_experts)
                rows = moe_ops.dispatch_sorted(tokens, order, inverse)
                counts = mesh_all_gather(group_sizes, "ep", tiled=False)
                plan = moe_ops.exchange_plan(
                    counts, jax.lax.axis_index("ep"), buffer_rows)
                by_expert, by_sender, held_sizes, carried = (
                    moe_ops.regroup_plan(plan["received"], buffer_rows))
            received = moe_ops.exchange_rows(
                rows, plan["there"], plan["back"], buffer_rows, "ep")
            with jax.named_scope("moe/dispatch"):
                held = moe_ops.permute_rows(
                    received, by_expert, by_sender, carried)
            with jax.named_scope("moe/experts"):
                hidden = [
                    moe_ops.grouped_matmul(held, w, held_sizes)
                    for w in weights[:-1]
                ]
                out = moe_ops.grouped_matmul(
                    self._act(hidden), weights[-1], held_sizes)
            with jax.named_scope("moe/combine"):
                out = moe_ops.permute_rows(
                    out, by_sender, by_expert, carried)
            returned = moe_ops.exchange_rows(
                out, plan["back"], plan["there"], rows.shape[0], "ep")
            with jax.named_scope("moe/combine"):
                y = moe_ops.combine_sorted(returned, gates, order, inverse)
            with jax.named_scope("moe/router"):
                # the group's loads are in the gathered table already
                # (integers: nothing differentiates through these two)
                loads = mesh_psum(counts.sum(axis=0), replicas)
                balance = (
                    moe_ops.sequence_balance_loss(
                        probs, experts, x.shape[0], axes)
                    if self.seq_aux
                    else moe_ops.load_balancing_loss(probs, loads, axes))
                stats = moe_ops.routing_stats(
                    probs, loads, self.top_k,
                    dropped=mesh_psum(plan["dropped"], replicas),
                    axes=axes)
                stats.update(moe_ops.exchange_stats(
                    plan["sent"], dim * x.dtype.itemsize, buffer_rows,
                    replicas))
            return (y.reshape(x.shape), experts.reshape(x.shape[:2] + (-1,)),
                    balance, stats, loads)

        batch, whole = P(DATA_AXES), P()
        y, experts, balance, stats, loads = jax_compat.shard_map(
            layer, mesh=mesh,
            in_specs=(batch, batch, P("ep"), whole),
            out_specs=(batch, batch, whole, whole, whole),
            # the backend's grouped matmul declares no vma for its
            # results (``jax_compat.shard_map``)
            check_vma=False,
        )(x, router_logits, weights, None if bias is None else bias.value)
        # for whoever asks with mutable=["intermediates"], as ``_sorted``
        self.sow("intermediates", "experts", experts)
        self._move_bias(bias, loads, training, stats)
        return y, {"load_balancing": balance, "routing": stats}


# The kinds of ``MoeTransformerLM.layer_kinds`` that are no mixer but a
# second sublayer standing alone in its layer: naming one makes the
# stack one of layers of ONE sublayer each
ONE_SUBLAYER_KINDS = ("experts", "mlp")

# What a model names again (nine zoo files build ``MoeTransformerLM``
# with flat keywords) and hands to its expert blocks whole: every field
# the layer declares but the mesh, which is the block's
EXPERT_FIELDS = tuple(
    f.name for f in dataclasses.fields(MoeMlp)
    if f.name not in ("mesh", "parent", "name"))


def merge_routing(layers):
    """One set of ``moe_routing`` counters from the expert layers' own:
    the largest load of any expert in any layer, the mean load, the
    mean entropy, all dropped pairs and, where the layers keep a
    balancing bias, its largest magnitude; a held share's and an
    exchange's counters where the layers have them."""
    merged = {
        "load_max": jnp.stack([r["load_max"] for r in layers]).max(),
        "load_mean": jnp.stack([r["load_mean"] for r in layers]).mean(),
        "entropy": jnp.stack([r["entropy"] for r in layers]).mean(),
        "dropped": jnp.stack([r["dropped"] for r in layers]).sum(),
    }
    if "bias_abs_max" in layers[0]:
        # the largest |balancing bias| of any expert in any layer
        merged["bias_abs_max"] = jnp.stack(
            [r["bias_abs_max"] for r in layers]).max()
    for name in ("relu2_active", "relu2_shared_active"):
        # a ReLU-squared body's share of hidden units above zero, the
        # layers' mean
        if name in layers[0]:
            merged[name] = jnp.stack([r[name] for r in layers]).mean()
    if "held" in layers[0]:
        # the pairs of the layer whose held experts got the most: what
        # the row buffer has to hold
        merged["held"] = jnp.stack([r["held"] for r in layers]).max()
        # and of all the layers' buffers' rows, those the step ran
        for name in ("rows_run", "rows_buffer"):
            merged[name] = jnp.stack([r[name] for r in layers]).sum()
    if "sent" in layers[0]:
        # the exchange's (``ops/moe.py:exchange_stats``): what a rank
        # sends a step over all the layers, the rows of the rank that
        # received the most in the layer where it did, and those of
        # its buffer that its regrouping ran there
        for name, over in (("sent", jnp.sum), ("exchange_bytes", jnp.sum),
                           ("received_max", jnp.max),
                           ("received_mean", jnp.mean),
                           ("received_run", jnp.max),
                           ("received_buffer", jnp.max)):
            merged[name] = over(jnp.stack([r[name] for r in layers]))
    return merged


class HeadKernel(nn.Module):
    """The output head's kernel (D, V) as ``nn.Dense`` names and draws
    it (``<name>/kernel``), handed out unapplied: a looped model's loss
    applies it to every exit a chunk of positions at a time."""

    features: int

    @nn.compact
    def __call__(self, width):
        return self.param(
            "kernel", nn.initializers.lecun_normal(),
            (width, self.features))


class MoeTransformerLM(nn.Module):
    """Decoder-only LM with an MoE FFN in every ``moe_every``-th block
    (block i is an expert block when ``i % moe_every == moe_every - 1``:
    every other one by default, every one at 1).

    Training call returns ``{"logits", "aux_loss"}`` (the router's
    penalties must reach the loss: ``aux_loss_weight`` x load balancing
    + ``z_loss_weight`` x router z-loss, each summed over the expert
    layers) and, on the sorted path, ``"routing"`` (counters for the
    ``moe_routing`` journal event; train/step_fns.py hands them out of
    the jitted step); eval returns bare logits so metrics and export see
    the same surface as the dense LM.

    The defaults are the legacy zoo model (LayerNorm, GELU experts of
    ``mlp_ratio x embed_dim``, one-hot dispatch, renormalised gates);
    OLMoE-1B-7B is ``norm="rmsnorm"``, ``qk_norm``, ``expert_act=
    "swiglu"``, ``expert_dim=1024``, ``moe_every=1``,
    ``dispatch_impl="sorted"``, ``normalize_gates=False``,
    ``z_loss_weight=0.001``. Moonlight-16B-A3B's (DeepSeek-V3's) block
    is ``latent`` (latent attention in every block), ``first_k_dense=1``
    with ``dense_act="swiglu"`` and ``dense_dim=11264``, and an expert
    layer of ``scoring="sigmoid"``, ``gate_scale=2.446``,
    ``bias_update_speed``, ``seq_aux`` and ``shared_experts=2``
    (``MoeMlp``). Qwen3-Next-80B-A3B's is ``norm=
    "zero_centred_rmsnorm"``, ``layer_kinds=("linear", "linear",
    "linear", "full")`` with ``linear`` (a Gated DeltaNet mixer's
    sizes) and, for the full layers, ``head_dim=256``, ``num_kv_heads=
    2``, ``head_norm``, ``rotary_dim=64``, ``output_gate="sigmoid"``;
    an expert layer of 512 softmax-routed experts of which this chip
    holds ``held_experts=(0, 32)`` in ``held_rows`` rows, and one
    shared expert behind ``shared_gate``.

    LFM2-8B-A1B's is ``layer_kinds`` as the list itself (``conv
    conv full conv conv conv ...``: no period) with ``conv`` (a gated
    short convolution's taps, ``ShortConv``), full layers of
    ``head_dim=64`` over ``num_kv_heads=8`` with ``head_norm``,
    ``first_k_dense=2`` (both of them ``conv`` layers) and a sigmoid
    router with a balancing bias over 32 experts of which a chip holds
    8; ``mixer_kinds()`` says what such a model is made of (the
    journal's event of that name).

    Kimi-Linear-48B-A3B's is ``layer_kinds=("kda", "kda", "kda",
    "full")`` with ``kda`` (a Kimi Delta Attention mixer's sizes,
    ``KdaDims``: a delta rule whose decay is a vector a head) and
    ``latent`` with ``rotary=False`` for the full layers (latent
    attention that rotates nothing: the first stack in which a latent
    and a recurrent mixer alternate), ``first_k_dense=1`` (a ``kda``
    layer) and Moonlight's expert layer over 256 experts of which a
    chip holds 8; a training call also returns ``kda`` (the
    ``kda_gates`` event's facts, one entry a KDA layer).

    granite-4.0-h-micro's is every layer dense again, ``layer_kinds``
    the published list (``mamba`` x 5, ``full``, ``mamba`` x 4: nine
    Mamba-2 state-space mixers, ``mamba``'s sizes, to one softmax layer)
    with ``rotary=False`` and ``attention_scale`` for the softmax layer
    (nothing rotates: the order comes from the recurrent layers),
    ``embedding_scale``, ``residual_scale`` and ``logits_divisor`` (the
    family's four muP multipliers) and ``tie_embeddings``; a training
    call also returns ``mamba`` (the ``mamba_gates`` event's facts, one
    entry a Mamba layer).

    NVIDIA-Nemotron-3-Nano-30B-A3B's (``nemotron_h``) is a stack of
    layers of ONE sublayer each (``Block.only``): ``layer_kinds`` the
    published pattern's letters as ``mamba`` (M), ``experts`` (E: the
    expert layer ALONE, no mixer) and ``full`` (*), ``moe_every=1`` and
    ``first_k_dense=0`` (the kinds say which layers hold experts),
    ``mamba`` at 8 groups, ``rotary=False`` with ``head_dim=128`` over
    ``num_kv_heads=2``, and an expert layer of ``expert_act="relu2"``
    (two matrices an expert, the shared expert in the same body),
    ``scoring="sigmoid"`` with a balancing bias over 128 experts of
    which a chip holds 8, ``router_float32``; every block's tree is
    ``ln`` and ``attn`` or ``moe_mlp``.

    Ouro-2.6B's is every layer dense (``first_k_dense=num_layers``,
    ``dense_act="swiglu"``), ``sandwich`` (a norm on each sublayer's
    output too) and ``looped`` (a ``LoopedDims``): the blocks run
    ``passes`` times over ONE parameter tree, ``ln_f`` ends every pass
    (the normed state is that pass's exit and the next pass's input),
    and one gate (``early_exit_gate``, a ``Dense(1)`` with bias shared
    by the passes) gives every position a distribution over the exits
    (``ops/looped_exit.py``). A training call then returns no
    ``logits``: ``exits`` (the passes' states, (B, S, D) each),
    ``exit_log_probs`` (passes, B, S) in float32, ``head_kernel`` and
    ``exit_beta`` for ``loss``, which applies the one head to every
    exit a chunk of positions at a time, and ``looped`` (the
    ``looped_exit`` event's facts). An eval call returns the LAST
    pass's logits, bare.

    ``objective="block_diffusion"`` (SDAR's: ``ops/block_diffusion.py``)
    trains the same blocks to denoise and not to predict the next
    token: a training call draws the step's noise from the ``noise``
    random stream (``train/step_fns.py`` folds it from the step),
    assembles ``[x_t ; x_0]``, 2 L positions that rotate by ``p mod L``,
    runs every layer under the mask ``BlockDiffusion(L, bd_block)``,
    puts ``ln_f`` and ``lm_head`` on the NOISY half alone (the clean
    half's logits enter no loss) and returns ``logits`` (L positions),
    ``weights``, ``noise`` (the ``bd_noise`` event's facts),
    ``aux_loss``, ``routing``; ``loss`` reads ``weights`` where the
    outputs carry them. ``noisy`` and ``weights`` passed in take the
    draw's place (eval, the reference check); an eval call without them
    draws from a fixed key and returns bare logits. Every layer is then
    an expert block with softmax attention: a dense block, a latent or
    a linear mixer under this objective is refused.
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
    expert_dim: Optional[int] = None
    expert_act: str = "gelu"
    normalize_gates: bool = True
    z_loss_weight: float = 0.0
    moe_every: int = 2
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    qk_norm: bool = False
    rope_theta: float = 10000.0
    latent: Optional[LatentDims] = None
    # the first k blocks are dense whatever ``moe_every`` says
    # (``first_k_dense_replace``); a dense block's MLP is ``dense_act``
    # of width ``dense_dim`` (``mlp_ratio x embed_dim`` when None)
    first_k_dense: int = 0
    dense_act: str = "gelu"
    dense_dim: Optional[int] = None
    scoring: str = "softmax"
    gate_scale: float = 1.0
    bias_update_speed: Optional[float] = None
    seq_aux: bool = False
    shared_experts: int = 0
    held_experts: Optional[Any] = None
    held_rows: Optional[int] = None
    shared_gate: bool = False
    # the rows of a rank's receive buffer where the experts are spread
    # over ``ep`` (``MoeMlp``); None: all the ranks' pairs
    exchange_rows: Optional[int] = None
    # the router's product in float32 (``MoeMlp.router_float32``)
    router_float32: bool = False
    # the mixers' kinds as a pattern with a period: layer i is
    # ``layer_kinds[i % len(layer_kinds)]``, "linear" (a Gated DeltaNet
    # of ``linear``'s sizes), "kda" (a Kimi Delta Attention of
    # ``kda``'s), "conv" (a gated short convolution of ``conv``'s),
    # "mamba" (a Mamba-2 state-space mixer of ``mamba``'s),
    # "full" (softmax or, with ``latent``, latent attention over the
    # causal prefix) or "window" (softmax attention over a band,
    # ``ops/flash_attention.py:Band``). None: every layer "full".
    # "experts" is no mixer: a pattern that names it is a stack of
    # layers of ONE sublayer each (``Block.only``), a mixer kind's layer
    # the mixer alone and an "experts" layer the expert layer alone
    # (``_check_one_sublayer``; "mlp", a dense MLP alone, is refused:
    # not built). The
    # five fields after ``linear`` are ``Attention``'s own of those
    # names, for every softmax layer. What a KIND of softmax layer has
    # of its own is ``kind_fields``, {kind: ``MixerKind``}: heads,
    # rotary base, rotating lanes, YaRN, window; a kind without an
    # entry has the model's ``num_heads``, ``rope_theta``,
    # ``rotary_dim`` and ``rope_scaling`` and sees the causal prefix,
    # and "window" needs one (``_mixer`` says all of it, once)
    layer_kinds: Optional[Any] = None
    kind_fields: Optional[Any] = None
    linear: Optional[GatedDeltaDims] = None
    conv: Optional[ShortConvDims] = None
    kda: Optional[KdaDims] = None
    mamba: Optional[Mamba2Dims] = None
    head_dim: Optional[int] = None
    num_kv_heads: Optional[int] = None
    head_norm: Optional[str] = None
    rotary_dim: Optional[int] = None
    output_gate: Optional[str] = None
    # standard deviation of the token embedding's init (None: flax's,
    # 1 / sqrt(embed_dim)). At 1 / sqrt(embed_dim) a block's output is
    # ~10 times the embedding it is added to, and at init that output is
    # the context's mean: every token's router then sees one direction
    # and picks the same experts (PERF.md Section 6, PR 29)
    embed_init_std: Optional[float] = None
    # the output head is the token embedding, transposed (LFM2's
    # ``tie_word_embeddings``): no ``lm_head``, and the embedding's
    # gradient is the gather's plus the head's
    tie_embeddings: bool = False
    # per-block rematerialization, as TransformerLM has it
    # (models/transformer.py:remat_block)
    remat: bool = False
    remat_policy: str = "full"
    # "next_token" or "block_diffusion" with its block length, the id
    # that stands for a masked token and the least noise level
    objective: str = "next_token"
    bd_block: int = 4
    bd_mask_id: Optional[int] = None
    bd_t_min: float = 1e-3
    # a latent mixer's YaRN, the hyper-connected residual path and the
    # multi-token-prediction module (depth 0 or 1) with its loss weight
    rope_scaling: Optional[YarnScaling] = None
    hc: Optional[HyperDims] = None
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.1
    # a learned sparse-attention indexer in every layer (``IndexerDims``;
    # ``models/transformer.py:Attention.indexer``): a training call then
    # also returns ``indexer_loss`` (a sample's KL term summed over the
    # layers, which ``loss`` adds times ``indexer_loss_coef`` and names)
    # and ``dsa`` (the ``dsa_select`` event's facts, one entry a layer)
    indexer: Optional[IndexerDims] = None
    indexer_loss_coef: float = 1.0
    # a norm on each sublayer's OUTPUT too (``Block.sandwich``), and
    # the blocks run ``looped.passes`` times over one parameter tree
    # (``LoopedDims``; the class's docstring). None: the stack is
    # walked once, the tree and the program every model always had
    sandwich: bool = False
    looped: Optional[LoopedDims] = None
    # granite's four multipliers, each None for the program every older
    # model has: the embedding times ``embedding_scale``, both of a
    # block's branches times ``residual_scale`` before they are added
    # (``Block.residual_scale``), the softmax of ``attention_scale x q
    # k^T`` in the place of ``head width ** -0.5``, the logits over
    # ``logits_divisor``; and ``rotary`` False: the softmax layers
    # rotate nothing (``Attention.rotary``)
    embedding_scale: Optional[float] = None
    residual_scale: Optional[float] = None
    attention_scale: Optional[float] = None
    logits_divisor: Optional[float] = None
    rotary: bool = True

    def _mixer(self, kind, layout=None):
        """A layer's mixer by its KIND, stated once: what a block hands
        to ``make_attention`` unopened. The kind decides the mixer (a
        Gated DeltaNet of ``linear``'s sizes, a short convolution of
        ``conv``'s, a Kimi Delta Attention of ``kda``'s, a Mamba-2 mixer
        of ``mamba``'s, else softmax or latent attention) and, of a softmax
        one, the query heads, the rotary base, the lanes that rotate,
        YaRN, the mask's layout and the scope its operations lie under;
        the rest is the model's, for every kind alike. A kind without
        an entry in ``kind_fields`` (every kind of every model built
        before there were any) has the model's own four and the call's
        ``layout`` (None: causal; block diffusion's), and no scope: the
        mixer, the tree and the program it always had."""
        own = dict(self.kind_fields or {}).get(kind)
        if own is None:
            own = MixerKind(self.num_heads, self.rope_theta,
                            self.rotary_dim, self.rope_scaling)
        return dict(
            # (a model without the kind hands on what it always did)
            **({"kda": self.kda} if kind == "kda" else {}),
            **({"mamba": self.mamba} if kind == "mamba" else {}),
            **({"rotary": False} if not self.rotary
               and kind in ("full", "window") else {}),
            **({"sm_scale": self.attention_scale}
               if self.attention_scale is not None
               and kind in ("full", "window") else {}),
            num_heads=own.num_heads,
            latent=self.latent,
            linear=self.linear if kind == "linear" else None,
            conv=self.conv if kind == "conv" else None,
            attention_impl=self.attention_impl,
            qk_norm=self.qk_norm,
            head_dim=self.head_dim,
            num_kv_heads=self.num_kv_heads,
            head_norm=self.head_norm,
            output_gate=self.output_gate,
            indexer=self.indexer,
            rope_theta=own.rope_theta,
            rotary_dim=own.rotary_dim,
            rope_scaling=own.rope_scaling,
            mask=(flash_attention.Band(own.window) if own.window
                  else layout),
            kind_scope="attn_" + kind if self.kind_fields else None,
        )

    def _check_kinds(self, kinds, denoise):
        """Refuses, by name, a pattern the blocks cannot run."""
        by_kind = dict(self.kind_fields or {})
        sized = {"linear": self.linear, "conv": self.conv, "kda": self.kda,
                 "mamba": self.mamba}
        if set(kinds) & set(ONE_SUBLAYER_KINDS):
            self._check_one_sublayer(kinds, denoise)
            kinds = tuple(k for k in kinds if k != "experts")
        if set(kinds) - {"full", "window", *sized} or any(
                sized[kind] is None for kind in set(kinds) & set(sized)):
            raise ValueError(
                "layer_kinds=%r: each is 'full', 'window', 'linear' (a "
                "Gated DeltaNet), 'conv' (a gated short convolution), "
                "'kda' (a Kimi Delta Attention) or 'mamba' (a Mamba-2 "
                "state-space mixer), and 'linear', 'conv', 'kda' and "
                "'mamba' need their mixer's sizes (linear, conv, kda, "
                "mamba)" % (self.layer_kinds,))
        if "conv" in kinds:
            self._check_conv(denoise, by_kind)
        if "kda" in kinds:
            self._check_kda(kinds, denoise, by_kind)
        if "mamba" in kinds:
            self._check_mamba(kinds, denoise)
        self._check_multipliers(denoise)
        if self.indexer is not None:
            self._check_indexer(kinds, denoise, by_kind)
        if set(by_kind) - {"full", "window"}:
            raise ValueError(
                "kind_fields=%r: only the softmax kinds 'full' and "
                "'window' have fields of their own" % (sorted(by_kind),))
        windows = {kind: own.window for kind, own in by_kind.items()}
        if ("window" in kinds) != bool(windows.get("window")) or (
                windows.get("full")):
            raise ValueError(
                "a 'window' layer needs kind_fields['window'] with its "
                "window, and no other kind takes one; layer_kinds=%r, "
                "windows=%r" % (self.layer_kinds, windows))
        if by_kind and (
                denoise or self.latent is not None or self.mtp_layers
                or self.attention_impl in ("ring", "ulysses")):
            raise ValueError(
                "kind_fields (a band, heads or a rotary table by layer "
                "kind) under objective=\"block_diffusion\", with latent "
                "attention, beside the prediction module (mtp_layers) "
                "or under attention_impl='ring' / 'ulysses': not built, "
                "so not run")
        if by_kind:
            _log_kinds_once(tuple(
                (kind, sum(kinds[i % len(kinds)] == kind
                           for i in range(self.num_layers)),
                 str(by_kind.get(kind, "the model's own")))
                for kind in sorted(set(kinds))))

    def _check_one_sublayer(self, kinds, denoise):
        """A stack of layers of one sublayer each runs Mamba-2 mixers,
        causal softmax layers of one kind and expert layers, each alone
        in its layer, under next-token prediction on one device. What it
        was not built beside is refused, each by its name."""
        ranks = {} if self.mesh is None else dict(self.mesh.shape)
        for what, asked in (
                ("a layer that is a dense MLP alone ('mlp' in "
                 "layer_kinds=%r: only 'experts' stands alone)"
                 % (self.layer_kinds,), "mlp" in kinds),
                ("a 'linear', 'conv', 'kda' or 'window' layer "
                 "(layer_kinds=%r)" % (self.layer_kinds,),
                 bool(set(kinds) - {"mamba", "full", "experts", "mlp"})),
                ("first_k_dense=%d or moe_every=%d (the kinds say which "
                 "layers hold experts: first_k_dense=0, moe_every=1)"
                 % (self.first_k_dense, self.moe_every),
                 bool(self.first_k_dense) or self.moe_every != 1),
                ("objective=\"block_diffusion\"", denoise),
                ("latent attention (latent)", self.latent is not None),
                ("kind_fields (a band, heads or a rotary table by layer "
                 "kind)", bool(self.kind_fields)),
                ("hyper-connections (hc)", self.hc is not None),
                ("sandwich norms (sandwich)", self.sandwich),
                ("a looped stack (looped)", self.looped is not None),
                ("the prediction module (mtp_layers: its block is a "
                 "mixer and an expert layer)", bool(self.mtp_layers)),
                ("a learned indexer (indexer)", self.indexer is not None),
                ("a scaled residual branch (residual_scale)",
                 self.residual_scale is not None),
                ("attention_impl='ring' / 'ulysses'",
                 self.attention_impl in ("ring", "ulysses")
                 or ranks.get("sp", 1) > 1),
                ("experts spread over ep (the mesh's ep=%d)"
                 % ranks.get("ep", 1), ranks.get("ep", 1) > 1)):
            if asked:
                raise ValueError(
                    "a stack of layers of one sublayer each ('experts' in "
                    "layer_kinds) beside %s: not built, so not run" % what)

    def mixer_kinds(self, seq=None, dtype=None):
        """What a model with gated short convolutions, Kimi Delta
        Attention or Mamba-2 layers is made of, for the journal's
        ``mixer_kinds`` event (the worker emits it once, when the state
        is made: ``worker/trainer.py:ensure_state``); None for a model
        without any of those kinds. Read from the fields alone; for the
        convolutions,
        given a batch's length ``seq`` and the step's compute ``dtype``
        (None: float32), also what runs them there
        (``ops/short_conv.py:conv_choice``: ``conv_impl``,
        ``conv_tile``)."""
        kinds = tuple(self.layer_kinds or ("full",))
        built = [kinds[i % len(kinds)] for i in range(self.num_layers)]
        if self.mamba is not None:
            return {
                "mamba_layers": built.count("mamba"),
                "full_layers": built.count("full"),
                "dense_layers": self.first_k_dense,
                # (a stack of layers of one sublayer each: its expert
                # layers; a model without such a stack says what it
                # always did)
                **({"expert_layers": built.count("experts")}
                   if "experts" in built else {}),
                "mamba_heads": self.mamba.num_heads,
                "mamba_head_dim": self.mamba.head_dim,
                "mamba_state": self.mamba.state,
                "mamba_groups": self.mamba.groups,
                "mamba_taps": self.mamba.conv_kernel,
                "mamba_chunk": self.mamba.chunk,
                "head_dim": self.head_dim or self.embed_dim // self.num_heads,
                "kv_heads": self.num_kv_heads or self.num_heads,
                "rotary": self.rotary,
            }
        if self.kda is not None:
            return {
                "kda_layers": built.count("kda"),
                "full_layers": built.count("full"),
                "dense_layers": self.first_k_dense,
                "kda_heads": self.kda.num_heads,
                "kda_head_dim": self.kda.head_dim,
                "kda_taps": self.kda.conv_kernel_dim,
                "kda_gate_rank": self.kda.gate_rank,
                "kda_chunk": self.kda.chunk,
                "latent": self.latent is not None,
                "latent_rotary": bool(self.latent and self.latent.rotary),
            }
        if self.conv is None:
            return None
        runs = {}
        if seq is not None:
            runs["conv_impl"], runs["conv_tile"] = short_conv.conv_choice(
                dtype or jnp.float32, self.embed_dim, seq, self.conv.taps,
                self.mesh)
        return {
            **runs,
            "conv_layers": built.count("conv"),
            "full_layers": built.count("full"),
            "dense_layers": self.first_k_dense,
            "conv_taps": self.conv.taps,
            "conv_channels": self.embed_dim,
            "head_dim": self.head_dim or self.embed_dim // self.num_heads,
            "kv_heads": self.num_kv_heads or self.num_heads,
        }

    def _check_indexer(self, kinds, denoise, by_kind):
        """A learned indexer picks the keys of causal softmax layers,
        every layer an expert block, on one device. What it was not
        built beside is refused, each by its name."""
        for what, asked in (
                ("objective=\"block_diffusion\" (its mask is a layout of "
                 "two copies; the selection is over one causal prefix)",
                 denoise),
                ("a 'window', 'linear', 'conv', 'kda' or 'mamba' layer "
                 "(layer_kinds=%r: the "
                 "indexer picks keys for full softmax attention)"
                 % (self.layer_kinds,), set(kinds) != {"full"}),
                ("kind_fields (a band, heads or a rotary table by layer "
                 "kind)", bool(by_kind)),
                ("latent attention (latent: its keys are one latent a "
                 "position, which the kernels do not read)",
                 self.latent is not None),
                ("hyper-connections (hc: the mixer reads one stream)",
                 self.hc is not None),
                ("the prediction module (mtp_layers)", bool(self.mtp_layers)),
                ("a dense block (first_k_dense, moe_every: only an expert "
                 "block hands the indexer's term out)",
                 bool(self.first_k_dense) or self.moe_every != 1),
                ("attention_impl='ring' / 'ulysses' (a query's picks lie "
                 "on every shard of the sequence)",
                 self.attention_impl in ("ring", "ulysses")),
                ("a mesh of %d devices (ROADMAP M15: the kernels are one "
                 "device's)" % (1 if self.mesh is None else self.mesh.size),
                 self.mesh is not None and self.mesh.size > 1)):
            if asked:
                raise ValueError(
                    "%s beside %s: not built, so not run"
                    % (self.indexer, what))

    def _check_looped(self, kinds, denoise):
        """A looped stack runs dense blocks with softmax attention under
        next-token prediction. What it was not built beside is refused,
        each by its name."""
        dense = all(
            i < self.first_k_dense
            or i % self.moe_every != self.moe_every - 1
            for i in range(self.num_layers))
        if self.looped.passes < 1:
            raise ValueError("%s: at least one pass" % (self.looped,))
        for what, asked in (
                ("an expert block (first_k_dense=%d of %d layers, "
                 "moe_every=%d: the passes share dense blocks)"
                 % (self.first_k_dense, self.num_layers, self.moe_every),
                 not dense),
                ("hyper-connections (hc)", self.hc is not None),
                ("the prediction module (mtp_layers)", bool(self.mtp_layers)),
                ("objective=\"block_diffusion\"", denoise),
                ("a learned indexer (indexer)", self.indexer is not None),
                ("a 'linear', 'conv', 'mamba' or 'kda' mixer "
                 "(layer_kinds=%r)" % (self.layer_kinds,),
                 bool({"linear", "conv", "kda", "mamba"} & set(kinds))),
                ("a head tied to the embedding (tie_embeddings)",
                 self.tie_embeddings)):
            if asked:
                raise ValueError(
                    "%s beside %s: not built, so not run"
                    % (self.looped, what))

    def _looped(self, x, make_blocks, training, positions):
        """The looped stack from the embedded tokens on: ONE ``nn.scan``
        over a pass (the blocks ``make_blocks()`` builds, ``ln_f``, the
        gate) with the parameters broadcast, so every pass reads the
        same tree and the compiler sees one pass (against the passes
        unrolled, on the chip at Ouro's cell: the step 4% faster, the
        compile 36 s for 91, the peak 0.7 GB lower; PERF.md Section 6,
        PR 55). The gate reads every exit; the last pass's reading is
        not used (the last exit takes what is left)."""
        passes = self.looped.passes

        def end_of_pass(model, x):
            with jax.named_scope("looped/exit_norm"):
                x = make_norm(self.norm, self.norm_eps, "ln_f")(x)
            if passes == 1:
                return x, jnp.zeros(x.shape[:-1], jnp.float32)
            with jax.named_scope("exit/gate"):
                # float32: a position's probabilities come from it
                gate = nn.Dense(
                    1, dtype=jnp.float32, name="early_exit_gate")(x)
            return x, gate[..., 0]

        # under remat a pass's end keeps its input alone, as a block
        # does (the gate's float32 copy of an exit is 128 MB a pass)
        if self.remat:
            end_of_pass = nn.remat(end_of_pass)

        def a_pass(model, x, _):
            with jax.named_scope("looped/pass"):
                for block in make_blocks():
                    x, _ = block(x, training, positions)
            x, gate = end_of_pass(model, x)
            return x, (x, gate)

        x, (exits, gate_logits) = nn.scan(
            a_pass, variable_broadcast="params",
            split_rngs={"params": False}, length=passes)(self, x, None)
        kernel = HeadKernel(self.vocab_size, name="lm_head")(self.embed_dim)
        if not training:
            with jax.named_scope("head"):
                return x @ kernel
        with jax.named_scope("exit/gate"):
            logits = gate_logits[:passes - 1]
            log_p = looped_exit.exit_distribution(logits)
            p, log_p_fact, logits = jax.lax.stop_gradient(
                (jnp.exp(log_p), log_p, logits))
            facts = {
                "passes": jnp.float32(passes),
                "p_mean": p.mean(axis=(1, 2)),
                "entropy": -(p * log_p_fact).sum(axis=0).mean(),
                "lambda_mean": jax.nn.sigmoid(logits).mean(axis=(1, 2)),
            }
        return {
            "exits": tuple(exits[t] for t in range(passes)),
            "exit_log_probs": log_p,
            "head_kernel": kernel, "exit_beta": self.looped.beta,
            "looped": facts,
        }

    def _check_conv(self, denoise, by_kind):
        """A gated short convolution runs beside causal softmax layers
        of one kind, in dense and in expert blocks, on one device or
        under a data axis. What it was not built beside is refused, each
        by its name."""
        ranks = {} if self.mesh is None else dict(self.mesh.shape)
        for what, asked in (
                ("objective=\"block_diffusion\" (its two copies of a "
                 "sequence are one axis, and a convolution would read "
                 "across their seam)", denoise),
                ("kind_fields (a band, heads or a rotary table by layer "
                 "kind)", bool(by_kind)),
                ("latent attention (latent)", self.latent is not None),
                ("a Gated DeltaNet mixer (linear)", self.linear is not None),
                ("a Kimi Delta Attention mixer (kda)", self.kda is not None),
                ("a Mamba-2 mixer (mamba)", self.mamba is not None),
                ("hyper-connections (hc: the mixer reads one stream)",
                 self.hc is not None),
                ("the prediction module (mtp_layers)", bool(self.mtp_layers)),
                ("attention_impl='ring' / 'ulysses' (the sequence over "
                 "sp: a shard's first positions read the shard before)",
                 self.attention_impl in ("ring", "ulysses")
                 or ranks.get("sp", 1) > 1),
                ("experts spread over ep (the mesh's ep=%d)"
                 % ranks.get("ep", 1), ranks.get("ep", 1) > 1)):
            if asked:
                raise ValueError(
                    "a 'conv' layer (a gated short convolution) beside "
                    "%s: not built, so not run" % what)

    def _check_kda(self, kinds, denoise, by_kind):
        """A Kimi Delta Attention layer runs beside causal softmax or
        latent layers of one kind, in dense and in expert blocks, under
        next-token prediction, its sequence whole on a device. What it
        was not built beside is refused, each by its name."""
        ranks = {} if self.mesh is None else dict(self.mesh.shape)
        for what, asked in (
                ("objective=\"block_diffusion\" (its two copies of a "
                 "sequence are one axis, and a recurrence would run "
                 "across their seam)", denoise),
                ("kind_fields (a band, heads or a rotary table by layer "
                 "kind)", bool(by_kind)),
                ("a 'linear', 'conv', 'mamba' or 'window' layer "
                 "(layer_kinds=%r)" % (self.layer_kinds,),
                 bool(set(kinds) - {"kda", "full"})),
                ("hyper-connections (hc: the mixer reads one stream)",
                 self.hc is not None),
                ("the prediction module (mtp_layers)", bool(self.mtp_layers)),
                ("a learned indexer (indexer)", self.indexer is not None),
                ("a looped stack (looped)", self.looped is not None),
                ("attention_impl='ring' / 'ulysses' (the sequence over "
                 "sp: a shard's state is the shard before's)",
                 self.attention_impl in ("ring", "ulysses")
                 or ranks.get("sp", 1) > 1)):
            if asked:
                raise ValueError(
                    "a 'kda' layer (Kimi Delta Attention) beside %s: not "
                    "built, so not run" % what)

    def _check_mamba(self, kinds, denoise):
        """A Mamba-2 layer runs beside causal softmax layers of one
        kind, in dense and in expert blocks, under next-token
        prediction, its sequence whole on a device and its experts (if
        any) on this one. What it was not built beside is refused, each
        by its name."""
        ranks = {} if self.mesh is None else dict(self.mesh.shape)
        for what, asked in (
                ("objective=\"block_diffusion\" (its two copies of a "
                 "sequence are one axis, and a recurrence would run "
                 "across their seam)", denoise),
                ("a 'linear', 'conv', 'kda' or 'window' layer "
                 "(layer_kinds=%r)" % (self.layer_kinds,),
                 bool(set(kinds) - {"mamba", "full"})),
                ("latent attention (latent)", self.latent is not None),
                ("hyper-connections (hc: the mixer reads one stream)",
                 self.hc is not None),
                ("the prediction module (mtp_layers)", bool(self.mtp_layers)),
                ("a learned indexer (indexer)", self.indexer is not None),
                ("a looped stack (looped)", self.looped is not None),
                ("attention_impl='ring' / 'ulysses' (the sequence over "
                 "sp: a shard's state is the shard before's)",
                 self.attention_impl in ("ring", "ulysses")
                 or ranks.get("sp", 1) > 1),
                ("experts spread over ep (the mesh's ep=%d)"
                 % ranks.get("ep", 1), ranks.get("ep", 1) > 1)):
            if asked:
                raise ValueError(
                    "a 'mamba' layer (a Mamba-2 state-space mixer) beside "
                    "%s: not built, so not run" % what)

    def _check_multipliers(self, denoise):
        """The multipliers and the softmax layers that rotate nothing
        were built for the plain stack: refused, by name, beside what
        was not tried with them."""
        asked = [
            name for name, stated in (
                ("embedding_scale", self.embedding_scale is not None),
                ("residual_scale", self.residual_scale is not None),
                ("attention_scale", self.attention_scale is not None),
                ("logits_divisor", self.logits_divisor is not None),
                ("rotary=False", not self.rotary))
            if stated]
        if not asked:
            return
        for what, beside in (
                ("objective=\"block_diffusion\"", denoise),
                ("latent attention (latent: its own scale and "
                 "LatentDims.rotary)", self.latent is not None),
                ("hyper-connections (hc)", self.hc is not None),
                ("the prediction module (mtp_layers)", bool(self.mtp_layers)),
                ("a learned indexer (indexer)", self.indexer is not None),
                ("a looped stack (looped)", self.looped is not None),
                ("attention_impl='ring' / 'ulysses'",
                 self.attention_impl in ("ring", "ulysses"))):
            if beside:
                raise ValueError(
                    "%s beside %s: not built, so not run"
                    % (", ".join(asked), what))

    def _block_diffusion_inputs(self, tokens, training, noisy, weights):
        """``(inputs (B, 2 L), positions, mask layout, weights, the
        noise's facts or None)`` of a call under the block-diffusion
        objective."""
        if self.bd_mask_id is None or not (
                0 <= self.bd_mask_id < self.vocab_size):
            raise ValueError(
                "objective=\"block_diffusion\" needs bd_mask_id, an id "
                "of the vocabulary; got %r" % (self.bd_mask_id,))
        if (self.first_k_dense or self.moe_every != 1
                or self.latent is not None or self.linear is not None):
            raise ValueError(
                "objective=\"block_diffusion\" runs expert blocks with "
                "softmax attention: no dense block, no latent and no "
                "linear mixer carries its mask")
        if (noisy is None) != (weights is None):
            raise ValueError("noisy and weights come together")
        facts = None
        if noisy is None:
            # the step's own stream when training; one fixed draw for an
            # eval call that brings none, so that it is a function of
            # its tokens
            key = (self.make_rng("noise") if self.has_rng("noise")
                   else jax.random.PRNGKey(0))
            noisy, weights = block_diffusion.noise(
                key, tokens, self.bd_block, self.bd_mask_id, self.bd_t_min)
            # for whoever asks with mutable=["intermediates"] (the
            # benchmark's reference check draws the same noise from
            # the same key); nothing otherwise
            self.sow("intermediates", "noise_key", key)
            self.sow("intermediates", "noisy", noisy)
            if training:
                facts = block_diffusion.noise_facts(
                    key, weights, self.bd_block, self.bd_t_min)
        inputs, positions = block_diffusion.assemble(noisy, tokens)
        layout = flash_attention.BlockDiffusion(
            tokens.shape[-1], self.bd_block)
        return inputs, positions, layout, weights, facts

    @nn.compact
    def __call__(self, tokens, training: bool = False, noisy=None,
                 weights=None):
        if self.objective not in ("next_token", "block_diffusion"):
            raise ValueError(
                "objective must be 'next_token' or 'block_diffusion', "
                "got %r" % (self.objective,))
        denoise = self.objective == "block_diffusion"
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                "mtp_layers=%r: one prediction module or none"
                % (self.mtp_layers,))
        for what, asked in (("hyper-connections (hc)", self.hc is not None),
                            ("the prediction module (mtp_layers)",
                             bool(self.mtp_layers))):
            if asked and (denoise or self.linear is not None):
                raise ValueError(
                    "%s under objective=\"block_diffusion\" or beside a "
                    "linear mixer (linear): not built, so not run" % what)
        kinds = tuple(self.layer_kinds or ("full",))
        if self.looped is not None:
            self._check_looped(kinds, denoise)
        tokens = tokens.astype(jnp.int32)
        positions = layout = facts = None
        if denoise:
            tokens, positions, layout, weights, facts = (
                self._block_diffusion_inputs(
                    tokens, training, noisy, weights))
        elif noisy is not None or weights is not None:
            raise ValueError("only block diffusion takes noisy and weights")
        embed_init = (
            {} if self.embed_init_std is None else
            {"embedding_init": nn.initializers.normal(self.embed_init_std)})
        embed = nn.Embed(
            self.vocab_size, self.embed_dim, name="wte", **embed_init)

        def expand_streams(x):
            """Expand by copy: every stream starts as ``x``."""
            if self.hc is None:
                return x
            return jnp.broadcast_to(
                x[:, None], (x.shape[0], self.hc.streams) + x.shape[1:])

        # names only, here and around the head below
        # (``observability/scopes.py``)
        with jax.named_scope("embed"):
            x = embed(tokens)
            if self.embedding_scale is not None:
                x = x * self.embedding_scale
            x = expand_streams(x)
        wrap = (
            functools.partial(
                remat_block, remat_policy=self.remat_policy,
                attention_impl=self.attention_impl,
            )
            if self.remat else (lambda cls: cls)
        )
        self._check_kinds(kinds, denoise)
        balance = z_loss = jnp.float32(0.0)
        routing, mhc, dsa, kda, mamba = [], [], [], [], []

        experts = {name: getattr(self, name) for name in EXPERT_FIELDS}

        one_sublayer = bool(set(kinds) & set(ONE_SUBLAYER_KINDS))

        def block(name, index, kind="full", dense=False):
            second = (
                dict(mlp_act=self.dense_act, mlp_dim=self.dense_dim)
                if dense else dict(experts=experts))
            if one_sublayer:
                # a layer is its mixer alone or its experts alone
                second = (
                    dict(experts=experts, only="second")
                    if kind == "experts" else dict(only="mixer"))
            return wrap(Block)(
                None if kind == "experts" else self._mixer(kind, layout),
                mlp_ratio=self.mlp_ratio,
                norm=self.norm, norm_eps=self.norm_eps, hc=self.hc,
                layer_index=index, mesh=self.mesh, name=name,
                sandwich=self.sandwich,
                # (a model without the multiplier builds the block it
                # always did)
                **({} if self.residual_scale is None
                   else {"residual_scale": self.residual_scale}),
                **second)

        def count(aux):
            """A block's losses and facts into the model's."""
            nonlocal balance, z_loss
            if "router_z" in aux:
                balance = balance + aux["load_balancing"]
                z_loss = z_loss + aux["router_z"]
                if aux["routing"] is not None:
                    routing.append(aux["routing"])
            if "mhc" in aux:
                mhc.append(aux["mhc"])
            if "dsa" in aux:
                dsa.append(aux["dsa"])
            if "kda" in aux:
                kda.append(aux["kda"])
            if "mamba" in aux:
                mamba.append(aux["mamba"])

        if self.looped is not None:
            return self._looped(x, lambda: [
                block("block_%d" % i, i, kinds[i % len(kinds)], dense=True)
                for i in range(self.num_layers)], training, positions)
        for i in range(self.num_layers):
            kind = kinds[i % len(kinds)]
            dense = not one_sublayer and (
                i < self.first_k_dense
                or i % self.moe_every != self.moe_every - 1)
            if dense and kind == "linear":
                raise ValueError(
                    "a dense block's mixer is softmax attention, "
                    "latent attention, a gated short convolution, a "
                    "Kimi Delta Attention or a Mamba-2 mixer; layer %d "
                    "asks for a Gated DeltaNet, which only an expert "
                    "block takes" % i)
            x, aux = block("block_%d" % i, i, kind, dense)(
                x, training, positions)
            count(aux)
        if denoise:
            x = block_diffusion.noisy_half(x)

        def reduce_streams(streams):
            """The n streams' sum, in float32, rounded once."""
            if self.hc is None:
                return streams
            return streams.astype(jnp.float32).sum(axis=1).astype(
                streams.dtype)

        with jax.named_scope("final_norm"):
            x = reduce_streams(x)
            normed = make_norm(self.norm, self.norm_eps, "ln_f")(x)
        head = embed.attend if self.tie_embeddings else nn.Dense(
            self.vocab_size, use_bias=False, name="lm_head")
        with jax.named_scope("head"):
            logits = head(normed)
            if self.logits_divisor is not None:
                logits = logits / self.logits_divisor
        mtp_logits = None
        # the module's parameters are made by ``init``, which is no
        # training call
        if self.mtp_layers and (training or self.is_initializing()):
            with jax.named_scope("mtp/proj"):
                # position i reads t_(i+1); the last one the wrap
                merged = jnp.concatenate([
                    make_norm(self.norm, self.norm_eps, "mtp_hnorm")(x),
                    make_norm(self.norm, self.norm_eps, "mtp_enorm")(
                        embed(jnp.roll(tokens, -1, axis=-1))),
                ], axis=-1)
                h = expand_streams(nn.Dense(
                    self.embed_dim, use_bias=False, name="mtp_proj")(merged))
            with jax.named_scope("mtp/block"):
                h, aux = block("mtp_block", self.num_layers)(
                    h, training, positions)
                count(aux)
                h = reduce_streams(h)
            with jax.named_scope("mtp/head"):
                mtp_logits = head(
                    make_norm(self.norm, self.norm_eps, "mtp_norm")(h))
        if not training:
            return logits
        aux_loss = self.aux_loss_weight * balance
        if self.z_loss_weight:
            aux_loss = aux_loss + self.z_loss_weight * z_loss
        outputs = {"logits": logits, "aux_loss": aux_loss}
        if mtp_logits is not None:
            outputs["mtp_logits"] = mtp_logits
            outputs["mtp_loss_weight"] = self.mtp_loss_weight
        if routing:
            outputs["routing"] = merge_routing(routing)
        if mhc:
            # one fact a block, the prediction module's last
            outputs["mhc"] = {
                name: jnp.stack([block_facts[name] for block_facts in mhc])
                for name in ("row_err", "diag_mean")}
        if dsa:
            # a sample's term over the layers, and one fact a layer
            outputs["indexer_loss"] = sum(d["indexer_loss"] for d in dsa)
            outputs["indexer_loss_coef"] = self.indexer_loss_coef
            tiles = sparse_attention.tiles_facts(
                tokens.shape[-1], self.indexer.topk,
                self.head_dim or self.embed_dim // self.num_heads, x.dtype)
            outputs["dsa"] = {
                **{name: jnp.stack([jnp.mean(d[name]) for d in dsa])
                   for name in ("indexer_loss", "kept_mean", "entropy",
                                "near_share")},
                # of one head's forward grid: the first version runs
                # every tile of the causal prefix
                "tiles_run": jnp.float32(tiles["forward"][0]),
                "tiles_causal": jnp.float32(tiles["forward"][0]),
            }
        if kda:
            # one fact a Kimi Delta Attention layer
            outputs["kda"] = {
                name: jnp.stack([facts[name] for facts in kda])
                for name in kda[0]}
        if mamba:
            # one fact a Mamba-2 layer
            outputs["mamba"] = {
                name: jnp.stack([facts[name] for facts in mamba])
                for name in mamba[0]}
        if denoise:
            outputs["weights"] = weights
            if facts is not None:
                outputs["noise"] = facts
        return outputs


# ---------------------------------------------------------------------------
# Sharding rules: transformer TP rules + expert-dim ep sharding
# ---------------------------------------------------------------------------


def moe_sharding_rules():
    """Dense-block rules plus expert weights over (ep, fsdp/tp).

    w_up and, in a SwiGLU expert, w_gate (E, M, F): experts over ep,
    FFN dim over tp (Megatron within the expert); w_down (E, F, M)
    transposed to match. The router stays
    replicated — it is tiny and on the critical path of every token.
    """
    return ShardingRules(
        rules=[
            (r"router/kernel$", P()),
            (r"w_(gate|up)$", P("ep", "fsdp", "tp")),
            (r"w_down$", P("ep", "tp", "fsdp")),
            (r"(query|key|value)/kernel$", P("fsdp", "tp", None)),
            # latent attention: heads over tp where a kernel has them;
            # kv_down makes the one latent all heads share, so its
            # output stays whole
            (r"(q_proj|kv_up)/kernel$", P("fsdp", "tp", None)),
            (r"kv_down/kernel$", P("fsdp", None)),
            # the q latent: its down-projection makes one latent all
            # heads read, as kv_down does
            (r"q_down/kernel$", P("fsdp", None)),
            # hyper-connections: the coefficient kernels are (n, D, 4
            # to 16) and read the whole residual, so they follow it
            # (replicated but for fsdp's storage); the gates and biases
            # are a few floats
            (r"hc_(attn|mlp)/p_(pre|post|res)$", P(None, "fsdp", None)),
            (r"hc_(attn|mlp)/(a|b)_(pre|post|res)$", P()),
            # the prediction module's projection (2 D x D): an
            # up-projection's layout
            (r"mtp_proj/kernel$", P("fsdp", "tp")),
            # Gated DeltaNet: the two input projections split their
            # output features over tp like every up-projection, the
            # depthwise conv follows its channels (annotation only:
            # q | k | v | z lie side by side, so over tp > 1 the split
            # into heads moves data; no cell runs that yet); the
            # per-head decay parameters and the shared expert's gate
            # are tiny
            (r"in_proj_(qkvz|ba)/kernel$", P("fsdp", "tp")),
            (r"conv_kernel$", P(None, "tp")),
            # Kimi Delta Attention: q | k | v as Gated DeltaNet's; the
            # low-rank gates' down-projections make one latent all heads
            # read (as kv_down does), their up-projections split their
            # heads' columns over tp; beta's is a column a head
            (r"in_proj_qkv/kernel$", P("fsdp", "tp")),
            (r"(f|g)_down/kernel$", P("fsdp", None)),
            (r"(f|g)_up/kernel$", P(None, "tp")),
            (r"b_proj/kernel$", P("fsdp", None)),
            # a gated short convolution's two projections: stored over
            # fsdp; B | C | X lie side by side, so nothing splits over tp
            (r"attn/in_proj/kernel$", P("fsdp", None)),
            (r"attn/proj_out/kernel$", P(None, "fsdp")),
            # a Mamba-2 mixer: z | x | B | C | dt lie side by side in
            # ``in_proj`` (the short convolution's row above stores it
            # over fsdp and splits nothing over tp); the convolution's
            # bias follows its channels as the taps do; a number a head
            (r"conv_bias$", P("tp")),
            (r"attn/(D|out_norm_scale)$", P()),
            (r"(A_log|dt_bias)$", P()),
            (r"shared_expert_gate/kernel$", P()),
            (r"out_proj/kernel$", P("tp", None, "fsdp")),
            # the shared experts are a dense MLP (Megatron over tp)
            (r"(mlp|shared)_(gate|up)/kernel$", P("fsdp", "tp")),
            (r"(mlp|shared)_down/kernel$", P("tp", "fsdp")),
            # the vocabulary's two matrices are the largest tensors
            # outside the experts: an expert group's ranks, which are
            # data shards here, each store a slice of them as fsdp's do
            # (gathered where they are used, their gradients
            # reduce-scattered)
            (r"wte/embedding$", P("tp", ("fsdp", "ep"))),
            (r"lm_head/kernel$", P(("fsdp", "ep"), "tp")),
            # the norms' scales and biases: small, replicated
            (r"(scale|bias)$", P()),
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


def shifted_cross_entropy(labels, logits, ahead=1, horizon=1):
    """A sample's mean cross-entropy of position ``i``'s logits against
    ``labels[i + ahead]``, over the positions that have every target up
    to ``horizon`` ahead (the last ``horizon`` have not)."""
    last = labels.shape[1] - horizon
    return sparse_softmax_cross_entropy(
        labels[:, ahead:last + ahead], logits[:, :last]).mean(axis=-1)


def loss(labels, predictions):
    if not isinstance(predictions, dict):
        return shifted_cross_entropy(labels, predictions)
    if "exits" in predictions:
        # a looped stack: the expected cross-entropy over the passes'
        # exits less beta x the exit distribution's entropy, the one
        # head applied a chunk of positions at a time
        # (ops/looped_exit.py); its parts go out by name
        return looped_exit.expected_loss(
            labels, predictions["exits"], predictions["head_kernel"],
            predictions["exit_log_probs"], predictions["exit_beta"])
    logits = predictions["logits"]
    # aux is a scalar: adding it to every per-sample loss leaves the
    # masked mean shifted by exactly aux.
    aux = predictions["aux_loss"]
    if "weights" in predictions:
        # block diffusion: position-aligned, weighted by 1 / t on
        # the masked positions (ops/block_diffusion.py)
        return block_diffusion.weighted_loss(
            labels, logits, predictions["weights"]) + aux
    if "mtp_logits" in predictions:
        # a multi-token-prediction module: over the positions that
        # have both targets, CE(main, t_(i+1)) + weight x CE(module,
        # t_(i+2)); the second term goes out by name as well
        main = shifted_cross_entropy(labels, logits, horizon=2)
        mtp = shifted_cross_entropy(
            labels, predictions["mtp_logits"], ahead=2, horizon=2)
        return (main + predictions["mtp_loss_weight"] * mtp + aux,
                {"mtp_loss": mtp})
    main = shifted_cross_entropy(labels, logits)
    if "indexer_loss" in predictions:
        # a learned indexer's own term (its KL to the attention's
        # probabilities, summed over the layers), times its
        # coefficient in the sum and unweighted by its name
        term = predictions["indexer_loss"]
        return (main + aux + predictions["indexer_loss_coef"] * term,
                {"indexer_loss": term})
    return main + aux


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
