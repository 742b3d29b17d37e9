"""The Gated DeltaNet layer's convolution, SiLU, norms and head split
as Pallas kernels (PR 44, ``ops/qkv_conv.py``) in interpret mode on the
CPU against the module's own lines: results and every gradient (``dX``
into ``qkvz``'s first columns, zeros into z's, the taps'), at a row
tile's edges, for Hv = Hk and Hv = 2 Hk; ``conv_impl``'s table; the
layer both ways with the line that says which it got; and that a model
the chooser refuses traces the step it traced before. What interpret
mode cannot see (the chip's tiling and VMEM) is
``tests/test_qkv_conv_tpu_compile.py``'s and ``scripts/qkv_conv.py``'s.
"""

import functools
import hashlib
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.ops import qkv_conv as Q
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state

DIM, TAPS = 128, 4
KERNELS = ("qkv_conv_fwd", "qkv_conv_bwd")
TPU, CPU = "tpu", "cpu"


def force_pallas(monkeypatch):
    """What a TPU backend would choose for these lines (the rule stays
    on its XLA lines), run by the interpreter, 64 rows an iteration of
    the kernels' loops: a tile of 128 rows is then two of them."""
    monkeypatch.setattr(Q, "conv_impl", lambda *a, **kw: "pallas")
    for name in KERNELS:
        monkeypatch.setattr(Q, name, functools.partial(
            getattr(Q, name), interpret=True, chunk=64))


def operands(dtype, hk, hv, seq, batch, taps=TAPS, dim=DIM, seed=0):
    conv_dim = (2 * hk + hv) * dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    qkvz = jax.random.normal(keys[0], (batch, seq, conv_dim + hv * dim))
    w = jax.random.normal(keys[1], (taps, conv_dim)) * taps ** -0.5
    grads = [
        jax.random.normal(key, (batch, num, seq, dim))
        for key, num in zip(keys[2:], (hk, hk, hv))]
    return tuple(x.astype(dtype) for x in [qkvz, w] + grads)


def program(fn, heads, **kw):
    """``fn``'s results and VJP as one program (a new one: ``fn`` is
    traced under what the test has patched by then); a test that calls
    it on several operands of one shape lowers its kernels once."""
    def both(qkvz, w, *grads):
        results, vjp = jax.vjp(lambda x, w: fn(x, w, heads, **kw), qkvz, w)
        return tuple(results) + tuple(vjp(tuple(grads)))

    return jax.jit(both)


def value_and_vjp(fn, heads, qkvz, w, *grads, **kw):
    return program(fn, heads, **kw)(qkvz, w, *grads)


def worst(got, want):
    """The largest difference over the largest wanted magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


# rows: what a case is there for. A tile of 128 rows is two iterations
# of the kernels' loops; 256 tokens are two tiles (the rows across a tile
# boundary, the first tile's zero rows, the last tile's missing
# successors), and a second sequence in the batch starts from zeros
# again and ends without successors too
# the last column: the equal runs of the sequence the results are written
# by (the rule's segments), one or two tiles a run
@pytest.mark.parametrize("dtype,hk,hv,seq,batch,taps,dim,limit,runs", [
    (jnp.float32, 2, 4, 256, 2, 4, DIM, 1e-5, 1),
    (jnp.float32, 2, 2, 128, 1, 4, DIM, 1e-5, 1),  # one tile alone, Hv = Hk
    (jnp.float32, 1, 2, 384, 1, 2, DIM, 1e-5, 3),  # a head a step, two taps
    (jnp.float32, 1, 1, 256, 1, 4, 2 * DIM, 1e-5, 1),  # two lane rows a head
    (jnp.float32, 2, 4, 512, 2, 4, DIM, 1e-5, 2),
    (jnp.bfloat16, 2, 4, 256, 1, 4, DIM, 6e-3, 2),
    (jnp.bfloat16, 2, 2, 256, 2, 4, DIM, 6e-3, 1),
], ids=["float32-two-tiles", "float32-one-tile", "float32-two-taps",
        "float32-heads-of-256", "float32-two-tiles-a-segment",
        "bfloat16-two-to-one", "bfloat16-one-to-one"])
def test_the_pair_against_the_module_s_lines(
        monkeypatch, dtype, hk, hv, seq, batch, taps, dim, limit, runs):
    """Against the lines in float32 from the same values: in bfloat16
    the lines themselves stand 8e-3 to 1e-2 from that, the kernels
    under 5e-3 (no product between taps is rounded)."""
    monkeypatch.setattr(Q, "_TILES", (128,))
    force_pallas(monkeypatch)
    heads = (hk, hv, dim)
    args = operands(dtype, hk, hv, seq, batch, taps, dim)
    got = value_and_vjp(Q.qkv_conv, heads, *args, segments=runs)
    want = value_and_vjp(
        Q.qkv_conv_xla, heads, *(x.astype(jnp.float32) for x in args))
    conv_dim = args[1].shape[1]
    for name, g, w in zip(("q", "k", "v", "dqkvz", "dtaps"), got, want):
        assert g.dtype == dtype and g.shape == w.shape, name
        assert worst(g, w) < limit, name
    # z's columns belong to the output gate's path
    assert not np.asarray(got[3][..., conv_dim:], np.float32).any()
    assert got[0].shape == (batch, hk, seq, dim)
    assert got[2].shape == (batch, hv, seq, dim)


def test_a_tile_s_edges_see_their_neighbours_and_no_further(monkeypatch):
    """Row t of the results reads rows t - 3 .. t of ``qkvz`` and row t
    of ``dX`` reads rows t .. t + 3 of the cotangents, across a tile's
    boundary as inside it; nothing reaches a sequence's first rows from
    before it, nor its last rows from after."""
    monkeypatch.setattr(Q, "_TILES", (128,))
    force_pallas(monkeypatch)
    heads, edge = (1, 1, DIM), 128
    qkvz, w, *grads = operands(jnp.float32, 1, 1, 256, 2)
    pair = program(Q.qkv_conv, heads)
    base = pair(qkvz, w, *grads)
    # the last row of the first tile moves the next tile's first three
    moved = pair(qkvz.at[:, edge - 1].add(1.0), w, *grads)
    for b, m in zip(base[:3], moved[:3]):
        changed = np.asarray(jnp.abs(b - m).max(axis=(0, 1, 3)) > 1e-6)
        assert changed[edge - 1:edge + 3].all()
        assert not changed[:edge - 1].any() and not changed[edge + 3:].any()
    # a cotangent at the second tile's first row moves dX three rows back
    moved = pair(qkvz, w, *(g.at[:, :, edge].add(1.0) for g in grads))
    changed = np.asarray(
        jnp.abs(base[3] - moved[3]).max(axis=(0, 2)) > 1e-6)
    assert changed[edge - 3:edge + 1].all()
    assert not changed[:edge - 3].any() and not changed[edge + 1:].any()
    # the second sequence starts from zeros, whatever the first ends on
    alone = pair(qkvz[1:], w, *(g[1:] for g in grads))
    for b, a in zip(base[:4], alone[:4]):
        np.testing.assert_array_equal(np.asarray(b[1:]), np.asarray(a))


class FourDevices:
    size = 4
    axis_names = ("data",)


@pytest.mark.parametrize("backend,dtype,dk,dv,seq,taps,mesh,want", [
    (TPU, jnp.bfloat16, 128, 128, 32768, 4, None, "pallas"),
    (TPU, jnp.float32, 256, 256, 128, 2, None, "pallas"),
    (CPU, jnp.bfloat16, 128, 128, 32768, 4, None, "xla"),
    (TPU, jnp.float16, 128, 128, 32768, 4, None, "xla"),
    (TPU, jnp.bfloat16, 16, 16, 32768, 4, None, "xla"),    # the tests' heads
    (TPU, jnp.bfloat16, 192, 192, 32768, 4, None, "xla"),  # half a lane row
    (TPU, jnp.bfloat16, 128, 256, 32768, 4, None, "xla"),  # two widths
    (TPU, jnp.bfloat16, 128, 128, 32768 + 64, 4, None, "xla"),  # no tile
    (TPU, jnp.bfloat16, 128, 128, 40, 4, None, "xla"),
    (TPU, jnp.bfloat16, 128, 128, 32768, 10, None, "xla"),  # past 8 rows
    (TPU, jnp.bfloat16, 128, 128, 32768, 4, FourDevices, "xla"),
])
def test_conv_impl_chooses_from_what_it_sees(
        monkeypatch, backend, dtype, dk, dv, seq, taps, mesh, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert Q.conv_impl(dtype, dk, dv, seq, taps, mesh) == want


def test_conv_impl_takes_a_region_manual_over_the_mesh(monkeypatch):
    """Where the caller has already opened a ``shard_map`` over the
    whole mesh the projection is one shard, and the kernels take it."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    seen = []

    def shard(x):
        seen.append(Q.conv_impl(jnp.bfloat16, 128, 128, 1024, 4, mesh))
        return x

    jax.eval_shape(jax_compat.shard_map(
        shard, mesh=mesh, in_specs=P("data"), out_specs=P("data")),
        jnp.zeros(4))
    assert seen == ["pallas"]
    assert Q.conv_impl(jnp.bfloat16, 128, 128, 1024, 4, mesh) == "xla"


def test_the_tiles_a_grid_step_takes():
    assert Q.row_tile(32768) == 1024 and Q.row_tile(384) == 128
    assert Q.row_tile(32768 + 64) is None
    assert Q.group_heads(16, 32) == 4 and Q.group_heads(2, 4) == 2
    assert Q.group_heads(3, 6) == 1


def test_the_results_are_written_by_the_rule_s_segments():
    """Four segments of 8,192 tokens in the cell; one where the rule
    runs one or pads."""
    assert Q.rule_segments(32768, 64) == 4
    assert Q.rule_segments(8192, 64) == 1
    assert Q.rule_segments(32768 + 1024, 64) == 1     # the rule pads
    assert Q.rule_segments(4096, 16) == 2
    assert Q.rule_segments(4096, 1) == 32    # segments of one 128-row tile
    assert Q.rule_segments(4096 + 2048, 16) == 3
    for seq, chunk in ((32768, 64), (4096, 16)):
        assert Q.rule_segments(seq, chunk) == (
            Q.segments_of(seq, chunk)[1])


def test_the_kernels_names_are_not_the_scan_s():
    """``benchmark/lib/gdn_trace.py`` charges a Mosaic kernel named
    ``gdn...`` to ``gdn/scan`` (and ``mhc...`` / ``flash...`` kernels
    have readers of their own): these two are found by their scope."""
    for name in KERNELS:
        assert getattr(Q, name).__name__ == name
        assert not any(word in name for word in ("gdn", "mhc", "flash"))
    assert Q.SCOPE == "gdn/conv"


# ---------------------------------------------------------- the layer

WIDE = T.GatedDeltaDims(
    num_key_heads=2, num_value_heads=4, key_head_dim=DIM,
    value_head_dim=DIM, conv_kernel_dim=TAPS, chunk=16)


def layer_gradients(dims, seq=128):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 32))
    layer = T.GatedDeltaNet(dims)
    params = jax.jit(layer.init)(jax.random.PRNGKey(2), x)["params"]
    target = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda params, x: jnp.sum(
        layer.apply({"params": params}, x) * target)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)


def test_the_layer_both_ways_over_two_of_the_rule_s_segments(monkeypatch):
    """128 chunks of one token are a segment: the results are written
    two segments first and the rule reads what the lines gave it."""
    dims = T.GatedDeltaDims(2, 4, DIM, DIM, TAPS, chunk=1)
    assert Q.rule_segments(256, 1) == 2
    want = layer_gradients(dims, 256)
    force_pallas(monkeypatch)
    got = layer_gradients(dims, 256)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got[1])[0],
            jax.tree_util.tree_leaves(want[1])):
        assert worst(g, w) < 2e-5, jax.tree_util.keystr(path)


def test_the_layer_both_ways(monkeypatch, caplog):
    """The module's output and every parameter's gradient with the
    kernels as with its own lines, and the line that says which ran."""
    Q.log_choice.cache_clear()
    with caplog.at_level(logging.INFO):
        want = layer_gradients(WIDE)
    assert ("linear attention conv heads k=2 v=4 dim=128 taps=4 impl=xla "
            "(tokens=128 tile=None)") in caplog.text
    force_pallas(monkeypatch)
    with caplog.at_level(logging.INFO):
        got = layer_gradients(WIDE)
    Q.log_choice.cache_clear()
    assert ("linear attention conv heads k=2 v=4 dim=128 taps=4 "
            "impl=pallas (tokens=128 tile=128)") in caplog.text
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got[1])[0],
            jax.tree_util.tree_leaves(want[1])):
        assert worst(g, w) < 2e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("dims,seq", [
    (T.GatedDeltaDims(2, 4, 16, 16, TAPS, chunk=16), 128),
    (WIDE, 40),
], ids=["16-wide-heads", "no-tile-divides-the-sequence"])
def test_what_the_chooser_refuses_runs_the_module_s_lines(
        monkeypatch, dims, seq):
    """On a TPU backend too: no kernel is traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    for name in KERNELS:
        monkeypatch.setattr(Q, name, None)
    layer = T.GatedDeltaNet(dims)
    x = jax.ShapeDtypeStruct((1, seq, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x: layer.init_with_output(jax.random.PRNGKey(0), x)[0])(x)
    assert "pallas_call" not in str(jaxpr)


def test_a_mesh_that_is_not_manual_runs_the_module_s_lines(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    for name in KERNELS:
        monkeypatch.setattr(Q, name, None)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    layer = T.GatedDeltaNet(
        T.GatedDeltaDims(2, 4, DIM, DIM, TAPS, chunk=16), mesh=mesh)
    x = jax.ShapeDtypeStruct((4, 128, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x: layer.init_with_output(jax.random.PRNGKey(0), x)[0])(x)
    assert "pallas_call" not in str(jaxpr)


def _sha(text):
    return hashlib.sha256(
        re.sub(r" at 0x[0-9a-f]+", "", text).encode()).hexdigest()[:16]


# sha256 of the jaxpr of the train step of a tiny Qwen3-Next (three
# Gated DeltaNet layers to one gated grouped-query layer, a held share
# of the experts, a shared expert), recorded on the parent of PR 44
# (d973009) with the pinned jax, at the tests' 16-wide heads and at
# heads of 128, where a TPU would take the kernels and the CPU does not
PARENT_STEPS = {16: "f83f89e0a30a3e6b", 128: "aadd4c6078dfb271"}


@pytest.mark.parametrize("width", sorted(PARENT_STEPS))
def test_a_tiny_qwen3_next_traces_the_parent_s_step_on_the_cpu(width):
    model = moe_transformer.MoeTransformerLM(
        vocab_size=128, num_layers=4, num_heads=4, embed_dim=64, top_k=2,
        num_experts=8, attention_impl="xla",
        layer_kinds=("linear", "linear", "linear", "full"),
        linear=T.GatedDeltaDims(2, 4, width, width, TAPS, chunk=16),
        head_dim=16, num_kv_heads=2, head_norm="zero_centred_rmsnorm",
        rotary_dim=8, output_gate="sigmoid", norm="zero_centred_rmsnorm",
        moe_every=1, dispatch_impl="sorted", expert_act="swiglu",
        expert_dim=32, held_experts=(0, 4), held_rows=512,
        shared_experts=1, shared_gate=True, remat=True,
        remat_policy="full")
    tokens = jnp.zeros((2, 128), jnp.int32)
    tx = moe_transformer.optimizer()
    # the trace reads shapes and dtypes: no parameter is initialised
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.PRNGKey(0), tokens))
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((2,), jnp.float32)}
    step = make_train_step(
        model, moe_transformer.loss, tx, jnp.bfloat16, health=True)
    assert _sha(str(jax.make_jaxpr(step)(state, batch))) == (
        PARENT_STEPS[width])
