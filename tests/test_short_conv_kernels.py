"""The gated short convolution as Pallas kernels (PR 50,
``ops/short_conv.py``) in interpret mode on the CPU against the
module's own lines (``gated_short_conv_xla``): ``y``, ``dbcx`` and the
taps' gradient, at a row tile's edges, for a batch of two
sequences; ``conv_impl``'s table; the mixer both ways with
the line that says which it got; and that a model the chooser refuses
traces the step it traced before. What interpret mode cannot see (the
chip's tiling and VMEM) is ``tests/test_short_conv_tpu_compile.py``'s
and ``scripts/short_conv.py``'s.
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
from elasticdl_tpu.ops import short_conv as S
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state

KERNELS = ("short_conv_fwd", "short_conv_bwd")
TPU, CPU = "tpu", "cpu"


def force_pallas(monkeypatch):
    """What a TPU backend would choose, run by the interpreter, 64 rows
    an iteration of the kernels' loops and 128 rows a grid step: a tile
    is then two iterations, 256 tokens two tiles."""
    monkeypatch.setattr(S, "conv_impl", lambda *a, **kw: "pallas")
    monkeypatch.setattr(S, "_TILES", (128,))
    for name in KERNELS:
        monkeypatch.setattr(S, name, functools.partial(
            getattr(S, name), interpret=True, chunk=64))


def operands(dtype, channels, seq, batch, taps, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(keys[0], (batch, seq, 3 * channels))
    w = jax.random.normal(keys[1], (taps, channels)) * taps ** -0.5
    dy = jax.random.normal(keys[2], (batch, seq, channels))
    return tuple(x.astype(dtype) for x in (bcx, w, dy))


def program(fn):
    """``fn``'s value and VJP as one program (a new one: ``fn`` is
    traced under what the test has patched by then); a test that calls
    it on several operands of one shape lowers its kernels once."""
    def both(bcx, w, dy):
        y, vjp = jax.vjp(fn, bcx, w)
        return (y,) + tuple(vjp(dy))

    return jax.jit(both)


def value_and_vjp(fn, bcx, w, dy):
    return program(fn)(bcx, w, dy)


def worst(got, want):
    """The largest difference over the largest wanted magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


NAMES = ("y", "dbcx", "dtaps")


# a tile of 128 rows is two iterations of the kernels' loops; 256 tokens
# are two tiles (the rows across a tile's boundary, the first tile's
# zero rows, the last tile's missing successors), and a second sequence
# in the batch starts from zeros again and ends without successors too;
# 256 channels are two lane rows
@pytest.mark.parametrize("dtype,channels,seq,batch,taps,limit", [
    (jnp.float32, 256, 256, 2, 3, 1e-5),
    (jnp.float32, 128, 128, 1, 3, 1e-5),   # one tile alone
    (jnp.float32, 128, 384, 1, 1, 1e-5),   # a gate and no shift
    (jnp.float32, 256, 256, 1, 2, 1e-5),
    (jnp.float32, 256, 256, 2, 4, 1e-5),
    (jnp.bfloat16, 256, 256, 2, 3, 6e-3),
    (jnp.bfloat16, 128, 256, 2, 4, 6e-3),
    (jnp.bfloat16, 128, 128, 1, 1, 6e-3),
    (jnp.bfloat16, 384, 256, 1, 2, 6e-3),
], ids=["float32-two-tiles", "float32-one-tile", "float32-one-tap",
        "float32-two-taps", "float32-four-taps", "bfloat16-three-taps",
        "bfloat16-four-taps", "bfloat16-one-tap",
        "bfloat16-two-taps-three-lane-rows"])
def test_the_pair_against_the_module_s_lines(
        monkeypatch, dtype, channels, seq, batch, taps, limit):
    """Against the lines in float32 from the same values: in bfloat16
    both round ``y`` and ``dbcx`` once, half a step of 2^-8."""
    force_pallas(monkeypatch)
    args = operands(dtype, channels, seq, batch, taps)
    got = value_and_vjp(S.gated_short_conv, *args)
    want = value_and_vjp(
        S.gated_short_conv_xla, *(x.astype(jnp.float32) for x in args))
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == dtype and g.shape == w.shape, name
        assert worst(g, w) < limit, name
    if dtype == jnp.bfloat16:
        # and the lines in bfloat16 round where the kernels do: y to
        # the bit, dbcx but for a sum of taps taken in another order
        lines = value_and_vjp(S.gated_short_conv_xla, *args)
        np.testing.assert_array_equal(
            np.asarray(got[0], np.float32), np.asarray(lines[0], np.float32))
        assert np.mean(np.asarray(got[1] != lines[1])) < 1e-3


def test_leading_axes_are_sequences_of_their_own(monkeypatch):
    """(2, 3, S, 3 C) is six sequences; (S, 3 C) is one."""
    force_pallas(monkeypatch)
    bcx, w, dy = operands(jnp.float32, 128, 128, 6, 3)
    flat = value_and_vjp(S.gated_short_conv, bcx, w, dy)
    deep = value_and_vjp(
        S.gated_short_conv, bcx.reshape(2, 3, 128, 384), w,
        dy.reshape(2, 3, 128, 128))
    one = value_and_vjp(S.gated_short_conv, bcx[0], w, dy[0])
    np.testing.assert_array_equal(deep[0].reshape(flat[0].shape), flat[0])
    np.testing.assert_array_equal(deep[1].reshape(flat[1].shape), flat[1])
    np.testing.assert_allclose(deep[2], flat[2], rtol=1e-6)
    np.testing.assert_array_equal(one[0], flat[0][0])
    np.testing.assert_array_equal(one[1], flat[1][0])


def test_a_tile_s_edges_see_their_neighbours_and_no_further(monkeypatch):
    """Row t of ``y`` reads rows t - 2 .. t of B and X, and row t of
    ``dB`` / ``dX`` reads rows t .. t + 2 of C and ``dy``, across a
    tile's boundary as inside it; nothing reaches a sequence's first
    rows from before it, nor its last rows from after."""
    force_pallas(monkeypatch)
    channels, edge = 128, 128
    bcx, w, dy = operands(jnp.float32, channels, 256, 2, 3)
    pair = program(S.gated_short_conv)
    base = pair(bcx, w, dy)
    rows = lambda a, b: np.asarray(jnp.abs(a - b).max(axis=(0, 2)) > 1e-6)
    # B's last row of the first tile moves the next tile's first two
    moved = pair(bcx.at[:, edge - 1, :channels].add(1.0), w, dy)
    changed = rows(base[0], moved[0])
    assert changed[edge - 1:edge + 2].all()
    assert not changed[:edge - 1].any() and not changed[edge + 2:].any()
    # a cotangent at the second tile's first row moves dB and dX two
    # rows back, and dC at its own row alone
    moved = pair(bcx, w, dy.at[:, edge].add(1.0))
    for part, back in ((0, 2), (1, 0), (2, 2)):
        lanes = slice(part * channels, (part + 1) * channels)
        changed = rows(base[1][..., lanes], moved[1][..., lanes])
        assert changed[edge - back:edge + 1].all(), part
        assert not changed[:edge - back].any(), part
        assert not changed[edge + 1:].any(), part
    # C at the second tile's first row reaches dz two rows back too
    moved = pair(bcx.at[:, edge, channels:2 * channels].add(1.0), w, dy)
    changed = rows(base[1][..., :channels], moved[1][..., :channels])
    assert changed[edge - 2:edge + 1].all()
    assert not changed[:edge - 2].any() and not changed[edge + 1:].any()
    # the second sequence starts from zeros and ends without
    # successors, whatever the first holds
    alone = pair(bcx[1:], w, dy[1:])
    for b, a in zip(base[:2], alone[:2]):
        np.testing.assert_array_equal(np.asarray(b[1:]), np.asarray(a))


class FourDevices:
    size = 4
    axis_names = ("data",)


@pytest.mark.parametrize("backend,dtype,channels,seq,taps,mesh,want", [
    (TPU, jnp.bfloat16, 2048, 32768, 3, None, ("pallas", 512)),
    (TPU, jnp.float32, 2048, 32768, 3, None, ("pallas", 256)),
    (TPU, jnp.bfloat16, 128, 384, 1, None, ("pallas", 128)),
    (TPU, jnp.bfloat16, 256, 2048, 9, None, ("pallas", 1024)),
    (CPU, jnp.bfloat16, 2048, 32768, 3, None, ("xla", None)),
    (TPU, jnp.float16, 2048, 32768, 3, None, ("xla", None)),
    (TPU, jnp.bfloat16, 64, 32768, 3, None, ("xla", None)),  # the tests'
    (TPU, jnp.bfloat16, 2048 + 64, 32768, 3, None, ("xla", None)),
    (TPU, jnp.bfloat16, 2048, 32768 + 64, 3, None, ("xla", None)),
    (TPU, jnp.bfloat16, 2048, 40, 3, None, ("xla", None)),   # no tile
    (TPU, jnp.bfloat16, 32768, 4096, 3, None, ("xla", None)),  # no VMEM
    (TPU, jnp.bfloat16, 2048, 32768, 10, None, ("xla", None)),  # > 8 rows
    (TPU, jnp.bfloat16, 2048, 32768, 0, None, ("xla", None)),
    (TPU, jnp.bfloat16, 2048, 32768, 3, FourDevices, ("xla", None)),
])
def test_conv_impl_chooses_from_what_it_sees(
        monkeypatch, backend, dtype, channels, seq, taps, mesh, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert S.conv_impl(dtype, channels, seq, taps, mesh) == want[0]
    assert S.conv_choice(dtype, channels, seq, taps, mesh) == want


def test_conv_impl_takes_a_region_manual_over_the_mesh(monkeypatch):
    """Where the caller has already opened a ``shard_map`` over the
    whole mesh the projection is one shard, and the kernels take it."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    seen = []

    def shard(x):
        seen.append(S.conv_impl(jnp.bfloat16, 128, 1024, 3, mesh))
        return x

    jax.eval_shape(jax_compat.shard_map(
        shard, mesh=mesh, in_specs=P("data"), out_specs=P("data")),
        jnp.zeros(4))
    assert seen == ["pallas"]
    assert S.conv_impl(jnp.bfloat16, 128, 1024, 3, mesh) == "xla"


def test_the_tiles_a_grid_step_takes():
    """Rows by the backward's seven whole-row blocks, twice over, in 40
    MiB."""
    assert S.row_tile(32768, 2048, 2) == 512
    assert S.row_tile(32768, 2048, 4) == 256
    assert S.row_tile(32768, 1024, 2) == 1024
    assert S.row_tile(384, 2048, 2) == 128
    assert S.row_tile(32768 + 64, 2048, 2) is None
    assert S.row_tile(32768, 16384, 2) is None


def test_the_rows_an_iteration_of_a_kernel_s_loop_takes():
    """Whole rows, 32 float32 registers an array; a packed tile of
    sublanes at least and the tile at most."""
    assert S.loop_rows(512, 2048) == 16
    assert S.loop_rows(1024, 768) == 32
    assert S.loop_rows(1024, 128) == 256
    assert S.loop_rows(128, 128) == 128
    assert S.loop_rows(128, 8192) == 16


def test_the_kernels_names_are_the_gates(monkeypatch):
    """``benchmark/lib/conv_trace.py`` charges a Mosaic kernel named
    ``short_conv...`` to ``short_conv/gate``, the scope both calls sit
    under."""
    from benchmark.lib import conv_trace

    for name in KERNELS:
        assert getattr(S, name).__name__ == name
        assert name.startswith(conv_trace.CONV_KERNEL)
    assert S.SCOPE == conv_trace.GATE


# ---------------------------------------------------------- the mixer

def mixer_gradients(dim=128, seq=256, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, dim)).astype(dtype)
    mixer = T.ShortConv(T.ShortConvDims(taps=3))
    params = mixer.init(jax.random.PRNGKey(2), x)["params"]
    target = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda params, x: jnp.sum(
        mixer.apply({"params": params}, x) * target)
    return jax.value_and_grad(loss, argnums=(0, 1))(params, x)


def test_the_mixer_both_ways(monkeypatch, caplog):
    """The module's output and every parameter's gradient with the
    kernels as with its own lines, and the line that says which ran."""
    S.log_choice.cache_clear()
    with caplog.at_level(logging.INFO):
        want = mixer_gradients()
    assert ("short conv channels=128 taps=3 impl=xla (tokens=256 "
            "tile=None;") in caplog.text
    force_pallas(monkeypatch)
    with caplog.at_level(logging.INFO):
        got = mixer_gradients()
    S.log_choice.cache_clear()
    assert ("short conv channels=128 taps=3 impl=pallas (tokens=256 "
            "tile=128;") in caplog.text
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got[1])[0],
            jax.tree_util.tree_leaves(want[1])):
        assert worst(g, w) < 2e-5, jax.tree_util.keystr(path)


def test_the_kernel_path_keeps_its_two_operands_and_no_more(monkeypatch):
    """Residuals: ``bcx`` and the taps; no checkpoint around the pair,
    and the lines keep theirs."""
    from jax._src.ad_checkpoint import saved_residuals

    bcx, w, _ = operands(jnp.bfloat16, 128, 256, 1, 3)
    shapes = lambda: sorted(r[0].shape for r in saved_residuals(
        lambda bcx, w: S.gated_short_conv(bcx, w), bcx, w))
    assert shapes() == sorted([w.shape, bcx.shape])
    # (a function of its own a trace: jax keeps a function's traces)
    traced = lambda: str(jax.make_jaxpr(
        lambda bcx, w: S.gated_short_conv(bcx, w))(bcx, w))
    assert "remat" in traced()
    force_pallas(monkeypatch)
    assert shapes() == sorted([w.shape, bcx.shape])
    jaxpr = traced()
    assert "remat" not in jaxpr and "short_conv_fwd" in jaxpr


@pytest.mark.parametrize("dim,seq,mesh", [
    (64, 128, False), (128, 40, False), (128, 128, True),
], ids=["64-channels", "no-tile-divides-the-sequence",
        "a-mesh-that-is-not-manual"])
def test_what_the_chooser_refuses_runs_the_module_s_lines(
        monkeypatch, dim, seq, mesh):
    """On a TPU backend too: no kernel is traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    for name in KERNELS:
        monkeypatch.setattr(S, name, None)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",)) if mesh else None
    mixer = T.make_attention(
        4, conv=T.ShortConvDims(3), norm_eps=1e-5, mesh=mesh)
    assert mixer.mesh is mesh
    x = jax.ShapeDtypeStruct((4, seq, dim), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x: mixer.init_with_output(jax.random.PRNGKey(0), x)[0])(x)
    assert "pallas_call" not in str(jaxpr)


def test_a_tpu_backend_traces_the_pair(monkeypatch):
    """And what it does not refuse is the two kernels, the forward once
    and the backward once."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    mixer = T.ShortConv(T.ShortConvDims(3))
    x = jax.ShapeDtypeStruct((1, 256, 128), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))["params"]
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p, x: mixer.apply({"params": p}, x).astype(
            jnp.float32).sum()))(params, x))
    for name in KERNELS:
        assert len(re.findall(r"jit\[\s*name=%s\b" % name, jaxpr)) == 1, name


@pytest.mark.parametrize("backend,dtype,want", [
    (CPU, jnp.bfloat16, ("xla", None)),
    (TPU, jnp.bfloat16, ("pallas", 256)),
    (TPU, None, ("pallas", 256)),            # the parameters' float32
    (TPU, jnp.float16, ("xla", None)),
])
def test_the_worker_says_once_what_runs_the_gates(
        monkeypatch, backend, dtype, want):
    """The ``mixer_kinds`` event, with what ``conv_choice`` says of the
    batch's length on the host: nothing leaves a step for it."""
    from elasticdl_tpu.observability import events
    from elasticdl_tpu.worker.trainer import Trainer

    model = moe_transformer.MoeTransformerLM(
        vocab_size=64, num_layers=2, num_heads=4, embed_dim=128,
        layer_kinds=("conv", "full"), conv=T.ShortConvDims(3))

    class OneState(Trainer):
        _model, compute_dtype = model, dtype

        def create_state(self, features):
            return object()

    said = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(
        events, "emit", lambda name, **fields: said.append((name, fields)))
    trainer = OneState()
    state = trainer.ensure_state(
        None, {"features": np.zeros((2, 256), np.int32)})
    assert trainer.ensure_state(state, {"features": None}) is state
    kinds = [fields for name, fields in said if name == "mixer_kinds"]
    assert len(kinds) == 1
    assert (kinds[0]["conv_impl"], kinds[0]["conv_tile"]) == want
    assert {k: kinds[0][k] for k in model.mixer_kinds()} == (
        model.mixer_kinds())


def _sha(text):
    return hashlib.sha256(
        re.sub(r" at 0x[0-9a-f]+", "", text).encode()).hexdigest()[:16]


# sha256 of the jaxpr of the train step of a tiny LFM2 (five gated short
# convolutions to one grouped-query layer, two dense layers, a held
# share of the experts, a tied head), recorded on the parent of PR 50
# (e124bae) with the pinned jax, at the tests' 64 channels and at 128,
# where a TPU would take the kernels and the CPU does not
PARENT_STEPS = {64: "1794765ceccb11e5", 128: "d7d7fec6e0399426"}


@pytest.mark.parametrize("width", sorted(PARENT_STEPS))
def test_a_tiny_lfm2_traces_the_parent_s_step_on_the_cpu(width):
    model = moe_transformer.MoeTransformerLM(
        vocab_size=64, num_layers=6, num_heads=4, embed_dim=width,
        layer_kinds=("conv", "conv", "full", "conv", "conv", "conv"),
        conv=T.ShortConvDims(3), head_dim=8, num_kv_heads=2,
        head_norm="rmsnorm", first_k_dense=2, dense_act="swiglu",
        dense_dim=48, num_experts=8, held_experts=(0, 4), held_rows=512,
        top_k=2, expert_dim=16, expert_act="swiglu", moe_every=1,
        norm="rmsnorm", norm_eps=1e-5, scoring="sigmoid",
        bias_update_speed=0.001, dispatch_impl="sorted",
        aux_loss_weight=0.0, rope_theta=1e6, tie_embeddings=True,
        attention_impl="xla", remat=True, remat_policy="full")
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
