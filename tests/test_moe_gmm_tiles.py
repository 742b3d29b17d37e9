"""The Pallas grouped matmul's tiles, chosen call by call from the
call's own shapes (``ops/moe.py:gmm_tiles``), and the custom VJP that
asks for them (``pallas_grouped_matmul``), on the CPU: the rule at the
two benchmark models' eighteen calls, the VMEM count against the list
of tiles the TPU compiler accepted and refused (ISSUE 30; the compiler
itself is asked in ``tests/test_moe_tpu_compile.py``), and the VJP in
``interpret`` mode against ``jax.lax.ragged_dot``'s own autodiff."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.moe_transformer import MoeMlp
from elasticdl_tpu.ops import moe as moe_ops

BF16 = jnp.bfloat16
OLD = (512, 1024, 1024)
# a projection (rows, k) x (E, k, n) is three calls; in each call's own
# (K, N) the rows' gradient contracts over n
CALLS = {
    "fwd": ("gmm", lambda k, n: (k, n)),
    "d_rows": ("gmm_transposed", lambda k, n: (n, k)),
    "d_weights": ("tgmm", lambda k, n: (k, n)),
}


@pytest.mark.parametrize("which", sorted(CALLS))
@pytest.mark.parametrize("k, n", [(2048, 1024), (1024, 2048)])
def test_olmoe_s_calls_keep_the_tiles_they_had(k, n, which):
    """gate / up (2048 -> 1024) and down (1024 -> 2048) at 262,144
    rows: 1024 divides every dimension, so all nine calls compile to
    the Mosaic modules they compiled to under the one constant."""
    tiles = moe_ops.projection_tiles(262144, k, n, BF16)
    assert tiles[which] == OLD


@pytest.mark.parametrize("k, n, which, want", [
    (2048, 1408, "fwd", (512, 1024, 1408)),
    (2048, 1408, "d_rows", (512, 1408, 1024)),
    (2048, 1408, "d_weights", (256, 1024, 1408)),
    (1408, 2048, "fwd", (512, 1408, 1024)),
    (1408, 2048, "d_rows", (512, 1024, 1408)),
    (1408, 2048, "d_weights", (256, 1408, 1024)),
])
def test_moonlight_s_calls_pad_nothing(k, n, which, want):
    """1408 = 11 x 128 is one tile wherever it stands, the 2048 beside
    it in tiles of 1024; the weights' gradient, whose (1024, 1408)
    output tile and accumulator leave no room for 512 rows, takes 256
    (its rows are its contraction: they buy no reuse). Under the old
    tiles every call did 2048 / 1408 of the work it needed."""
    call, dims = CALLS[which]
    tiles = moe_ops.projection_tiles(98304, k, n, BF16)[which]
    assert tiles == want
    assert moe_ops.gmm_fill(*dims(k, n), tiles) == 1.0
    assert moe_ops.gmm_fill(*dims(k, n), OLD) == 1408 / 2048 == 0.6875
    assert moe_ops.gmm_vmem_bytes(call, tiles, BF16) <= moe_ops.GMM_VMEM_BYTES


def test_a_projection_s_fill_is_over_its_three_calls():
    old = dict.fromkeys(CALLS, OLD)
    assert moe_ops.projection_fill(2048, 1408, old) == pytest.approx(0.6875)
    half = dict(old, fwd=(512, 1024, 1408))
    assert moe_ops.projection_fill(2048, 1408, half) == pytest.approx(
        3 / (1 + 2 / 0.6875))


# what the TPU compiler said at 98,304 x 2048 x 1408, 64 groups,
# bfloat16, for a described v5e (ISSUE 30; PR 30 compiled 60 more)
ACCEPTED = [
    ("gmm", (512, 1024, 1408)), ("gmm_transposed", (512, 1024, 1408)),
    ("gmm", (512, 1408, 1024)), ("gmm_transposed", (512, 1408, 1024)),
    ("tgmm", (512, 512, 1408)), ("tgmm", (512, 1024, 768)),
    ("tgmm", (512, 1408, 512)), ("tgmm", (512, 1408, 768)),
    ("tgmm", (512, 768, 1024)),
    ("gmm", OLD), ("gmm_transposed", OLD), ("tgmm", OLD),
    ("tgmm", (512, 1280, 1024)), ("tgmm", (512, 1152, 1152)),
    ("gmm", (512, 1024, 1536)), ("gmm", (256, 512, 3072)),
    ("tgmm", (256, 1024, 1408)), ("tgmm", (256, 1408, 1152)),
    ("tgmm", (256, 1024, 1536)),
]
REFUSED = [
    ("tgmm", (512, 1024, 1408)), ("tgmm", (512, 2048, 768)),
    ("tgmm", (512, 1408, 1024)), ("tgmm", (512, 1408, 2048)),
    ("tgmm", (512, 1280, 1152)), ("tgmm", (512, 1152, 1280)),
    ("gmm", (512, 2048, 1024)), ("gmm", (512, 1024, 2048)),
    ("gmm", (1024, 1024, 1024)), ("gmm", (512, 1024, 1664)),
    ("gmm_transposed", (512, 1024, 1792)), ("gmm", (256, 1024, 2560)),
    ("tgmm", (256, 2048, 896)),
]


@pytest.mark.parametrize("call, tiles", ACCEPTED)
def test_the_vmem_count_fits_what_the_compiler_accepted(call, tiles):
    assert moe_ops.gmm_vmem_bytes(call, tiles, BF16) <= moe_ops.GMM_VMEM_BYTES


@pytest.mark.parametrize("call, tiles", REFUSED)
def test_the_vmem_count_is_over_for_what_the_compiler_refused(call, tiles):
    assert moe_ops.gmm_vmem_bytes(call, tiles, BF16) > moe_ops.GMM_VMEM_BYTES


def test_a_width_that_fits_no_whole_tile_takes_the_best_cover():
    """2944 = 23 x 128 as N of a ``gmm``: no whole tile of it fits
    (18.85 MiB beside a K tile of 512), and 23 is prime, so it is
    covered by the multiple of 128 that pads least, two tiles of 1536
    (3072, 4%): the larger of the tiles that pad so little, never 128s.
    3200 = 5 x 640 beside it pads nothing in tiles of 640."""
    tiles = moe_ops.gmm_tiles(4096, 4096, 2944, BF16, "gmm")
    assert tiles == (512, 1024, 1536)
    assert moe_ops.gmm_vmem_bytes("gmm", tiles, BF16) <= (
        moe_ops.GMM_VMEM_BYTES)
    assert moe_ops.gmm_fill(4096, 2944, tiles) == 2944 / 3072
    assert moe_ops.gmm_tiles(4096, 4096, 3200, BF16, "gmm") == (
        512, 1024, 640)
    # a dimension under the smallest tile is one tile, lanes whole
    assert moe_ops.gmm_tiles(512, 200, 384, BF16, "gmm") == (512, 256, 384)


def test_what_cannot_be_tiled_is_said():
    with pytest.raises(ValueError, match="whole row tiles"):
        moe_ops.gmm_tiles(1000, 2048, 1024, BF16, "gmm")
    with pytest.raises(ValueError, match="call must be one of"):
        moe_ops.gmm_tiles(1024, 2048, 1024, BF16, "gmm_t")


def _experts(width, dim=256, rows=1024, groups=4, seed=0):
    rng = np.random.RandomState(seed)
    make = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.1, BF16)
    sizes = jnp.asarray([300, 0, 513, rows - 813], jnp.int32)
    assert sizes.shape[0] == groups and int(sizes.sum()) == rows
    return (make(rows, dim), make(groups, dim, width),
            make(groups, dim, width), make(groups, width, dim), sizes)


def _swiglu_experts(matmul, x, gate, up, down, sizes):
    hidden = jax.nn.silu(matmul(x, gate, sizes)) * matmul(x, up, sizes)
    return matmul(hidden, down, sizes)


def _value_and_gradients(matmul, operands):
    x, gate, up, down, sizes = operands

    def loss(x, gate, up, down):
        y = _swiglu_experts(matmul, x, gate, up, down, sizes)
        return (y.astype(jnp.float32) ** 2).sum(), y

    (_, y), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True)(x, gate, up, down)
    return dict(zip(("y", "dx", "d_gate", "d_up", "d_down"), (y,) + grads))


def _assert_close(got, want, tolerance=0.02):
    for name in want:
        a, b = (np.asarray(t, np.float32) for t in (got[name], want[name]))
        assert np.abs(a - b).max() <= tolerance * np.abs(b).max(), name


@pytest.mark.parametrize("width", [384, 640])
def test_the_custom_vjp_matches_ragged_dot_s_autodiff(width):
    """A SwiGLU expert layer of a width that 1024 does not divide, over
    ragged groups (one empty, one ending inside a row tile), through
    the Pallas kernels in interpret mode: the value, the rows' gradient
    and the three weights' gradients."""
    operands = _experts(width)
    pallas = lambda rows, w, sizes: moe_ops.pallas_grouped_matmul(
        rows, w, sizes, True)
    tiles = moe_ops.projection_tiles(1024, 256, width, BF16)
    assert tiles["fwd"] == (512, 256, width)  # one tile, not 1024's share
    _assert_close(
        _value_and_gradients(pallas, operands),
        _value_and_gradients(jax.lax.ragged_dot, operands))


def test_the_custom_vjp_over_tiles_that_do_not_divide(monkeypatch):
    """With room for tiles of 512 only, a width of 640 is two tiles,
    the second a quarter full: the kernels mask the rest of it, as K
    tile and as N tile alike."""
    monkeypatch.setattr(moe_ops, "GMM_VMEM_BYTES", 5 * 2**20)
    tiles = moe_ops.projection_tiles(1024, 640, 640, BF16)
    assert tiles == {"fwd": (512, 512, 512), "d_rows": (512, 512, 512),
                     "d_weights": (256, 640, 640)}
    operands = _experts(640, dim=640, seed=1)
    pallas = lambda rows, w, sizes: moe_ops.pallas_grouped_matmul(
        rows, w, sizes, True)
    _assert_close(
        _value_and_gradients(pallas, operands),
        _value_and_gradients(jax.lax.ragged_dot, operands))


def _trace_layer(width, top_k):
    layer = MoeMlp(
        64, top_k=top_k, dispatch_impl="sorted", expert_dim=width,
        expert_act="swiglu", normalize_gates=False)
    x = jax.ShapeDtypeStruct((2, 1024, 2048), BF16)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))
    # traced, not lowered: the lines are written at trace time
    jax.eval_shape(lambda p, x: layer.apply(p, x), params, x)


@pytest.mark.parametrize("width, top_k, tiles, fill", [
    (1408, 6,
     "gate/up fwd=(512, 1024, 1408) d_rows=(512, 1408, 1024) "
     "d_weights=(256, 1024, 1408), down fwd=(512, 1408, 1024) "
     "d_rows=(512, 1024, 1408) d_weights=(256, 1408, 1024)", "100.00%"),
    (1024, 8,
     "gate/up fwd=(512, 1024, 1024) d_rows=(512, 1024, 1024) "
     "d_weights=(512, 1024, 1024), down fwd=(512, 1024, 1024) "
     "d_rows=(512, 1024, 1024) d_weights=(512, 1024, 1024)", "100.00%"),
])
def test_the_log_says_which_tiles_a_program_got(
        monkeypatch, caplog, width, top_k, tiles, fill):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with caplog.at_level(logging.INFO):
        _trace_layer(width, top_k)
    lines = [r.getMessage() for r in caplog.records]
    dispatch = [m for m in lines if m.startswith("moe dispatch resolved")]
    assert len(dispatch) == 1 and dispatch[0].endswith(
        "shared=0, experts' matmul=pallas_gmm)")
    said = [m for m in lines if m.startswith("moe experts' matmul tiles")]
    assert said == [
        "moe experts' matmul tiles (rows=%d experts=64 dim=2048 width=%d): "
        "%s, fill=%s" % (2048 * top_k, width, tiles, fill)]
    # once a distinct layer
    with caplog.at_level(logging.INFO):
        _trace_layer(width, top_k)
    assert sum(
        r.getMessage().startswith("moe experts' matmul tiles")
        for r in caplog.records) == 1


def test_no_tiles_line_where_ragged_dot_runs(caplog):
    with caplog.at_level(logging.INFO):
        _trace_layer(896, 2)
    lines = [r.getMessage() for r in caplog.records]
    assert any(m.endswith("experts' matmul=ragged_dot)") for m in lines)
    assert not any("matmul tiles" in m for m in lines)
