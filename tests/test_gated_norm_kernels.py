"""The gated output norm as Pallas kernels (PR 65,
``ops/gated_norm.py``) in interpret mode on the CPU against the
modules' lines: the three forms (the gate after the norm, SiLU or
sigmoid; SiLU before it) over the three layouts (one norm over the
whole row and eight groups of a row, both by columns as the selective
scan hands its output; a head's 128 lanes with the delta rules'
transposition and the gate's columns at an offset), forward and VJP;
the three mixers both ways, with the line that says which they got;
``gated_norm_impl``'s table. Every case's pair is built and
interpreted ONCE, in one program a module. What interpret mode cannot
see (the chip's tiling and VMEM) is ``tests/test_gated_norm_tpu_compile
.py``'s and ``scripts/gated_norm.py``'s.
"""

import functools
import logging

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.ops import gated_norm as G

KERNELS = ("gated_norm_fwd", "gated_norm_bwd")
TPU, CPU = "tpu", "cpu"
EPS = 1e-5
# layout -> (normed segments, lanes of one, the rows of a chunk where
# x lies by columns (None: by heads, read by two segments of the
# sequence), the gate's array's width, the gate's first column)
LAYOUTS = {
    "whole-row": (1, 256, 128, 384, 0),
    "8-groups": (8, 128, 128, 1024 + 64, 0),
    "per-head-transposed": (4, 128, None, 1024, 512),
}
SEQ = 256
NAMES = ("out", "dx", "dz", "dscale")


def lines(x, z, scale, form, lanes, z_offset):
    """The modules' lines, one function for the three (``Mamba2Mixer``'s
    word for word with the gate's kind and place a choice;
    ``nn.RMSNorm``'s arithmetic, ``x (rsqrt(mean(x^2) + eps) scale)``,
    with the gate's line after it, where the module rounds the norm to
    the parameters' dtype between the two)."""
    kind, gate_first = G.FORMS[form]
    if x.ndim == 4:
        x = x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)
    batch, seq, inner = x.shape
    z = z[..., z_offset:z_offset + inner].astype(jnp.float32)
    gate = nn.silu(z) if kind == "silu" else jax.nn.sigmoid(z)
    scale = jnp.tile(scale.astype(jnp.float32), inner // scale.shape[0])
    gated = x.astype(jnp.float32) * gate if gate_first else x.astype(
        jnp.float32)
    by_lanes = gated.reshape(batch, seq, inner // lanes, lanes)
    var = jnp.mean(by_lanes * by_lanes, axis=-1, keepdims=True)
    mul = jax.lax.rsqrt(var + EPS)
    if gate_first:
        return ((by_lanes * mul).reshape(gated.shape) * scale).astype(
            x.dtype)
    return ((by_lanes * (mul * scale.reshape(-1, lanes))).reshape(
        gated.shape) * gate).astype(x.dtype)


def operands(layout, dtype, seed=0):
    segments, lanes, rows, z_width, _ = LAYOUTS[layout]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    inner = segments * lanes
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32)
    return (
        normal(keys[0], (1, SEQ, inner) if rows
               else (1, segments, SEQ, lanes)).astype(dtype),
        normal(keys[1], (1, SEQ, z_width)).astype(dtype),
        (1.0 + 0.1 * normal(
            keys[2], (inner if rows else lanes,))).astype(dtype),
        normal(keys[3], (1, SEQ, inner)).astype(dtype))


def value_and_vjp(fn, x, z, scale, grad):
    out, vjp = jax.vjp(fn, x, z, scale)
    return (out,) + tuple(vjp(grad))


CASES = [(form, layout, jnp.bfloat16)
         for form in G.FORMS for layout in LAYOUTS] + [
    ("norm_sigmoid", "8-groups", jnp.float32),
    ("silu_norm", "per-head-transposed", jnp.float32)]


@pytest.fixture(scope="module")
def results():
    """Every case's (the pair's, the lines', the lines' in float32)
    value and VJP from ONE program: 128 rows a grid step (two tiles,
    or two chunks, of the 256 tokens), 64 rows (by columns: 64 of a
    group's channels) an iteration of the loop."""
    held = dict(interpret=True, tile=128, chunk=64)

    def all_cases():
        out = {}
        for form, layout, dtype in CASES:
            _, lanes, rows, _, z_offset = LAYOUTS[layout]
            args = operands(layout, dtype)
            by_lines = functools.partial(
                lines, form=form, lanes=lanes, z_offset=z_offset)
            out[form, layout, jnp.dtype(dtype).name] = (
                value_and_vjp(lambda x, z, scale: G.gated_norm(
                    x, z, scale, form, lanes, EPS, z_offset,
                    "test/out_norm", rows, 1 if rows else 2), *args),
                value_and_vjp(by_lines, *args),
                value_and_vjp(
                    by_lines, *(a.astype(jnp.float32) for a in args)))
        return out

    with pytest.MonkeyPatch.context() as patch:
        for name in KERNELS:
            patch.setattr(G, name, functools.partial(
                getattr(G, name), **held))
        return jax.jit(all_cases)()


def roundings(got, want, dtype):
    """(the share of elements that differ, the largest difference in
    roundings of ``dtype`` at the wanted magnitude, the array's mean
    magnitude at the least: a small element is a difference of large
    ones)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    size = np.maximum(np.abs(want), np.abs(want).mean())
    return float(np.mean(got != want)), float(
        (np.abs(got - want) / size).max() / float(jnp.finfo(dtype).eps))


@pytest.mark.parametrize("direction", ["forward", "vjp"])
@pytest.mark.parametrize(
    "form,layout,dtype", CASES, ids=[
        "%s-%s-%s" % (form, layout, jnp.dtype(dtype).name)
        for form, layout, dtype in CASES])
def test_the_pair_against_the_module_s_lines(
        results, form, layout, dtype, direction):
    """Within ONE rounding of the result's dtype, not element for
    element: a lane sum's order differs, the sigmoid is the hyperbolic
    tangent's and ``dx``'s products are formed in another order. In
    bfloat16 fewer than 2 elements in 1,000 are unequal (read: 0 to 8
    in 100,000; of the scale's gradient, a float32 sum of 256 rows
    rounded once, at most 2 of 256 to 1,024); in float32 every result
    is within 64 roundings of the lines' at the array's scale (``dz`` of a gate near 0 is a
    difference of two products)."""
    segments, lanes, rows, z_width, z_offset = LAYOUTS[layout]
    got, want, exact = results[form, layout, jnp.dtype(dtype).name]
    inner = segments * lanes
    assert got[0].shape == (1, SEQ, inner)
    assert got[1].shape == (
        (1, SEQ, inner) if rows else (1, segments, SEQ, lanes))
    picked = range(1) if direction == "forward" else range(1, 4)
    for name, g, w, e in [
            (NAMES[i], got[i], want[i], exact[i]) for i in picked]:
        if name == "dz":
            # the whole array's cotangent: the pair's pad writes the
            # zeros outside the gate's columns
            assert g.shape == (1, SEQ, z_width)
            assert not np.asarray(g[..., :z_offset], np.float32).any()
            assert not np.asarray(
                g[..., z_offset + inner:], np.float32).any()
        assert g.dtype == dtype and g.shape == w.shape, name
        share, steps = roundings(g, w, dtype)
        if dtype == jnp.bfloat16:
            few = 1e-2 if name == "dscale" else 2e-3
            assert share < few and steps <= 1.0, (name, share, steps)
            assert roundings(g, e, dtype)[1] <= 1.0, name
        else:
            assert steps <= 64.0, (name, share, steps)


@pytest.mark.parametrize(
    "backend,dtype,lanes,heads,seq,mesh,offset,rows,want", [
        (TPU, jnp.bfloat16, 4096, 1, 8192, None, 0, 256, "pallas"),  # granite
        (TPU, jnp.bfloat16, 512, 8, 8192, None, 0, 256, "pallas"),  # nemotron
        (TPU, jnp.bfloat16, 128, 32, 32768, None, 8192, None, "pallas"),
        (TPU, jnp.bfloat16, 128, 32, 32768, None, 0, None, "pallas"),  # kimi
        (TPU, jnp.float32, 128, 32, 32768, None, 0, None, "pallas"),
        (TPU, jnp.bfloat16, 256, 6, 384, None, 256, None, "pallas"),
        (TPU, jnp.float32, 512, 8, 8192, None, 0, 128, "pallas"),
        (CPU, jnp.bfloat16, 128, 32, 32768, None, 0, None, "xla"),
        (CPU, jnp.bfloat16, 4096, 1, 8192, None, 0, 256, "xla"),
        (TPU, jnp.float16, 128, 32, 32768, None, 0, None, "xla"),
        (TPU, jnp.bfloat16, 16, 4, 128, None, 0, None, "xla"),   # the tests'
        (TPU, jnp.bfloat16, 64, 64, 8192, None, 0, None, "xla"),
        (TPU, jnp.bfloat16, 128, 32, 32768 + 64, None, 0, None, "xla"),
        (TPU, jnp.bfloat16, 128, 32, 32768, None, 8192 + 64, None, "xla"),
        (TPU, jnp.bfloat16, 4096, 1, 8192, None, 0, 64, "xla"),  # half a row
        (TPU, jnp.bfloat16, 4096, 1, 8192 + 128, None, 0, 256, "xla"),
        (TPU, jnp.bfloat16, 4096, 1, 8192, None, 512, 256, "xla"),
        (TPU, jnp.float32, 32768, 1, 8192, None, 0, 256, "xla"),  # no block
        (TPU, jnp.bfloat16, 128, 32, 32768, "two", 0, None, "xla"),
        (TPU, jnp.bfloat16, 4096, 1, 8192, "four", 0, 256, "xla"),
    ])
def test_gated_norm_impl_chooses_from_what_it_sees(
        monkeypatch, backend, dtype, lanes, heads, seq, mesh, offset, rows,
        want):
    class Devices:
        size = {"two": 2, "four": 4}.get(mesh)
        axis_names = ("data",)

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert G.gated_norm_impl(
        dtype, lanes, heads, seq, Devices if mesh else None, offset,
        rows) == want


def test_by_heads_the_row_tile_divides_a_segment(monkeypatch):
    """``o`` is read by the rule's segments: 8 segments of 4,096 rows
    take tiles of 1,024; 3 segments of 128 + 64 rows have no tile."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    assert G.gated_norm_impl(
        jnp.bfloat16, 128, 32, 32768, None, 8192, None, 8) == "pallas"
    assert G._block(128, 32, 32768, 2, 8192, None, 8) == (1024, 4)
    assert G._block(128, 32, 1024, 2, 0, None, 8) == (128, 4)
    assert G.gated_norm_impl(
        jnp.bfloat16, 128, 32, 576, None, 0, None, 3) == "xla"
    assert G.gated_norm_impl(
        jnp.bfloat16, 128, 32, 1024, None, 0, None, 3) == "xla"


@pytest.mark.parametrize("seq,lanes,heads,itemsize,offset,want", [
    (32768, 128, 32, 2, 8192, (1024, 4)),   # qwen3next80b-s32k
    (32768, 128, 32, 2, 0, (1024, 4)),      # kimi-linear48b-s32k
    (32768, 128, 32, 2, 256, (1024, 2)),    # the gate's block a whole one
    (32768, 128, 6, 4, 0, (1024, 3)),
    (32768, 256, 8, 2, 0, (1024, 2)),
    (384, 128, 2, 2, 0, (128, 2)),
    (8192 + 64, 128, 32, 2, 0, None),
    (8192, 128, 32, 2, 64, None),
])
def test_the_block_a_grid_step_takes_by_heads(
        seq, lanes, heads, itemsize, offset, want):
    assert G.step_block(seq, lanes, heads, itemsize, offset) == want


@pytest.mark.parametrize("lanes,groups,rows,itemsize,offset,want", [
    (4096, 1, 256, 2, 0, 1),     # granite4h-micro-s8k: 4,096 channels
    (512, 8, 256, 2, 0, 8),      # nemotron3-nano-s8k: the same 4,096
    (512, 8, 256, 4, 0, 4),
    (512, 8, 256, 2, 1024, 2),   # the gate's block a whole one
    (512, 8, 256, 2, 256, None),
    (4096, 1, 128, 4, 0, 1),
    (4096, 1, 256, 4, 0, None),  # not one group fits
])
def test_the_groups_a_grid_step_takes_by_columns(
        lanes, groups, rows, itemsize, offset, want):
    assert G.column_block(lanes, groups, rows, itemsize, offset) == want


def test_the_kernels_names_hold_none_of_the_readers_words():
    """``benchmark/lib/gdn_trace.py``, ``kda_trace.py`` and
    ``ssm_trace.py`` charge a Mosaic kernel to the SCAN by a word of
    its name; the norm's time stays with ``*/out_norm`` by its scope."""
    x, *rest = operands("per-head-transposed", jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(functools.partial(
        G.gated_norm_bwd, form="norm_silu", lanes=128, eps=EPS,
        z_offset=512, interpret=True))(x[None], *rest))
    assert "name=gated_norm_bwd" in jaxpr
    for name in KERNELS:
        assert getattr(G, name).__name__ == name
        assert not name.startswith("ssd")
        for word in ("gdn", "kda", "flash", "conv", "mhc", "gmm"):
            assert word not in name


# ------------------------------------------------------ the mixers

def force_pallas(monkeypatch):
    """What a TPU backend would choose, run by the interpreter, 128
    rows a grid step."""
    monkeypatch.setattr(G, "gated_norm_impl", lambda *a, **kw: "pallas")
    monkeypatch.setattr(G, "_TILES", (128,))
    for name in KERNELS:
        monkeypatch.setattr(G, name, functools.partial(
            getattr(G, name), interpret=True))


MIXERS = {
    "GatedDeltaNet": (
        lambda: T.GatedDeltaNet(T.GatedDeltaDims(1, 2, 128, 128, 4, chunk=64)),
        "gated norm form=norm_silu lanes=128 heads=2 impl=%s tile=%s "
        "(tokens=128)", ("out_norm", "scale")),
    "KimiDeltaAttention": (
        lambda: T.KimiDeltaAttention(T.KdaDims(2, 128, 4, 16, chunk=64)),
        "gated norm form=norm_sigmoid lanes=128 heads=2 impl=%s tile=%s "
        "(tokens=128)", ("out_norm", "scale")),
    "Mamba2Mixer": (
        lambda: T.Mamba2Mixer(T.Mamba2Dims(8, 32, 16, 2, 4, chunk=128)),
        "gated norm form=silu_norm lanes=128 heads=2 impl=%s tile=%s "
        "(tokens=128)", ("out_norm_scale",)),
}


@pytest.mark.parametrize("name", sorted(MIXERS))
def test_a_mixer_takes_the_pair_and_says_so(monkeypatch, caplog, name):
    """The module on its lines and on the pair, float32, in one
    program: the same parameter tree (``out_norm/scale`` in the delta
    rules' mixers, ``out_norm_scale`` in Mamba-2's), the same output
    and the same gradients of the input and of every parameter to 1e-4
    of their scale; the log says ``impl=`` once a shape, however many
    layers ask."""
    build, line, leaf = MIXERS[name]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 64))
    layer = build()
    G.log_choice.cache_clear()
    caplog.set_level(logging.INFO, logger="elasticdl_tpu.ops.gated_norm")
    variables = jax.jit(layer.init)(jax.random.PRNGKey(1), x)

    def loss(variables, x):
        out = layer.apply(variables, x)
        out = out[0] if isinstance(out, tuple) else out
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).sum()

    def both(variables, x):
        want = jax.value_and_grad(loss, argnums=(0, 1))(variables, x)
        with pytest.MonkeyPatch.context() as patch:
            force_pallas(patch)
            assert jax.tree_util.tree_structure(jax.eval_shape(
                layer.init, jax.random.PRNGKey(1), x)
            ) == jax.tree_util.tree_structure(variables)
            return want, jax.value_and_grad(loss, argnums=(0, 1))(
                variables, x)

    want, got = jax.jit(both)(variables, x)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("gated norm")]
    assert said == [line % ("xla", None), line % ("pallas", 128)]
    scale = variables["params"]
    for key in leaf:
        scale = scale[key]
    assert scale.shape == ((128,) if len(leaf) == 2 else (256,))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-4 * float(np.abs(np.asarray(w)).max()) + 1e-12)
