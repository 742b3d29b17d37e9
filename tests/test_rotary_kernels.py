"""The rotation of q and k as Pallas kernels (PR 56, ``ops/rotary.py``)
in interpret mode on the CPU against the module's own lines
(``rotary_embedding`` through ``rotate_xla``): the whole head, a partial
rotation, ``positions``, YaRN's table and amplitude, bfloat16 and
float32, differing q / kv head counts, forward and VJP;
``rotary_impl``'s table; ``Attention`` both ways with the line that says
which it got; and that a model the chooser refuses traces the step it
traced before. What interpret mode cannot see (the chip's tiling and
VMEM) is ``tests/test_rotary_tpu_compile.py``'s and
``scripts/rotary.py``'s.
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
from elasticdl_tpu.ops import rotary as R
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state

KERNELS = ("rotary_fwd", "rotary_bwd")
TPU, CPU = "tpu", "cpu"
YARN = T.YarnScaling(
    factor=64.0, original_max_position_embeddings=64, beta_fast=64.0,
    beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0)


def force_pallas(monkeypatch):
    """What a TPU backend would choose, run by the interpreter, 128
    rows a grid step: a tile is two iterations of the kernels' loop,
    256 tokens two tiles."""
    monkeypatch.setattr(R, "rotary_impl", lambda *a, **kw: "pallas")
    monkeypatch.setattr(R, "_TILES", (128,))
    for name in KERNELS:
        monkeypatch.setattr(R, name, functools.partial(
            getattr(R, name), interpret=True))


def operands(dtype, batch, heads, kv_heads, seq, dim, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (batch, h, seq, dim)
    return tuple(
        jax.random.normal(key, shape(h)).astype(dtype)
        for key, h in zip(keys, (heads, kv_heads, heads, kv_heads)))


def value_and_vjp(fn, q, k, gq, gk):
    """One program a call (a new one: ``fn`` is traced under what the
    test has patched by then)."""
    def both(q, k, gq, gk):
        out, vjp = jax.vjp(fn, q, k)
        return tuple(out) + tuple(vjp((gq, gk)))

    return jax.jit(both)(q, k, gq, gk)


def steps_apart(got, want, dtype):
    """(the share of elements that differ, the largest difference in
    units of the dtype's last place at the wanted magnitude, 1 at the
    least: the operands' own, where two terms cancel)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    ulp = np.maximum(np.abs(want), 1.0) * float(jnp.finfo(dtype).eps)
    return float(np.mean(got != want)), float(
        (np.abs(got - want) / ulp).max())


NAMES = ("q", "k", "dq", "dk")


# a tile of 128 rows is two iterations of the kernels' loop; 256 tokens
# are two tiles (a table block a tile); 256 lanes of which 64 rotate
# are two lane groups of which one is read
@pytest.mark.parametrize(
    "dtype,batch,heads,kv_heads,seq,dim,rotary_dim,given,scaling", [
        (jnp.float32, 2, 2, 2, 256, 128, None, False, None),
        (jnp.bfloat16, 1, 4, 4, 256, 128, None, False, None),
        (jnp.float32, 1, 2, 2, 128, 256, 64, False, None),
        (jnp.bfloat16, 1, 2, 2, 256, 256, 64, False, None),
        (jnp.bfloat16, 1, 2, 2, 128, 128, 64, False, YARN),
        (jnp.float32, 1, 2, 2, 128, 128, None, False, YARN),
        (jnp.bfloat16, 1, 4, 1, 256, 128, None, True, None),
        (jnp.float32, 1, 4, 2, 128, 128, None, True, None),
        (jnp.bfloat16, 2, 6, 2, 128, 256, None, False, None),
    ], ids=["float32-whole-head-two-tiles", "bfloat16-whole-head",
            "float32-64-of-256", "bfloat16-64-of-256-two-tiles",
            "bfloat16-64-of-128-yarn", "float32-yarn", "bfloat16-positions-"
            "4-heads-to-1", "float32-positions-4-heads-to-2",
            "bfloat16-256-wide-6-heads-to-2"])
def test_the_pair_against_the_module_s_lines(
        monkeypatch, dtype, batch, heads, kv_heads, seq, dim, rotary_dim,
        given, scaling):
    """Forward the lines' result; backward the lines' own arithmetic on
    the cotangent, turned by the negated positions. Element for element
    but where this backend contracts a multiply-add in one of the two
    programs (the CPU does; ``scripts/rotary.py`` counts 0 on the
    chip): then one step of the last place, in few places."""
    args = operands(dtype, batch, heads, kv_heads, seq, dim)
    positions = (
        jnp.tile(jnp.arange(seq // 2), 2) if given else None)
    rope = dict(base=1e4, positions=positions, scaling=scaling)
    lines = lambda rope: lambda q, k: (
        R.rotate_xla(q, rotary_dim, **rope),
        R.rotate_xla(k, rotary_dim, **rope))
    want = value_and_vjp(lines(rope), *args)
    exact = value_and_vjp(
        lines(rope), *(x.astype(jnp.float32) for x in args))
    back = dict(rope, positions=-(
        jnp.arange(seq) if positions is None else positions))
    turned_back = jax.jit(lines(back))(*args[2:])
    force_pallas(monkeypatch)
    got = value_and_vjp(
        functools.partial(R.rotate, rotary_dim=rotary_dim, **rope), *args)
    for name, g, w, e in zip(
            NAMES, got, want[:2] + tuple(turned_back), exact):
        assert g.dtype == dtype and g.shape == w.shape, name
        share, steps = steps_apart(g, w, dtype)
        if dtype == jnp.bfloat16:
            assert share < 1e-3 and steps <= 1.0, (name, share, steps)
        else:
            assert steps <= 2.0, (name, share, steps)
        assert steps_apart(g, e, dtype)[1] <= 2.0, name
    # against the lines' VJP, which rounds a lane's two terms apart and
    # adds them in the operand's dtype: the kernels are the closer
    # (in float32 the lines' VJP IS the exact one)
    error = lambda xs: sum(
        float(np.abs(np.asarray(x, np.float64) - np.asarray(e)).sum())
        for x, e in zip(xs, exact[2:]))
    if dtype == jnp.bfloat16:
        assert error(got[2:]) < error(want[2:])


def test_lanes_past_the_rotation_pass_through(monkeypatch):
    """Bit for bit, whatever the lanes that rotate hold; and only the
    lane group that rotates is a block of the kernel's."""
    force_pallas(monkeypatch)
    q, k, _, _ = operands(jnp.bfloat16, 1, 2, 2, 128, 256)
    q = q.at[0, 0, 5, 3].set(jnp.inf).at[0, 1, 7, 200].set(jnp.inf)
    out_q, out_k = R.rotate(q, k, rotary_dim=64)
    np.testing.assert_array_equal(
        np.asarray(out_q[..., 64:], np.float32),
        np.asarray(q[..., 64:], np.float32))
    np.testing.assert_array_equal(
        np.asarray(out_k[..., 64:], np.float32),
        np.asarray(k[..., 64:], np.float32))
    assert not np.isfinite(np.asarray(out_q[0, 0, 5, :64], np.float32)).all()
    assert np.isfinite(np.asarray(out_q[0, 0, 4], np.float32)).all()
    jaxpr = str(jax.make_jaxpr(
        lambda q, k: R.rotate(q, k, rotary_dim=64))(q, k))
    assert "f32[128,128]" in jaxpr and "f32[128,256]" not in jaxpr
    assert R.lane_groups(64) == 128
    assert R.lane_groups(128) == 128 and R.lane_groups(192) == 256


class FourDevices:
    size = 4
    axis_names = ("data",)


class TwoDevices:
    size = 2
    axis_names = ("data",)


@pytest.mark.parametrize("backend,dtype,head,lanes,seq,mesh,want", [
    (TPU, jnp.bfloat16, 128, 128, 16384, None, "pallas"),   # ouro2.6b
    (TPU, jnp.float32, 128, 128, 16384, None, "pallas"),
    (TPU, jnp.bfloat16, 256, 64, 16384, None, "pallas"),    # pythia1b
    (TPU, jnp.bfloat16, 128, 64, 32768, None, "pallas"),    # laguna full
    (TPU, jnp.bfloat16, 256, 256, 2048, None, "pallas"),
    (TPU, jnp.bfloat16, 128, 128, 384, None, "pallas"),
    (CPU, jnp.bfloat16, 128, 128, 16384, None, "xla"),
    (TPU, jnp.float16, 128, 128, 16384, None, "xla"),
    (TPU, jnp.bfloat16, 64, 64, 32768, None, "xla"),        # lfm2-8b
    (TPU, jnp.bfloat16, 8, 8, 128, None, "xla"),            # the tests'
    (TPU, jnp.bfloat16, 192, 64, 8192, None, "xla"),
    (TPU, jnp.bfloat16, 128, 128, 16384 + 64, None, "xla"),  # no tile
    (TPU, jnp.bfloat16, 128, 63, 16384, None, "xla"),
    (TPU, jnp.bfloat16, 128, 0, 16384, None, "xla"),
    (TPU, jnp.bfloat16, 128, 256, 16384, None, "xla"),
    (TPU, jnp.bfloat16, 128, 128, 16384, TwoDevices, "xla"),
    (TPU, jnp.bfloat16, 128, 128, 16384, FourDevices, "xla"),
])
def test_rotary_impl_chooses_from_what_it_sees(
        monkeypatch, backend, dtype, head, lanes, seq, mesh, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert R.rotary_impl(dtype, head, lanes, seq, mesh) == want


def test_rotary_impl_takes_a_region_manual_over_the_mesh(monkeypatch):
    """Where the caller has already opened a ``shard_map`` over the
    whole mesh q and k are one shard, and the kernels take them."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    seen = []

    def shard(x):
        seen.append(R.rotary_impl(jnp.bfloat16, 128, 128, 1024, mesh))
        return x

    jax.eval_shape(jax_compat.shard_map(
        shard, mesh=mesh, in_specs=P("data"), out_specs=P("data")),
        jnp.zeros(4))
    assert seen == ["pallas"]
    assert R.rotary_impl(jnp.bfloat16, 128, 128, 1024, mesh) == "xla"


@pytest.mark.parametrize("seq,heads,width,itemsize,want", [
    (16384, (16, 16), 128, 2, (1024, 2)),    # ouro2.6b-s16k
    (16384, (8, 8), 128, 2, (1024, 1)),      # pythia1b-s16k, 64 of 256
    (32768, (64, 8), 128, 2, (1024, 4)),     # laguna's window kind
    (32768, (16, 2), 128, 2, (1024, 1)),     # qwen3next80b-s32k
    (16384, (32, 4), 128, 2, (1024, 2)),     # sdar30b-bd-s8k
    (16384, (16, 16), 128, 4, (1024, 4)),
    (384, (4, 4), 128, 2, (128, 1)),
    (16384, (3, 3), 1024, 4, (512, 3)),      # a head a step: fewer rows
    (16384 + 64, (16, 16), 128, 2, None),
])
def test_the_block_a_grid_step_takes(seq, heads, width, itemsize, want):
    """The most rows, then the fewest steps over the heads, whose
    double-buffered blocks fit 24 MiB."""
    assert R.step_block(seq, heads, width, itemsize) == want


def test_the_kernels_names_hold_none_of_the_readers_words():
    """``benchmark/lib/*_trace.py`` charge a Mosaic kernel to a layer by
    a word of its name; the rotation's time stays with ``<kind>/rotary``
    by its scope."""
    for name in KERNELS:
        assert getattr(R, name).__name__ == name
        for word in ("flash", "dsa_", "gdn", "conv", "mhc", "gmm"):
            assert word not in name


# ------------------------------------------------------ the attention

def attention_gradients(dtype=jnp.float32, **fields):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 256)).astype(dtype)
    mixer = T.Attention(
        num_heads=2, attention_impl="xla", kind_scope="attn_full", **fields)
    params = jax.jit(mixer.init)(jax.random.PRNGKey(2), x)["params"]
    target = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda params, x: jnp.sum(
        mixer.apply({"params": params}, x) * target)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)


@pytest.mark.parametrize("fields,said", [
    ({}, "heads=2 kv_heads=2 head=128 lanes=128 tokens=128 "
         "positions=rows yarn=no"),
    (dict(num_kv_heads=1, rotary_dim=64, rope_scaling=YARN),
     "heads=2 kv_heads=1 head=128 lanes=64 tokens=128 "
     "positions=rows yarn=yes"),
], ids=["whole-head", "64-of-128-yarn-2-heads-to-1"])
def test_the_attention_both_ways(monkeypatch, caplog, fields, said):
    """The module's output and every parameter's gradient with the
    kernels as with its own lines, and the line that says which ran,
    once a call shape."""
    R.log_choice.cache_clear()
    with caplog.at_level(logging.INFO):
        want = attention_gradients(**fields)
    assert "rotary impl=xla " + said in caplog.text
    force_pallas(monkeypatch)
    with caplog.at_level(logging.INFO):
        got = attention_gradients(**fields)
    R.log_choice.cache_clear()
    assert caplog.text.count("rotary impl=pallas " + said) == 1
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got[1])[0],
            jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-4, err_msg=jax.tree_util.keystr(path))


def test_the_pair_keeps_its_table_and_no_more(monkeypatch):
    """Residuals: ``cos`` and ``sin``; neither q nor k."""
    from jax._src.ad_checkpoint import saved_residuals

    force_pallas(monkeypatch)
    q, k, _, _ = operands(jnp.bfloat16, 1, 2, 2, 128, 128)
    shapes = sorted(r[0].shape for r in saved_residuals(
        lambda q, k: R.rotate(q, k), q, k))
    assert shapes == [(128, 128), (128, 128)]


def _attention_jaxpr(mixer, seq, dim, dtype=jnp.bfloat16):
    x = jax.ShapeDtypeStruct((2, seq, dim), dtype)
    params = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"]

    def loss(p, x):
        out = mixer.apply({"params": p}, x)
        out = out[0] if isinstance(out, tuple) else out
        return out.astype(jnp.float32).sum()

    return str(jax.make_jaxpr(jax.grad(loss))(params, x))


@pytest.mark.parametrize("fields,seq,dim,mesh", [
    (dict(num_heads=4), 128, 256, False),
    (dict(num_heads=2), 40, 256, False),
    (dict(num_heads=2), 128, 256, True),
    (dict(num_heads=2, indexer=T.IndexerDims(
        heads=2, head_dim=64, topk=32)), 128, 256, False),
], ids=["64-wide-head", "no-tile-divides-the-sequence",
        "a-mesh-that-is-not-manual", "the-indexer-s-64-lanes"])
def test_what_the_chooser_refuses_runs_the_module_s_lines(
        monkeypatch, fields, seq, dim, mesh):
    """On a TPU backend too: the 64-wide head, the sequence no tile
    divides and the mesh trace no kernel; the indexer's own q and k keep
    their lines beside the head's pair."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    mesh = build_mesh(MeshConfig(dp=2), num_devices=2) if mesh else None
    mixer = T.Attention(attention_impl="xla", mesh=mesh, **fields)
    jaxpr = _attention_jaxpr(mixer, seq, dim)
    pairs = 1 if "indexer" in fields else 0
    for name in KERNELS:
        assert len(re.findall(
            r"jit\[\s*name=%s\b" % name, jaxpr)) == pairs, name


def test_latent_attention_keeps_its_lines(monkeypatch):
    """Its 64-wide rope part is not a head of whole lane rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    mixer = T.LatentAttention(
        num_heads=2, attention_impl="xla", dims=T.LatentDims(
            q_lora_rank=None, kv_lora_rank=64, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128))
    assert "rotary_" not in _attention_jaxpr(mixer, 128, 256)


def test_a_tpu_backend_traces_the_pair(monkeypatch):
    """And what it does not refuse is the two kernels, q and k in one
    call: the forward once and the backward once."""
    monkeypatch.setattr(jax, "default_backend", lambda: TPU)
    jaxpr = _attention_jaxpr(
        T.Attention(num_heads=2, num_kv_heads=1, attention_impl="xla"),
        256, 256)
    for name in KERNELS:
        assert len(re.findall(r"jit\[\s*name=%s\b" % name, jaxpr)) == 1, name


# ------------------------------------------------------ the step's trace

def _sha(text):
    return hashlib.sha256(
        re.sub(r" at 0x[0-9a-f]+", "", text).encode()).hexdigest()[:16]


def _dense(width):
    return T, T.TransformerLM(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=width,
        attention_impl="xla", remat=True, remat_policy="full")


def _partial_yarn():
    return moe_transformer, moe_transformer.MoeTransformerLM(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=256,
        num_kv_heads=1, rotary_dim=64, rope_scaling=YARN, num_experts=4,
        top_k=2, expert_dim=16, moe_every=1, attention_impl="xla")


# sha256 of the jaxpr of the train step of a tiny ``TransformerLM``
# (two GPT-NeoX blocks, the whole head rotating) at the tests' 8-wide
# head and at a 128-wide one, where a TPU would take the kernels and the
# CPU does not, and of a tiny ``MoeTransformerLM`` whose 128-wide heads
# rotate 64 lanes by YaRN's table, 2 heads to 1; recorded on the parent
# of PR 56 (3d16fea) with the pinned jax
PARENT_STEPS = {
    "dense-8-wide-head": (functools.partial(_dense, 16), "792722d95c05ab09"),
    "dense-128-wide-head": (
        functools.partial(_dense, 256), "7d6064988381d2ff"),
    "64-of-128-yarn": (_partial_yarn, "b54b49b83325b2c4"),
}


@pytest.mark.parametrize("case", sorted(PARENT_STEPS))
def test_a_tiny_model_traces_the_parent_s_step_on_the_cpu(case):
    build, want = PARENT_STEPS[case]
    module, model = build()
    tokens = jnp.zeros((2, 128), jnp.int32)
    tx = module.optimizer()
    # the trace reads shapes and dtypes: no parameter is initialised
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.PRNGKey(0), tokens))
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((2,), jnp.float32)}
    step = make_train_step(model, module.loss, tx, jnp.bfloat16, health=True)
    assert _sha(str(jax.make_jaxpr(step)(state, batch))) == want
