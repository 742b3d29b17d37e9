"""One chip's share of an expert layer (ISSUE 31, the first half of
expert parallelism): ``ops/moe.py``'s ``sort_held`` / ``dispatch_held``
/ ``combine_held`` and ``MoeMlp`` with ``held_experts``, at small sizes
on the CPU. The router runs over ALL experts; only the pairs of the
held experts get a row. That the shares of all the chips of a layer,
the shared expert counted once, add up to the whole layer (the test
that ties the share to the model) is ``test_held_experts_shares.py``'s:
one file summed past the rule's 100 s (``ROADMAP.md`` Queue 3 item
12)."""

import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.models.moe_transformer import MoeMlp, MoeTransformerLM
from elasticdl_tpu.models.transformer import GatedDeltaDims
from elasticdl_tpu.ops import moe as moe_ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sort_held_groups_the_held_pairs_and_counts_all_loads():
    experts = jnp.asarray(
        [[0, 5], [6, 1], [5, 7], [2, 5], [4, 6], [5, 4]], jnp.int32)
    pairs, valid, sizes, loads, held, dropped = moe_ops.sort_held(
        experts, 8, first=4, count=3, buffer_rows=8)
    # experts 4, 5, 6 are held: pairs (token * 2 + choice) by expert
    assert list(np.asarray(loads)) == [1, 1, 1, 0, 2, 4, 2, 1]
    assert int(held) == 8 and int(dropped) == 0
    assert list(np.asarray(pairs)) == [8, 11, 1, 4, 7, 10, 2, 9]
    assert bool(valid.all())
    assert list(np.asarray(sizes)) == [2, 4, 2]


def test_a_buffer_with_room_runs_the_rows_that_carry_a_pair():
    experts = jnp.asarray([[0, 5], [6, 1], [5, 7], [2, 3]], jnp.int32)
    pairs, valid, sizes, loads, held, dropped = moe_ops.sort_held(
        experts, 8, first=4, count=3, buffer_rows=6)
    assert int(held) == 3 and int(dropped) == 0
    assert list(np.asarray(valid)) == [True] * 3 + [False] * 3
    assert list(np.asarray(pairs))[:3] == [1, 4, 2]
    # the held experts' TRUE sizes: the grouped matmul stops with them,
    # and no group is given the rows that carry no pair
    assert list(np.asarray(sizes)) == [0, 2, 1]
    assert int(sizes.sum()) == int(valid.sum()) == 3
    # a buffer larger than all pairs is cut to them
    assert moe_ops.sort_held(experts, 8, 4, 3, 100)[0].shape == (8,)
    # what a step runs of its buffer: the eighth of it that holds the
    # held pairs (the first where there are none)
    run = lambda held, rows: int(moe_ops.rows_run(jnp.int32(held), rows))
    assert [run(h, 16) for h in (0, 1, 2, 3, 13, 16, 40)] == [
        2, 2, 2, 4, 14, 16, 16]
    assert run(0, 6) == run(3, 6) == 6  # 8 does not divide it: whole
    stats = moe_ops.routing_stats(
        jnp.full((4, 8), 0.125), loads, 2, held=held, dropped=dropped,
        buffer_rows=6)
    assert float(stats["rows_run"]) == 6 and float(stats["rows_buffer"]) == 6


def test_the_permutes_chunks_and_prefixes_divide_the_buffer():
    assert moe_ops.held_prefixes(49152, 8) == tuple(
        range(6144, 49153, 6144))
    assert moe_ops.held_prefixes(65536, 4) == (16384, 32768, 49152, 65536)
    assert moe_ops.held_prefixes(16, 8) == (2, 4, 6, 8, 10, 12, 14, 16)
    assert moe_ops.held_prefixes(6, 8) == (6,)  # 8 does not divide it
    assert (moe_ops.HELD_PREFIXES, moe_ops.HELD_BACKWARD_PREFIXES) == (8, 4)
    assert moe_ops.held_chunk_rows(49152) == 4096  # SDAR's: 12 chunks
    assert moe_ops.held_chunk_rows(65536) == 4096  # Qwen3-Next's: 16
    # a buffer that whole chunks do not divide is gathered as one
    assert moe_ops.held_chunk_rows(6144) == 6144
    assert moe_ops.held_chunk_rows(72) == 72


def test_a_pair_without_a_row_is_counted_not_hidden():
    experts = jnp.asarray([[4, 5], [4, 5], [4, 6], [5, 6]], jnp.int32)
    pairs, valid, sizes, _, held, dropped = moe_ops.sort_held(
        experts, 8, first=4, count=3, buffer_rows=5)
    assert int(held) == 8 and int(dropped) == 3
    assert bool(valid.all()) and list(np.asarray(sizes)) == [3, 2, 0]
    stats = moe_ops.routing_stats(
        jnp.full((4, 8), 0.125), jnp.asarray([0, 0, 0, 0, 3, 3, 2, 0]), 2,
        held=held, dropped=dropped, buffer_rows=5)
    assert float(stats["held"]) == 8 and float(stats["dropped"]) == 3
    # a buffer that is too small runs whole
    assert float(stats["rows_run"]) == float(stats["rows_buffer"]) == 5
    # the dropless path's counters are what they were
    whole = moe_ops.routing_stats(
        jnp.full((4, 8), 0.125), jnp.asarray([1] * 8), 2)
    assert "held" not in whole and "rows_run" not in whole
    assert float(whole["dropped"]) == 0


def test_dispatch_and_combine_are_each_other_s_transpose():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 4), jnp.float32)
    experts = jnp.asarray(rng.randint(0, 8, (6, 2)), jnp.int32)
    gates = jnp.asarray(rng.rand(6, 2), jnp.float32)
    pairs, valid, _, _, held, _ = moe_ops.sort_held(experts, 8, 2, 3, 8)
    rows = moe_ops.dispatch_held(x, pairs, valid, 2)
    np.testing.assert_array_equal(rows, x[np.asarray(pairs) // 2])
    y = moe_ops.combine_held(rows, gates, pairs, valid)
    here = (np.asarray(experts) >= 2) & (np.asarray(experts) < 5)
    want = (np.asarray(gates) * here).sum(1)[:, None] * np.asarray(x)
    np.testing.assert_allclose(y, want, atol=1e-6)
    # the gradient of a gather is a scatter-add and the other way round
    g = jax.grad(lambda x: moe_ops.combine_held(
        moe_ops.dispatch_held(x, pairs, valid, 2), gates, pairs,
        valid).sum())(x)
    np.testing.assert_allclose(
        g, np.broadcast_to((np.asarray(gates) * here).sum(1)[:, None],
                           x.shape), atol=1e-6)


# --- the rows past the last held one are nobody's ---------------------

# the held pairs of a layer of 16 rows in chunks of 4 and prefixes of 2
# (the dispatch's transpose: 4; 1,536 rows in tiles and chunks of 512
# and prefixes of 192 and 384 under the Pallas kernels): none, one, the
# middle of a tile, exactly a tile, the whole buffer, more than it holds
HELD_CASES = {"none": 0, "one": 1, "mid-tile": 6, "a-tile": 4, "full": 16,
              "over": 20}


def _routed(tokens, k, held, seed):
    """(tokens, k) experts of 8 of which exactly ``held`` pairs, at
    seeded places, fall on experts 2-4 (the share ``(2, 3)``)."""
    rng = np.random.RandomState(seed)
    flat = rng.choice([0, 1, 5, 6, 7], tokens * k)
    flat[rng.permutation(tokens * k)[:held]] = rng.choice([2, 3, 4], held)
    return jnp.asarray(flat.reshape(tokens, k), jnp.int32)


@jax.custom_vjp
def _spoil(rows, valid):
    """NaN in every row without a pair, and in its gradient's."""
    return jnp.where(valid[:, None], rows, jnp.nan)


_spoil.defvjp(
    lambda rows, valid: (_spoil(rows, valid), valid),
    lambda valid, d: (jnp.where(valid[:, None], d, jnp.nan), None))


def _pr35_sort_held(experts, num_experts, first, count, buffer_rows):
    """PR 35's contract: the rows past the held pairs are the last
    group's, and every stage runs the whole buffer."""
    pairs, valid, sizes, loads, held, dropped = moe_ops.sort_held(
        experts, num_experts, first, count, buffer_rows)
    spare = pairs.shape[0] - sizes.sum()
    return pairs, valid, sizes.at[-1].add(spare), loads, held, dropped


def _pr35_dispatch(x, pairs, valid, k):
    return jnp.take(x, pairs // k, axis=0)


def _pr35_combine(rows, gates, pairs, valid):
    tokens, k = gates.shape
    gate_of = jnp.where(valid, jnp.take(gates.reshape(-1), pairs), 0.0)
    y = jnp.zeros((tokens, rows.shape[-1]), jnp.float32).at[pairs // k].add(
        rows.astype(jnp.float32) * gate_of[:, None])
    return y.astype(rows.dtype)


def _held_layer(x, gates, w_in, w_out, experts, buffer_rows, matmul,
                sort=None, dispatch=None, combine=None, spoil=False):
    """The held expert layer by hand (a GELU-free one: silu between two
    grouped matmuls), with NaN planted in the spare rows of ``rows``,
    ``hidden``, ``out`` and of every gradient that reaches them."""
    sort = sort or moe_ops.sort_held
    dispatch = dispatch or moe_ops.dispatch_held
    combine = combine or moe_ops.combine_held
    k = gates.shape[1]
    pairs, valid, sizes, _, _, dropped = sort(experts, 8, 2, 3, buffer_rows)
    planted = (lambda a: _spoil(a, valid)) if spoil else (lambda a: a)
    rows = planted(dispatch(x, pairs, valid, k))
    hidden = planted(matmul(rows, w_in, sizes))
    out = planted(matmul(jax.nn.silu(hidden), w_out, sizes))
    return combine(out, gates, pairs, valid), dropped


def _operands(tokens, k, dim, width, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda key, *shape: jax.random.normal(
        key, shape, jnp.float32).astype(dtype)
    return (normal(keys[0], tokens, dim),
            jax.nn.softmax(normal(keys[1], tokens, k).astype(jnp.float32)),
            normal(keys[2], 3, dim, width) * 0.3,
            normal(keys[3], 3, width, dim) * 0.3,
            normal(keys[4], tokens, dim))


def _value_and_grads(layer, operands, experts):
    """y, dropped and (dx, d_gates, d_w_in, d_w_out) under a seeded
    cotangent."""
    x, gates, w_in, w_out, cotangent = operands

    def scalar(x, gates, w_in, w_out):
        y, dropped = layer(x, gates, w_in, w_out, experts)
        return (y.astype(jnp.float32) * cotangent).sum(), (y, dropped)

    (_, (y, dropped)), grads = jax.value_and_grad(
        scalar, argnums=(0, 1, 2, 3), has_aux=True)(x, gates, w_in, w_out)
    return y, int(dropped), grads


@pytest.mark.parametrize("chunk", [4, 16], ids=["chunks-of-4", "one-chunk"])
@pytest.mark.parametrize(
    "held", list(HELD_CASES.values()), ids=list(HELD_CASES))
def test_a_nan_in_a_spare_row_reaches_nothing(monkeypatch, held, chunk):
    """``ragged_dot`` on the CPU: y, dx, d_gates and both weights'
    gradients are to the bit what they are without the NaNs, and a pair
    over the buffer is counted."""
    monkeypatch.setattr(moe_ops, "HELD_CHUNK_ROWS", chunk)
    experts = _routed(16, 2, held, seed=held)
    operands = _operands(16, 2, 8, 8, jnp.float32)
    layer = functools.partial(
        _held_layer, buffer_rows=16, matmul=jax.lax.ragged_dot)
    y, dropped, grads = _value_and_grads(layer, operands, experts)
    got_y, got_dropped, got = _value_and_grads(
        functools.partial(layer, spoil=True), operands, experts)
    assert dropped == got_dropped == max(held - 16, 0)
    for name, want, have in zip(
            ("y", "dx", "d_gates", "d_w_in", "d_w_out"),
            (y,) + grads, (got_y,) + got):
        assert bool(jnp.isfinite(have).all()), name
        np.testing.assert_array_equal(have, want, err_msg=name)
    if held == 0:
        assert float(jnp.abs(y).max()) == 0
    else:
        assert all(float(jnp.abs(g).max()) > 0 for g in (y,) + grads)


@pytest.mark.parametrize("chunk", [4, 16], ids=["chunks-of-4", "one-chunk"])
@pytest.mark.parametrize(
    "held", list(HELD_CASES.values()), ids=list(HELD_CASES))
def test_value_and_gradients_are_pr_35_s_to_the_bit(monkeypatch, held,
                                                    chunk):
    """Against the formulation this one replaced, kept here as the
    reference (``take`` and ``.at[].add`` over the whole buffer, the
    spare rows in the last group), in float32. ``d_gates`` alone is
    held to a few ulp (1e-6), not to the bit: a gate's gradient is a sum over a
    row's products, and XLA's CPU backend orders that sum by the fusion
    it sits in (the chunks' loop body here, autodiff's reduce there)."""
    monkeypatch.setattr(moe_ops, "HELD_CHUNK_ROWS", chunk)
    experts = _routed(16, 2, held, seed=100 + held)
    operands = _operands(16, 2, 8, 8, jnp.float32, seed=1)
    layer = functools.partial(
        _held_layer, buffer_rows=16, matmul=jax.lax.ragged_dot)
    want_y, want_dropped, want = _value_and_grads(
        functools.partial(
            layer, sort=_pr35_sort_held, dispatch=_pr35_dispatch,
            combine=_pr35_combine), operands, experts)
    y, dropped, grads = _value_and_grads(layer, operands, experts)
    for name, a, b in zip(("y", "dx", "d_gates", "d_w_in", "d_w_out"),
                          (want_y,) + want, (y,) + grads):
        if name == "d_gates":
            np.testing.assert_allclose(b, a, rtol=1e-6, atol=0, err_msg=name)
            np.testing.assert_array_equal(b == 0, a == 0, err_msg=name)
        else:
            np.testing.assert_array_equal(b, a, err_msg=name)
    assert dropped == want_dropped == max(held - 16, 0)


@pytest.mark.parametrize(
    "held", [0, 1, 700, 512, 1536, 1800],
    ids=list(HELD_CASES))
def test_the_pallas_kernels_leave_the_spare_tiles_alone(monkeypatch, held):
    """The Pallas grouped matmuls in interpret mode, where a row tile
    no grid step visits reads NaN: ``gmm``, the rows' ``gmm`` and
    ``tgmm`` stop at the last held tile, ``hidden``, ``out`` and the
    rows' gradients hold NaN past it, and none reaches y, dx, d_gates
    or a weight's gradient, which are ``ragged_dot``'s to bfloat16."""
    monkeypatch.setattr(moe_ops, "HELD_CHUNK_ROWS", 512)
    experts = _routed(512, 4, held, seed=held)
    operands = _operands(512, 4, 128, 128, jnp.bfloat16)
    pallas = lambda rows, w, sizes: moe_ops.pallas_grouped_matmul(
        rows, w, sizes, True)
    # the kernels themselves plant the NaNs
    pairs, valid, sizes, _, _, _ = moe_ops.sort_held(experts, 8, 2, 3, 1536)
    rows = moe_ops.dispatch_held(operands[0], pairs, valid, 4)
    hidden = pallas(rows, operands[2], sizes)
    tiles = min(-(-held // 512) * 512, 1536)
    assert bool(jnp.isnan(hidden[tiles:]).all())
    assert bool(jnp.isfinite(hidden[:min(held, 1536)]).all())
    layer = functools.partial(_held_layer, buffer_rows=1536)
    y, dropped, grads = _value_and_grads(
        functools.partial(layer, matmul=pallas), operands, experts)
    want_y, _, want = _value_and_grads(
        functools.partial(layer, matmul=jax.lax.ragged_dot), operands,
        experts)
    assert dropped == max(held - 1536, 0)
    for name, a, b in zip(("y", "dx", "d_gates", "d_w_in", "d_w_out"),
                          (want_y,) + want, (y,) + grads):
        assert bool(jnp.isfinite(b).all()), name
        np.testing.assert_allclose(
            np.asarray(b, np.float32), np.asarray(a, np.float32),
            atol=0.05 * float(jnp.abs(a.astype(jnp.float32)).max()) + 1e-6,
            err_msg=name)


def test_ragged_dot_zeroes_the_rows_past_its_groups():
    """What the CPU path meets in a spare row: ``jax.lax.ragged_dot``
    writes zeros past its group sizes' sum, reads nothing there (a NaN
    in a spare row of either operand or of the cotangent stays where it
    is), and so do its two gradients."""
    rng = np.random.RandomState(0)
    lhs = jnp.asarray(rng.randn(12, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 4, 5), jnp.float32)
    sizes = jnp.asarray([2, 0, 5], jnp.int32)
    dot = lambda lhs, w: jax.lax.ragged_dot(lhs, w, sizes)
    out, vjp = jax.vjp(dot, lhs, w)
    assert float(jnp.abs(out[7:]).max()) == 0
    assert float(jnp.abs(out[:7]).min()) > 0
    spoiled, spoiled_vjp = jax.vjp(dot, lhs.at[7:].set(jnp.nan), w)
    np.testing.assert_array_equal(spoiled, out)
    cotangent = jnp.asarray(rng.randn(12, 5), jnp.float32)
    d_lhs, d_w = vjp(cotangent)
    got_lhs, got_w = spoiled_vjp(cotangent.at[7:].set(jnp.nan))
    assert float(jnp.abs(d_lhs[7:]).max()) == 0
    np.testing.assert_array_equal(got_lhs, d_lhs)
    np.testing.assert_array_equal(got_w, d_w)


# --- the dropless sort shares three functions and no behaviour --------

# sha256 of the jaxpr of one expert layer's value and gradients
# (parameters and input) at the two sorted-dispatch cells' shapes, as
# the CPU traces it (``ragged_dot``) and as a TPU does (the Pallas
# grouped matmuls, kernel bodies included), recorded on the parent of
# PR 36 (6d0da1f) with the pinned jax: ``sort_by_expert``,
# ``dispatch_sorted``, ``combine_sorted``, ``grouped_matmul``,
# ``pallas_grouped_matmul`` and ``projection_tiles`` are what they
# were. A change to them changes these knowingly.
SORTED_LAYERS = {
    "olmoe1b7b-s4k": ((8, 4096, 2048), dict(
        num_experts=64, top_k=8, expert_dim=1024, normalize_gates=False)),
    "moonlight16b-s8k": ((2, 8192, 2048), dict(
        num_experts=64, top_k=6, expert_dim=1408, normalize_gates=True,
        scoring="sigmoid", gate_scale=2.446, bias_update_speed=0.001,
        seq_aux=True, shared_experts=2)),
}
SORTED_SHA = {
    ("olmoe1b7b-s4k", "cpu"): "e831527d71334660",
    ("moonlight16b-s8k", "cpu"): "d5b7ff3860123633",
    ("olmoe1b7b-s4k", "tpu"): "25e0227a3cab4e97",
    ("moonlight16b-s8k", "tpu"): "c38c5bad1dba3b3d",
}


@pytest.mark.parametrize(
    "cell,backend", list(SORTED_SHA), ids=["-".join(c) for c in SORTED_SHA])
def test_the_dropless_layer_traces_what_it_traced(monkeypatch, cell,
                                                  backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    shape, fields = SORTED_LAYERS[cell]
    layer = MoeMlp(dispatch_impl="sorted", expert_act="swiglu", **fields)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    variables = jax.eval_shape(
        lambda x: layer.init(jax.random.PRNGKey(0), x), x)

    def loss(variables, x):
        (y, aux), state = layer.apply(
            variables, x, training=True, mutable=["moe_state"])
        return (y.astype(jnp.float32).sum() + aux["load_balancing"]
                + aux["router_z"]), (aux["routing"], state)

    text = str(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1), has_aux=True))(variables, x))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == SORTED_SHA[
        (cell, backend)]


def _layer(held, rows=64, **kw):
    return MoeMlp(
        16, top_k=3, dispatch_impl="sorted", expert_dim=8,
        expert_act="swiglu", normalize_gates=True, shared_experts=1,
        shared_gate=True, held_experts=held, held_rows=rows, **kw)


def test_a_share_holds_its_experts_kernels_only():
    x = jnp.zeros((1, 8, 16))
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape, jax.eval_shape(
            _layer((4, 4)).init, jax.random.PRNGKey(0), x)["params"])
    assert shapes["w_gate"] == shapes["w_up"] == (4, 16, 8)
    assert shapes["w_down"] == (4, 8, 16)
    assert shapes["router"]["kernel"] == (16, 16)  # all experts
    assert shapes["shared_expert_gate"]["kernel"] == (16, 1)


def test_held_counters_reach_the_model_s_routing():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 16))
    layer = _layer((0, 4), rows=8)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(3), x)
    _, aux = jax.jit(layer.apply)(variables, x)
    routing = aux["routing"]
    held = float(routing["held"])
    assert float(routing["load_mean"]) == 24 * 3 / 16  # over all experts
    assert float(routing["dropped"]) == max(held - 8, 0) > 0
    assert float(routing["rows_run"]) == float(routing["rows_buffer"]) == 8
    # a buffer with room: the rows run follow the held pairs, in
    # eighths of the buffer
    _, roomy = _layer((0, 4), rows=64).apply(variables, x)
    roomy = roomy["routing"]
    assert float(roomy["held"]) == held and float(roomy["dropped"]) == 0
    assert float(roomy["rows_run"]) == -(-held // 8) * 8 < 64
    assert float(roomy["rows_buffer"]) == 64
    merged = moe_transformer.merge_routing(
        [routing, dict(roomy, held=routing["held"] + 5)])
    assert float(merged["held"]) == held + 5
    assert float(merged["dropped"]) == float(routing["dropped"])
    # the layers' buffers and what was run of them add up
    assert float(merged["rows_buffer"]) == 8 + 64
    assert float(merged["rows_run"]) == 8 + float(roomy["rows_run"])


def test_the_rows_run_reach_the_moe_routing_event():
    """The journal's row of the merged counters
    (``train/step_fns.py:FACTS``): ``held_rows_run`` and
    ``held_rows_buffer`` beside ``held_pairs``, only from a model whose
    layers hold a share."""
    from elasticdl_tpu.train import step_fns

    fact, = [f for f in step_fns.FACTS if f.key == "routing"]
    routing = {"load_max": 9.0, "load_mean": 2.0, "entropy": 1.5,
               "dropped": 0.0}
    whole = fact.journal(routing)
    share = fact.journal(
        dict(routing, held=300.0, rows_run=1024.0, rows_buffer=4096.0))
    assert fact.event == "moe_routing"
    assert "held_pairs" not in whole and "held_rows_run" not in whole
    assert share["held_pairs"] == 300 and share["dropped_pairs"] == 0
    assert share["held_rows_run"] == 1024
    assert share["held_rows_buffer"] == 4096


def test_held_experts_refuse_what_they_do_not_build():
    x = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match="held_rows"):
        _layer((0, 4), rows=None).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="outside 16 experts"):
        _layer((14, 4)).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="sorted"):
        MoeMlp(16, held_experts=(0, 4), held_rows=8).init(
            jax.random.PRNGKey(0), x)


def _small_lm(**kw):
    fields = dict(
        vocab_size=97, num_layers=4, num_heads=4, embed_dim=32,
        num_experts=16, top_k=3, expert_dim=8, expert_act="swiglu",
        normalize_gates=True, moe_every=1, norm="zero_centred_rmsnorm",
        dispatch_impl="sorted", shared_experts=1, shared_gate=True,
        held_experts=(4, 4), held_rows=128, attention_impl="xla",
        layer_kinds=("linear", "linear", "linear", "full"),
        linear=GatedDeltaDims(2, 4, 8, 8, 4, chunk=16), head_dim=16,
        num_kv_heads=2, head_norm="zero_centred_rmsnorm", rotary_dim=4,
        output_gate="sigmoid", embed_init_std=1.0)
    fields.update(kw)
    return MoeTransformerLM(**fields)


def test_the_layer_kinds_are_a_pattern_with_a_period():
    model = _small_lm(num_layers=6)
    tokens = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    linear = [i for i in range(6)
              if "in_proj_qkvz" in params["block_%d" % i]["attn"]]
    full = [i for i in range(6) if "query" in params["block_%d" % i]["attn"]]
    assert linear == [0, 1, 2, 4, 5] and full == [3]
    with pytest.raises(ValueError, match="layer_kinds"):
        _small_lm(layer_kinds=("linear", "window")).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="layer_kinds"):
        _small_lm(linear=None).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="dense block"):
        _small_lm(first_k_dense=1).init(jax.random.PRNGKey(0), tokens)


def test_the_model_trains_and_reports_its_share():
    model = _small_lm()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 97)
    variables = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), tokens, training=False))()
    # the training call's outputs and the loss's gradient: one program
    def loss(params):
        out = model.apply(
            dict(variables, params=params), tokens, training=True)
        return moe_transformer.loss(tokens, out).mean(), out

    (_, out), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])
    assert out["logits"].shape == (2, 48, 97)
    assert set(out["routing"]) == {
        "load_max", "load_mean", "entropy", "dropped", "held", "rows_run",
        "rows_buffer"}
    assert float(out["routing"]["dropped"]) == 0
    assert 0 < float(out["routing"]["held"]) <= 96 * 3
    # four layers' buffers of 128 rows, and what the step ran of them
    assert float(out["routing"]["rows_buffer"]) == 4 * 128
    assert 0 < float(out["routing"]["rows_run"]) <= 4 * 128
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert bool(jnp.isfinite(leaf).all()), path
        assert float(jnp.abs(leaf).max()) > 0, path
    # eval returns bare logits, as every model of the family
    assert jax.eval_shape(
        lambda: model.apply(variables, tokens)).shape == (2, 48, 97)


def test_no_parameter_of_the_new_model_falls_to_the_catch_all_rule():
    model = _small_lm()
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((2, 32), int)))
    rules = moe_transformer.moe_sharding_rules()
    hits = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            variables["params"]):
        name = "/".join(p.key for p in path)
        pattern = next(
            pat.pattern for pat, _ in rules._rules if pat.search(name))
        assert pattern != ".*", name
        spec = rules.spec_for(name)
        assert len(spec) <= len(leaf.shape), (name, spec, leaf.shape)
        hits[name] = spec
    P = jax.sharding.PartitionSpec
    assert hits["block_0/attn/in_proj_qkvz/kernel"] == P("fsdp", "tp")
    assert hits["block_0/attn/in_proj_ba/kernel"] == P("fsdp", "tp")
    assert hits["block_0/attn/conv_kernel"] == P(None, "tp")
    assert hits["block_0/attn/A_log"] == hits["block_0/attn/dt_bias"] == P()
    assert hits["block_0/attn/out_norm/scale"] == P()
    assert hits["block_0/attn/out_proj/kernel"] == P("tp", None, "fsdp")
    assert hits["block_3/attn/query/kernel"] == P("fsdp", "tp", None)
    assert hits["block_3/attn/q_norm/scale"] == P()
    assert hits["block_3/moe_mlp/shared_expert_gate/kernel"] == P()
    assert hits["block_3/moe_mlp/w_gate"] == P("ep", "fsdp", "tp")
