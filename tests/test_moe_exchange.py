"""The sorted dropless dispatch with its experts spread over ``ep``
(``ops/moe.py``: ``exchange_plan``, ``regroup_plan``, ``exchange_rows``,
``permute_rows``; ``MoeMlp._sorted_over_ep``), on the CPU's virtual
devices: the plan's arithmetic, the exchange against what it says it
does and its transpose against autodiff, the layer against a dense
one-hot formulation and against itself on ONE device under even and
skewed routing, and what is still refused. A tiny Mellum2 over
``ep=4`` against its plain reference and the one-device program, and
the counters' way to the journal through ``SpmdTrainer``, are
``test_moe_exchange_model.py``'s: one file summed past the rule's 100 s
(``ROADMAP.md`` Queue 3 item 12)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.models.moe_transformer import MoeMlp
from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGURATION = os.path.join(
    REPO, "benchmark", "configs", "mellum2-12b-a2.5b-ep4")
TINY = os.path.join(
    REPO, "tests", "benchmark_harness", "preset", "configs", "tiny-mellum2",
    "config.json")


def ep_mesh(ep=4, dp=1):
    return build_mesh(MeshConfig(dp=dp, ep=ep), num_devices=dp * ep)


def load(name):
    spec = importlib.util.spec_from_file_location(
        "mellum2_" + name, os.path.join(CONFIGURATION, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# --- the plan ---------------------------------------------------------


def counts_of(rng, ranks, experts, most=9):
    return jnp.asarray(rng.randint(0, most, (ranks, experts)), jnp.int32)


@pytest.mark.parametrize("buffer_rows", [10_000, 40, 7])
def test_the_plan_is_one_table_that_every_rank_reads_alike(buffer_rows):
    """Whatever a sender says it sends a receiver says it receives, a
    receiver's chunks lie end to end under its buffer's size, what does
    not fit is counted and nothing else is, and the two directions are
    each other's mirror."""
    ranks, experts = 4, 8
    counts = counts_of(np.random.RandomState(0), ranks, experts)
    plans = [moe_ops.exchange_plan(counts, me, buffer_rows)
             for me in range(ranks)]
    want = np.asarray(counts).reshape(ranks, ranks, -1).sum(-1)
    sent = np.asarray(plans[0]["sent"])
    for me, plan in enumerate(plans):
        np.testing.assert_array_equal(plan["sent"], sent)
        lies, sends, lands, gets = (np.asarray(a) for a in plan["there"])
        np.testing.assert_array_equal(sends, sent[me])
        np.testing.assert_array_equal(gets, sent[:, me])
        # a rank's chunks start where its sorted rows put them
        np.testing.assert_array_equal(
            lies, np.cumsum(want[me]) - want[me])
        back = [np.asarray(a) for a in plan["back"]]
        np.testing.assert_array_equal(back[1], gets)
        np.testing.assert_array_equal(back[3], sends)
        # what lands here lies end to end and fits
        np.testing.assert_array_equal(
            back[0], np.cumsum(gets) - gets)
        assert gets.sum() <= buffer_rows
        np.testing.assert_array_equal(
            np.asarray(plan["received"]).sum(axis=1), gets)
        for peer in range(ranks):
            # where my chunk lands there is where the peer looks for it
            assert lands[peer] == np.asarray(plans[peer]["back"][0])[me]
            assert back[2][peer] == np.asarray(plans[peer]["there"][0])[me]
    assert int(plans[0]["dropped"]) == want.sum() - sent.sum()
    assert (int(plans[0]["dropped"]) == 0) == (
        want.sum(axis=0).max() <= buffer_rows)
    # the cut takes the last senders' highest experts first
    kept = np.stack([np.asarray(p["received"]) for p in plans], axis=1)
    whole = np.asarray(counts).reshape(ranks, ranks, -1)
    assert (kept <= whole).all()
    cut = np.argwhere(kept < whole)
    for sender, dest, expert in cut:
        assert (kept[sender, dest, expert + 1:] == 0).all()
        assert (kept[sender + 1:, dest] == 0).all()


def test_regrouping_is_a_permutation_that_groups_by_expert():
    rng = np.random.RandomState(1)
    received = jnp.asarray(rng.randint(0, 6, (4, 3)), jnp.int32)
    received = received.at[2, 1].set(0).at[0, 0].set(0)
    total, buffer_rows = int(received.sum()), int(received.sum()) + 11
    by_expert, by_sender, sizes, carried = moe_ops.regroup_plan(
        received, buffer_rows)
    np.testing.assert_array_equal(sizes, np.asarray(received).sum(axis=0))
    assert int(carried) == total
    np.testing.assert_array_equal(
        np.sort(by_expert), np.arange(buffer_rows))
    np.testing.assert_array_equal(
        np.asarray(by_sender)[np.asarray(by_expert)], np.arange(buffer_rows))
    # the rows past the last pair stay among themselves
    np.testing.assert_array_equal(
        by_expert[total:], np.arange(total, buffer_rows))
    # the buffer's rows by (sender, expert), each sender's in order
    labels = np.concatenate([
        np.full(int(received[s, e]), e)
        for s in range(4) for e in range(3)])
    senders = np.concatenate([
        np.full(int(received[s, e]), s)
        for s in range(4) for e in range(3)])
    grouped = labels[np.asarray(by_expert)[:total]]
    assert (np.diff(grouped) >= 0).all()
    for e in range(3):
        assert (np.diff(senders[np.asarray(by_expert)[:total]][
            grouped == e]) >= 0).all()
    rows = jnp.asarray(rng.randn(buffer_rows, 5), jnp.float32)
    # 4,096 does not divide this buffer: one chunk, the buffer whole
    out, vjp = jax.vjp(
        lambda r: moe_ops.permute_rows(r, by_expert, by_sender, carried),
        rows)
    np.testing.assert_array_equal(out, np.asarray(rows)[by_expert])
    cotangent = jnp.asarray(rng.randn(buffer_rows, 5), jnp.float32)
    (d_rows,) = vjp(cotangent)
    (expected,) = jax.vjp(lambda r: jnp.take(r, by_expert, axis=0), rows)[1](
        cotangent)
    np.testing.assert_allclose(d_rows, expected, rtol=1e-6)


def _plan_by_loops(received, buffer_rows):
    """``regroup_plan``'s two vectors by plain loops over the (sender,
    expert) runs: a run's rows lie in the buffer after every run of an
    earlier sender and every earlier run of its own, and go, expert by
    expert and sender by sender, to the next free places."""
    ranks, held = received.shape
    by_expert = []
    for expert in range(held):
        for sender in range(ranks):
            lies = sum(
                int(received[s, e]) for s in range(ranks)
                for e in range(held) if (s, e) < (sender, expert))
            by_expert.extend(range(lies, lies + int(received[sender, expert])))
    by_expert.extend(range(len(by_expert), buffer_rows))
    by_sender = [None] * buffer_rows
    for place, source in enumerate(by_expert):
        by_sender[source] = place
    return np.asarray(by_expert), np.asarray(by_sender)


def _received_table(kind, buffer_rows):
    rng = np.random.RandomState(0)
    ranks, held = 4, 16

    def drawn(carried, weights):
        return rng.multinomial(
            carried, weights / weights.sum()).reshape(ranks, held)

    zipf = rng.permutation(1.0 / np.arange(1, ranks * held + 1) ** 1.2)
    even = np.ones(ranks * held)
    if kind == "zipf_skewed":
        return drawn(buffer_rows * 5 // 8, zipf)
    if kind == "empty_runs":
        return drawn(buffer_rows // 2, zipf * (rng.rand(ranks * held) < 0.4))
    if kind == "first_and_last_run_empty":
        table = drawn(buffer_rows // 2, even)
        table[0, 0] = table[-1, -1] = 0
        return table
    if kind == "a_sender_sent_nothing":
        table = drawn(buffer_rows // 2, even)
        table[2] = 0
        return table
    if kind == "an_expert_received_nothing":
        table = drawn(buffer_rows // 2, even)
        table[:, 5] = 0
        return table
    if kind == "one_run_has_it_all":
        table = np.zeros((ranks, held), np.int64)
        table[1, 9] = buffer_rows // 3
        return table
    if kind == "nothing_carried":
        return np.zeros((ranks, held), np.int64)
    if kind == "the_buffer_full":
        return drawn(buffer_rows, zipf)
    assert kind == "under_one_chunk"
    return drawn(moe_ops.held_chunk_rows(buffer_rows) // 3, zipf)


@pytest.mark.parametrize("kind", [
    "zipf_skewed", "empty_runs", "first_and_last_run_empty",
    "a_sender_sent_nothing", "an_expert_received_nothing",
    "one_run_has_it_all", "nothing_carried", "the_buffer_full",
    "under_one_chunk"])
def test_the_plan_is_what_plain_loops_over_the_runs_give(kind):
    buffer_rows = 2 * 4096
    received = _received_table(kind, buffer_rows)
    by_expert, by_sender, sizes, carried = jax.jit(
        moe_ops.regroup_plan, static_argnums=(1,))(
            jnp.asarray(received, jnp.int32), buffer_rows)
    assert by_expert.dtype == by_sender.dtype == jnp.int32
    assert sizes.dtype == carried.dtype == jnp.int32
    want_by_expert, want_by_sender = _plan_by_loops(received, buffer_rows)
    np.testing.assert_array_equal(by_expert, want_by_expert)
    np.testing.assert_array_equal(by_sender, want_by_sender)
    at = np.arange(buffer_rows)
    np.testing.assert_array_equal(np.asarray(by_sender)[by_expert], at)
    np.testing.assert_array_equal(np.asarray(by_expert)[by_sender], at)
    total = int(received.sum())
    np.testing.assert_array_equal(by_expert[total:], at[total:])
    np.testing.assert_array_equal(by_sender[total:], at[total:])
    np.testing.assert_array_equal(sizes, received.sum(axis=0))
    assert int(carried) == int(np.asarray(sizes).sum()) == total


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _primitives(inner)


def test_the_plan_looks_nothing_up_by_position():
    """Both vectors are the position plus a sum of steps at the runs'
    ends: a table lookup a position costs ~11 ns each on a v5e whatever
    the table's size, 47 ms a step at the cell's buffer (PERF.md, PR
    48)."""
    jaxpr = jax.make_jaxpr(moe_ops.regroup_plan, static_argnums=(1,))(
        jnp.zeros((4, 16), jnp.int32), 8192)
    names = set(_primitives(jaxpr.jaxpr))
    assert "reduce_sum" in names and "select_n" in names
    assert not {n for n in names if "gather" in n or "sort" in n}, names


@pytest.mark.parametrize("buffer_rows, carried", [
    (3 * 4096, 0), (3 * 4096, 1), (3 * 4096, 4096), (3 * 4096, 5000),
    (3 * 4096, 2 * 4096), (3 * 4096, 3 * 4096), (1000, 0), (1000, 300),
    (1000, 1000)])
def test_the_regrouping_gathers_the_chunks_that_carry_a_pair(
        buffer_rows, carried):
    """``permute_rows`` is ``take(rows, index)`` on every row below the
    end of the last chunk of ``held_chunk_rows`` that carries a pair
    and zero past it, its transpose ``take``'s on the same rows: with
    no row, one, exactly a chunk, the buffer full, and a buffer that
    4,096 does not divide (one chunk: the buffer whole)."""
    rng = np.random.RandomState(buffer_rows + carried)
    received = jnp.asarray(
        rng.multinomial(carried, np.full(12, 1 / 12)).reshape(4, 3),
        jnp.int32)
    by_expert, by_sender, sizes, count = moe_ops.regroup_plan(
        received, buffer_rows)
    assert int(count) == int(sizes.sum()) == carried
    chunk = moe_ops.held_chunk_rows(buffer_rows)
    assert chunk == (4096 if buffer_rows % 4096 == 0 else buffer_rows)
    end = -(-carried // chunk) * chunk
    assert int(moe_ops.received_rows_run(count, buffer_rows)) == end
    rows = jnp.asarray(rng.randn(buffer_rows, 4), jnp.float32)
    cotangent = jnp.asarray(rng.randn(buffer_rows, 4), jnp.float32)
    for index, inverse in ((by_expert, by_sender), (by_sender, by_expert)):
        out, vjp = jax.jit(lambda r, index=index, inverse=inverse: jax.vjp(
            lambda r: moe_ops.permute_rows(r, index, inverse, count), r))(
                rows)
        np.testing.assert_array_equal(out[:end], np.asarray(rows)[index[:end]])
        assert not np.asarray(out[end:]).any()
        (d_rows,) = vjp(cotangent)
        (expected,) = jax.vjp(
            lambda r: jnp.take(r, index, axis=0), rows)[1](cotangent)
        np.testing.assert_array_equal(d_rows[:end], expected[:end])
        assert not np.asarray(d_rows[end:]).any()


# --- the exchange -----------------------------------------------------


def _exchange_case(buffer_rows):
    """Four ranks, 6 experts... each rank's rows are its sorted pairs
    and carry (rank, position) so that a row says where it came from."""
    ranks, experts, width = 4, 8, 3
    counts = counts_of(np.random.RandomState(2), ranks, experts, most=5)
    local = int(np.asarray(counts).sum(axis=1).max())
    rows = np.zeros((ranks, local, width), np.float32)
    for r in range(ranks):
        rows[r, :, 0] = r
        rows[r, :, 1] = np.arange(local)
        rows[r, :, 2] = np.random.RandomState(r).randn(local)
    mesh = ep_mesh()

    def over_ranks(fn):
        return jax.jit(jax_compat.shard_map(
            fn, mesh=mesh, in_specs=(P("ep"), P()), out_specs=P("ep"),
            check_vma=False))

    return counts, jnp.asarray(rows.reshape(ranks * local, width)), \
        local, over_ranks


@pytest.mark.parametrize("buffer_rows", [64, 24])
def test_rows_arrive_where_the_plan_says_and_come_back(buffer_rows):
    counts, rows, local, over_ranks = _exchange_case(buffer_rows)
    ranks = counts.shape[0]

    def there(rows, counts):
        plan = moe_ops.exchange_plan(
            counts, jax.lax.axis_index("ep"), buffer_rows)
        return moe_ops.exchange_rows(
            rows, plan["there"], plan["back"], buffer_rows, "ep")

    def there_and_back(rows, counts):
        plan = moe_ops.exchange_plan(
            counts, jax.lax.axis_index("ep"), buffer_rows)
        got = moe_ops.exchange_rows(
            rows, plan["there"], plan["back"], buffer_rows, "ep")
        return moe_ops.exchange_rows(
            got, plan["back"], plan["there"], rows.shape[0], "ep")

    got = np.asarray(over_ranks(there)(rows, counts)).reshape(
        ranks, buffer_rows, -1)
    back = np.asarray(over_ranks(there_and_back)(rows, counts)).reshape(
        ranks, local, -1)
    sent = np.asarray(
        moe_ops.exchange_plan(counts, 0, buffer_rows)["sent"])
    want = np.asarray(counts).reshape(ranks, ranks, -1).sum(-1)
    lies = np.cumsum(want, axis=1) - want
    every = np.asarray(rows).reshape(ranks, local, -1)
    for dest in range(ranks):
        at = 0
        for sender in range(ranks):
            n = sent[sender, dest]
            np.testing.assert_array_equal(
                got[dest, at:at + n],
                every[sender, lies[sender, dest]:lies[sender, dest] + n])
            at += n
        assert (got[dest, at:] == 0).all()
    for sender in range(ranks):
        kept = np.zeros(local, bool)
        for dest in range(ranks):
            start = lies[sender, dest]
            kept[start:start + sent[sender, dest]] = True
        np.testing.assert_array_equal(back[sender][kept], every[sender][kept])
        # a pair that found no row comes back as nothing
        assert (back[sender][~kept] == 0).all()


def test_the_exchange_s_transpose_is_the_exchange_reversed():
    """``exchange_rows``' VJP against autodiff of the same movement
    written with gathers (``_gathered_all_to_all`` under no custom
    rule)."""
    buffer_rows = 64
    counts, rows, _, over_ranks = _exchange_case(buffer_rows)

    def loss(exchange):
        def on_rank(rows, counts):
            plan = moe_ops.exchange_plan(
                counts, jax.lax.axis_index("ep"), buffer_rows)
            got = exchange(rows, plan)
            weight = jnp.arange(got.size, dtype=jnp.float32).reshape(
                got.shape) * (1.0 + jax.lax.axis_index("ep"))
            return jnp.sum(jnp.sin(got) * weight, keepdims=True)[0]

        return lambda rows: over_ranks(on_rank)(rows, counts).sum()

    ours = jax.jit(jax.grad(loss(
        lambda rows, plan: moe_ops.exchange_rows(
            rows, plan["there"], plan["back"], buffer_rows, "ep"))))(rows)
    plain = jax.jit(jax.grad(loss(
        lambda rows, plan: moe_ops._gathered_all_to_all(
            rows, buffer_rows, plan["there"], "ep"))))(rows)
    assert float(jnp.abs(plain).max()) > 0
    np.testing.assert_allclose(ours, plain, rtol=1e-5, atol=1e-6)


# --- the layer --------------------------------------------------------

LAYER = dict(
    num_experts=8, top_k=2, dispatch_impl="sorted", expert_dim=8,
    expert_act="swiglu", normalize_gates=True)


def _layer_case(skew=False, batch=4, seq=16, dim=12):
    rng = np.random.RandomState(3)
    x = rng.randn(batch, seq, dim).astype(np.float32)
    variables = jax.jit(MoeMlp(**LAYER).init)(
        jax.random.PRNGKey(0), jnp.asarray(x))
    if skew:
        # three tokens in four carry a lane that the router reads into
        # experts 0 and 1, rank 0's: that rank gets most of the pairs
        x[:, np.arange(seq) % 4 != 3, 0] = 6.0
        kernel = variables["params"]["router"]["kernel"]
        variables = {"params": dict(
            variables["params"],
            router={"kernel": kernel.at[0, :2].set(8.0)})}
    return variables, jnp.asarray(x)


def _value_and_grads(layer, variables, x):
    def loss(variables, x):
        y, aux = layer.apply(variables, x, training=True)
        weight = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(
            y.shape)
        return (jnp.sum(y * weight) + 3.0 * aux["load_balancing"]
                + aux["router_z"]), (y, aux["routing"])

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
        variables, x)


def _dense_layer(variables, x):
    """The same layer as a dense one-hot formulation under plain
    autodiff: every expert on every token, the gates a (T, E) matrix
    with zeros where the router did not choose."""
    p = variables["params"]
    tokens = x.reshape(-1, x.shape[-1])
    logits = tokens @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    top, experts = jax.lax.top_k(probs, LAYER["top_k"])
    top = top / (top.sum(axis=-1, keepdims=True) + 1e-9)
    gates = jnp.sum(
        jax.nn.one_hot(experts, LAYER["num_experts"]) * top[..., None],
        axis=1)
    hidden = jax.nn.silu(
        jnp.einsum("td,edf->etf", tokens, p["w_gate"])) * jnp.einsum(
            "td,edf->etf", tokens, p["w_up"])
    out = jnp.einsum("etf,efd->etd", hidden, p["w_down"])
    y = jnp.einsum("te,etd->td", gates, out).reshape(x.shape)
    loads = jax.nn.one_hot(experts, LAYER["num_experts"]).sum(axis=(0, 1))
    balance = LAYER["num_experts"] * jnp.sum(
        loads / tokens.shape[0] * probs.mean(axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, balance, z


def _assert_trees_close(got, want, rtol=2e-5, atol=2e-6):
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.abs(b).max()) + 1e-12
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, rtol=rtol,
            atol=atol, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("dp", [1, 2], ids=["ep4", "dp2xep4"])
@pytest.mark.parametrize("skew", [False, True], ids=["even", "skewed"])
def test_the_layer_over_ep_is_the_layer_on_one_device(skew, dp):
    """The exchange changes where rows are computed, not what: output,
    losses, counters and every gradient, under even routing and under
    routing skewed so that one rank receives over half the pairs; no
    pair is dropped."""
    variables, x = _layer_case(skew, batch=4 * dp)
    (one, (y_one, stats_one)), grads_one = _value_and_grads(
        MoeMlp(**LAYER), variables, x)
    (many, (y_many, stats)), grads_many = _value_and_grads(
        MoeMlp(mesh=ep_mesh(dp=dp), **LAYER), variables, x)
    np.testing.assert_allclose(many, one, rtol=1e-5)
    np.testing.assert_allclose(y_many, y_one, rtol=1e-4, atol=1e-5)
    _assert_trees_close(grads_many, grads_one)
    for name in ("load_max", "load_mean", "entropy", "dropped"):
        np.testing.assert_allclose(stats[name], stats_one[name], rtol=1e-5)
    assert float(stats["dropped"]) == 0
    pairs = x.shape[0] // dp * x.shape[1] * LAYER["top_k"]
    np.testing.assert_allclose(stats["received_mean"], pairs / 4)
    if skew:
        assert float(stats["received_max"]) > pairs / 2
    assert 0 < float(stats["sent"]) <= pairs / 4
    np.testing.assert_allclose(
        stats["exchange_bytes"],
        float(stats["sent"]) * x.shape[-1] * 4 * 4)


def test_a_deepseek_style_layer_over_ep_is_itself_on_one_device():
    """Sigmoid scores, the balancing bias (selected with inside the
    region, moved outside it by the loads of ALL ranks), the
    sequence-wise balance loss (a mean over every rank's sequences) and
    a shared expert: the same value, gradients and new bias."""
    fields = dict(LAYER, scoring="sigmoid", gate_scale=2.5,
                  bias_update_speed=0.01, seq_aux=True, shared_experts=1)
    _, x = _layer_case()
    variables = jax.jit(MoeMlp(**fields).init)(jax.random.PRNGKey(0), x)

    def run(layer):
        def loss(params, x):
            (y, aux), state = layer.apply(
                dict(variables, params=params), x, training=True,
                mutable=["moe_state"])
            weight = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(
                y.shape)
            return jnp.sum(y * weight) + 3.0 * aux["load_balancing"], (
                state, aux["routing"])

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
            variables["params"], x)

    (one, (state_one, stats_one)), grads_one = run(MoeMlp(**fields))
    (many, (state, stats)), grads = run(MoeMlp(mesh=ep_mesh(), **fields))
    np.testing.assert_allclose(many, one, rtol=1e-5)
    _assert_trees_close(grads, grads_one)
    _assert_trees_close(state, state_one)
    bias = state["moe_state"]["e_score_correction_bias"]
    assert float(jnp.abs(bias).max()) == pytest.approx(0.01)
    np.testing.assert_allclose(
        stats["bias_abs_max"], stats_one["bias_abs_max"])


def test_the_layer_s_gradients_are_a_dense_one_hot_layer_s():
    """The exchange's and the regrouping's VJPs, the sorted dispatch's
    and the combine's around them, against autodiff of a formulation
    that has none of them."""
    variables, x = _layer_case()

    def dense_loss(variables, x):
        y, balance, z = _dense_layer(variables, x)
        weight = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(
            y.shape)
        return jnp.sum(y * weight) + 3.0 * balance + z

    want, grads_want = jax.jit(
        jax.value_and_grad(dense_loss, argnums=(0, 1)))(variables, x)
    (got, _), grads_got = _value_and_grads(
        MoeMlp(mesh=ep_mesh(), **LAYER), variables, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_trees_close(grads_got, grads_want)


def test_a_receive_buffer_too_small_counts_what_it_drops():
    """``exchange_rows`` of a stated size: the pairs that found no row
    are counted, add nothing, and nothing is written past the buffer."""
    variables, x = _layer_case(skew=True)
    pairs = x.shape[0] // 4 * x.shape[1] * LAYER["top_k"]
    (_, (y_all, stats_all)), _ = _value_and_grads(
        MoeMlp(mesh=ep_mesh(), **LAYER), variables, x)
    (_, (y_cut, stats)), grads = _value_and_grads(
        MoeMlp(mesh=ep_mesh(), exchange_rows=pairs, **LAYER), variables, x)
    assert float(stats_all["dropped"]) == 0
    assert float(stats_all["received_max"]) > pairs
    assert float(stats["dropped"]) == float(
        stats_all["received_max"]) - pairs
    assert float(stats["received_max"]) == pairs
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))
    assert float(jnp.abs(y_cut - y_all).max()) > 0


def test_without_ep_the_layer_traces_what_it_traced():
    """No mesh, a mesh of one device and a mesh whose ``ep`` is 1 lower
    the program the sorted path always lowered (its recorded jaxprs are
    ``tests/test_held_experts.py``'s): no manual region, no exchange."""
    variables, x = _layer_case()

    def text(mesh):
        return str(jax.make_jaxpr(lambda v, x: MoeMlp(
            mesh=mesh, **LAYER).apply(v, x, training=True))(variables, x))

    plain = text(None)
    assert "shard_map" not in plain and "all_gather" not in plain
    assert text(build_mesh(MeshConfig(), num_devices=1)) == plain
    assert "shard_map" not in text(build_mesh(MeshConfig(dp=4),
                                              num_devices=4))
    assert "shard_map" in text(ep_mesh())


def test_what_is_still_refused():
    variables, x = _layer_case()
    # each is raised while the layer is traced: no operation need run
    init = lambda layer, x=x: jax.eval_shape(
        layer.init, jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="one or the other"):
        init(MoeMlp(mesh=ep_mesh(), held_experts=(0, 2), held_rows=64,
                    **LAYER))
    both = build_mesh(MeshConfig(fsdp=2, ep=2), num_devices=4)
    with pytest.raises(ValueError, match="ep beside fsdp"):
        init(MoeMlp(mesh=both, **LAYER))
    with pytest.raises(ValueError, match="have to divide over ep"):
        init(MoeMlp(mesh=ep_mesh(), **dict(LAYER, num_experts=6)))
    with pytest.raises(ValueError, match="does not divide over the 4"):
        init(MoeMlp(mesh=ep_mesh(), **LAYER), x[:3])
    with pytest.raises(ValueError, match="normalize_gates=False needs"):
        init(MoeMlp(mesh=ep_mesh(), **dict(
            LAYER, dispatch_impl="onehot", normalize_gates=False)))
