"""MoE routing + expert-parallel training.

Correctness ladder mirroring the transformer SPMD tests: (1) routing
invariants, (2) dispatch/combine against a brute-force per-token loop,
(3) the MoE LM trained GSPMD-sharded over a dp x tp x ep mesh matches
single-device losses, (4) the sorted dropless dispatch: its invariants,
its gradients against a brute-force loop and against the one-hot
dispatch where that drops nothing. The sorted dispatch in the LM, under
a dp mesh, what it must never hold and its counters' way out of the
step are ``test_moe_lm.py``'s: one file summed past the rule's 100 s
(``ROADMAP.md`` Queue 3 item 12).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops.moe import (
    expert_capacity,
    moe_combine,
    moe_dispatch,
    top_k_routing,
)
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
from elasticdl_tpu.train.optimizers import create_optimizer
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state


def test_top1_routing_matches_argmax():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(2, 16, 4).astype(np.float32))
    capacity = 16  # ample: nothing dropped
    combine, dispatch, aux = top_k_routing(logits, k=1, capacity=capacity)
    chosen = np.asarray(dispatch.sum(axis=-1).argmax(axis=-1))
    np.testing.assert_array_equal(
        chosen, np.asarray(logits.argmax(axis=-1))
    )
    # every token dispatched exactly once, with weight 1 after renorm
    np.testing.assert_allclose(
        np.asarray(combine.sum(axis=(2, 3))), 1.0, atol=1e-6
    )
    assert float(aux) > 0


def test_capacity_drops_overflow_tokens():
    # All 8 tokens pick expert 0; capacity 3 keeps only the first 3.
    logits = jnp.tile(
        jnp.asarray([[10.0, 0.0, 0.0, 0.0]]), (1, 8, 1)
    ).reshape(1, 8, 4)
    combine, dispatch, _ = top_k_routing(logits, k=1, capacity=3)
    per_token = np.asarray(dispatch.sum(axis=(2, 3)))
    assert per_token[0, :3].sum() == 3
    assert per_token[0, 3:].sum() == 0
    # each (expert, slot) holds at most one token
    per_slot = np.asarray(dispatch.sum(axis=1))
    assert per_slot.max() == 1


def test_dispatch_combine_matches_bruteforce():
    rng = np.random.RandomState(1)
    g, s, e, m, k = 2, 8, 4, 6, 2
    x = jnp.asarray(rng.randn(g, s, m).astype(np.float32))
    logits = jnp.asarray(rng.randn(g, s, e).astype(np.float32))
    capacity = s * k  # nothing dropped
    combine, dispatch, _ = top_k_routing(logits, k=k, capacity=capacity)

    # "experts" are simple per-expert linear maps
    w = jnp.asarray(rng.randn(e, m, m).astype(np.float32))
    expert_in = moe_dispatch(x, dispatch)  # (E, G, C, M)
    expert_out = jnp.einsum("egcm,emn->egcn", expert_in, w)
    y = moe_combine(expert_out, combine)

    # brute force: per token, weighted sum of its top-k experts' outputs
    probs = jax.nn.softmax(logits, axis=-1)
    gates, indices = jax.lax.top_k(probs, k)
    gates = gates / gates.sum(axis=-1, keepdims=True)
    expected = np.zeros((g, s, m), np.float32)
    for gi in range(g):
        for si in range(s):
            for ki in range(k):
                ei = int(indices[gi, si, ki])
                expected[gi, si] += float(gates[gi, si, ki]) * np.asarray(
                    x[gi, si] @ w[ei]
                )
    np.testing.assert_allclose(np.asarray(y), expected, atol=1e-4)


def _small_moe(**kwargs):
    return moe_transformer.MoeTransformerLM(
        vocab_size=128,
        num_layers=2,
        num_heads=4,
        embed_dim=32,
        num_experts=4,
        top_k=2,
        # ample capacity: deterministic routing regardless of sharding
        capacity_factor=2.0,
        **kwargs,
    )


def _batch(batch=4, seq=32, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, vocab, size=(batch, seq)).astype(np.int32)
    return {
        "features": tokens,
        "labels": tokens,
        "_mask": np.ones((batch,), np.float32),
    }


def _single_device_losses(batch, steps=3):
    model = _small_moe(attention_impl="xla")
    tx = create_optimizer("Adam", learning_rate=0.01)
    init_rng, _ = jax.random.split(jax.random.PRNGKey(0))
    state = create_train_state(model, tx, init_rng, batch["features"])
    step = jax.jit(make_train_step(model, moe_transformer.loss, tx))
    losses = []
    for _ in range(steps):
        state, loss = step(state, batch)
        losses.append(float(loss))
    return losses


def test_expert_parallel_matches_single_device():
    batch = _batch()
    expected = _single_device_losses(batch)

    mesh = build_mesh(MeshConfig(dp=2, tp=2, ep=2))
    model = _small_moe(attention_impl="xla", mesh=mesh)
    trainer = SpmdTrainer(
        model=model,
        loss_fn=moe_transformer.loss,
        optimizer=create_optimizer("Adam", learning_rate=0.01),
        mesh=mesh,
        seed=0,
        sharding_rules=moe_transformer.sharding_rules(),
        batch_spec=moe_transformer.batch_spec(),
    )
    state = trainer.create_state(batch["features"])
    losses = []
    for _ in range(3):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, expected, atol=1e-4, rtol=1e-4)


def test_moe_eval_returns_bare_logits():
    batch = _batch()
    model = _small_moe(attention_impl="xla")
    variables = model.init(
        jax.random.PRNGKey(0), batch["features"], training=False
    )
    out = model.apply(variables, batch["features"], training=False)
    assert out.shape == (4, 32, 128)
    out = model.apply(
        variables,
        batch["features"],
        training=True,
        rngs={"dropout": jax.random.PRNGKey(1)},
    )
    assert set(out.keys()) == {"logits", "aux_loss"}


def test_model_contract_loads():
    from elasticdl_tpu.models.registry import get_model_spec

    spec = get_model_spec("elasticdl_tpu.models.moe_transformer")
    assert spec.sharding_rules is not None
    assert spec.batch_spec is not None


def test_expert_capacity_static():
    assert expert_capacity(64, 8, k=2, capacity_factor=1.0) == 16
    assert expert_capacity(4, 8, k=1, capacity_factor=1.25) == 1


def test_aux_loss_gradient_pushes_toward_uniform():
    """Deterministic property behind the balance claim: at a collapsed
    router (every token's first choice = expert 0), d(aux)/d(logits)
    is negative-toward-expert-0 — following it redistributes load."""
    import jax

    from elasticdl_tpu.ops.moe import top_k_routing

    G, S, E, C = 2, 16, 4, 8
    logits = jnp.zeros((G, S, E)).at[..., 0].set(3.0)

    def aux_of(logits):
        _, _, aux = top_k_routing(logits, k=2, capacity=C)
        return aux

    grad = jax.grad(aux_of)(logits)
    # the dominant expert's logit gradient is positive (aux rises with
    # more concentration), every other expert's is negative — gradient
    # DESCENT therefore moves logits away from expert 0
    assert float(grad[..., 0].mean()) > 0
    assert float(grad[..., 1:].mean()) < 0


@pytest.mark.slow
def test_expert_balance_holds_over_a_real_run():
    """The aux loss keeps dispatch balanced while the model LEARNS —
    trained from a deliberately COLLAPSED router (expert 0 hoards >55%
    of first choices), the run must both fit the task and return to
    near-uniform routing. Full experiment (incl. the no-aux arm):
    scripts/convergence_moe.py."""
    import sys

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import convergence_moe

    result = convergence_moe.run_arm(
        aux_weight=0.01, steps=120, collapsed_init=True
    )
    # learned the task
    assert result["ce_last"] < 1.0 < result["ce_first"]
    # started collapsed...
    assert result["max_expert_share_init"] > 0.5
    # ...and recovered to near-uniform dispatch (uniform = 0.25 for
    # E=4; balance 1.0 = perfectly uniform f·p)
    assert result["balance"] < 1.1
    assert result["max_expert_share"] < 0.4


# ---------------------------------------------------------------------
# the sorted, dropless dispatch


def _sorted_path(x, logits, weights, k, normalize=False):
    """Tokens (T, M) through ``weights`` (E, M, N) experts, sorted."""
    gates, experts, _ = moe_ops.route_top_k(logits, k, normalize)
    order, inverse, sizes = moe_ops.sort_by_expert(experts, weights.shape[0])
    rows = moe_ops.dispatch_sorted(x, order, inverse)
    out = moe_ops.grouped_matmul(rows, weights, sizes)
    return moe_ops.combine_sorted(out, gates, order, inverse)


def _loop_path(x, logits, weights, k):
    """The same by a loop over tokens' choices: every expert computes
    every token, each token picks its k."""
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    every = jnp.einsum("tm,emn->ten", x, weights)
    return sum(
        gates[:, j, None]
        * jnp.take_along_axis(every, experts[:, j, None, None], 1)[:, 0]
        for j in range(k)
    )


@pytest.mark.parametrize("tokens, experts, k", [(40, 4, 2), (33, 8, 3),
                                                (16, 8, 8)])
def test_sorted_dispatch_invariants(tokens, experts, k):
    """Every token is in exactly k groups, the group sizes sum to
    tokens x k whatever the routing, groups are contiguous and in expert
    order, and combine(dispatch(x)) under unit gates is k x identity."""
    rng = np.random.RandomState(tokens)
    # a skewed router: some experts get most tokens, some may get none
    logits = jnp.asarray(
        rng.randn(tokens, experts) * 3 + np.linspace(2, -2, experts),
        jnp.float32)
    x = jnp.asarray(rng.randn(tokens, 5), jnp.float32)
    gates, chosen, _ = moe_ops.route_top_k(logits, k)
    order, inverse, sizes = moe_ops.sort_by_expert(chosen, experts)
    order, inverse, sizes = map(np.asarray, (order, inverse, sizes))
    assert sizes.sum() == tokens * k
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(chosen).ravel(), minlength=experts))
    np.testing.assert_array_equal(np.sort(order), np.arange(tokens * k))
    np.testing.assert_array_equal(inverse[order], np.arange(tokens * k))
    # each token appears k times among the sorted rows
    np.testing.assert_array_equal(
        np.bincount(order // k, minlength=tokens), np.full(tokens, k))
    # the sorted pairs' experts are non-decreasing: groups are contiguous
    sorted_experts = np.asarray(chosen).ravel()[order]
    assert (np.diff(sorted_experts) >= 0).all()
    rows = moe_ops.dispatch_sorted(x, order, inverse)
    np.testing.assert_array_equal(
        np.asarray(rows), np.asarray(x)[order // k])
    back = moe_ops.combine_sorted(
        rows, jnp.ones_like(gates), order, inverse)
    np.testing.assert_allclose(np.asarray(back), k * np.asarray(x), rtol=1e-6)
    stats = moe_ops.routing_stats(
        jax.nn.softmax(logits), jnp.asarray(sizes), k)
    assert float(stats["dropped"]) == 0.0
    assert float(stats["load_max"]) == sizes.max()
    assert float(stats["load_mean"]) == pytest.approx(tokens * k / experts)


def test_sorted_dispatch_matches_bruteforce_with_gradients():
    rng = np.random.RandomState(11)
    tokens, experts, dim, out_dim, k = 48, 6, 10, 7, 3
    x = jnp.asarray(rng.randn(tokens, dim), jnp.float32)
    logits = jnp.asarray(rng.randn(tokens, experts), jnp.float32)
    w = jnp.asarray(rng.randn(experts, dim, out_dim), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(_sorted_path(x, logits, w, k)),
        np.asarray(_loop_path(x, logits, w, k)), atol=1e-5)
    # unnormalised gates sum to less than one; normalised ones to one
    gates, _, _ = moe_ops.route_top_k(logits, k)
    assert float(gates.sum(-1).max()) < 1.0
    normed, _, _ = moe_ops.route_top_k(logits, k, normalize=True)
    np.testing.assert_allclose(np.asarray(normed.sum(-1)), 1.0, atol=1e-5)

    def loss(path):
        return lambda *a: (path(*a, k) ** 2).sum()

    got = jax.jit(jax.grad(loss(_sorted_path), argnums=(0, 1, 2)))(
        x, logits, w)
    want = jax.jit(jax.grad(loss(_loop_path), argnums=(0, 1, 2)))(
        x, logits, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_sorted_dispatch_matches_onehot_where_nothing_is_dropped():
    """With a capacity that holds every pair the one-hot formulation
    drops nothing, and the two compute the same function: outputs and
    the gradients through the tokens, the router logits and the
    experts (gates renormalised on both sides, as one-hot does)."""
    rng = np.random.RandomState(7)
    g, s, e, m, k = 2, 16, 4, 6, 2
    w = jnp.asarray(rng.randn(e, m, m).astype(np.float32))
    x = jnp.asarray(rng.randn(g, s, m).astype(np.float32))
    logits = jnp.asarray(rng.randn(g, s, e).astype(np.float32))

    def onehot_path(x, logits, w):
        combine, dispatch, _ = top_k_routing(logits, k, capacity=s * k)
        expert_out = jnp.einsum(
            "egcm,emn->egcn", moe_dispatch(x, dispatch), w)
        return moe_combine(expert_out, combine)

    def sorted_path(x, logits, w):
        return _sorted_path(
            x.reshape(g * s, m), logits.reshape(g * s, e), w, k,
            normalize=True).reshape(g, s, m)

    np.testing.assert_allclose(
        np.asarray(onehot_path(x, logits, w)),
        np.asarray(sorted_path(x, logits, w)), atol=1e-5)
    got = jax.jit(jax.grad(
        lambda *a: (sorted_path(*a) ** 2).sum(), argnums=(0, 1, 2)
    ))(x, logits, w)
    want = jax.jit(jax.grad(
        lambda *a: (onehot_path(*a) ** 2).sum(), argnums=(0, 1, 2)
    ))(x, logits, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_load_balancing_and_z_loss_by_hand():
    # 4 tokens, 2 experts, k=1: three choose expert 0
    probs = jnp.asarray([[0.9, 0.1], [0.8, 0.2], [0.6, 0.4], [0.3, 0.7]])
    sizes = jnp.asarray([3, 1])
    want = 2 * (3 / 4 * np.mean([0.9, 0.8, 0.6, 0.3])
                + 1 / 4 * np.mean([0.1, 0.2, 0.4, 0.7]))
    assert float(moe_ops.load_balancing_loss(probs, sizes)) == (
        pytest.approx(want, rel=1e-6))
    # a uniform router over E experts with k choices scores k
    uniform = jnp.full((8, 4), 0.25)
    assert float(moe_ops.load_balancing_loss(
        uniform, jnp.asarray([4, 4, 4, 4]))) == pytest.approx(2.0)
    logits = jnp.asarray([[0.0, 0.0], [1.0, -1.0]])
    z = np.mean([np.log(2.0) ** 2, np.log(np.e + 1 / np.e) ** 2])
    assert float(moe_ops.router_z_loss(logits)) == pytest.approx(z, rel=1e-6)
