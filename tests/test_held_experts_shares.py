"""The shares of an expert layer add up (ISSUE 31): each chip's
``MoeMlp`` with ``held_experts`` holds its own experts' kernels, routes
over ALL the experts and returns its part, and the parts of all the
chips of a layer, the shared expert counted once, sum to what the
configuration's uncut plain reference gives, at small sizes on the CPU.
The share's own operations are ``test_held_experts.py``'s, whose file
this was part of."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.moe_transformer import MoeMlp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference_of(configuration):
    path = os.path.join(
        REPO, "benchmark", "configs", configuration, "reference.py")
    spec = importlib.util.spec_from_file_location(
        configuration.replace("-", "_") + "_ref", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# (the configuration whose reference gives the uncut layer, experts,
# top-k, shared experts behind a gate, the ways the layer is shared)
SHARE_CASES = {
    # Qwen3-Next's layer at a small size: 16 experts, top-3, one
    # shared expert behind its gate, over 16 / 4 / 2 chips
    "qwen3next-16-top3-shared": (
        "qwen3-next-80b-a3b-1chip", 16, 3, 1, (16, 4, 2)),
    # SDAR's layer at its published counts: 128 experts, top-8, no
    # shared expert, the deployment's eight shares (16 experts a chip)
    "sdar-128-top8-eight-shares": (
        "sdar-30b-a3b-1chip", 128, 8, 0, (8,)),
}


@pytest.mark.parametrize(
    "case", list(SHARE_CASES.values()), ids=list(SHARE_CASES))
def test_the_sixteen_shares_add_up_to_the_uncut_layer(case):
    """Each chip's ``MoeMlp`` holds its own experts' kernels (rows of
    ONE seeded stack), routes over all the experts and returns its
    part; the parts, a shared expert counted once, sum to what the
    uncut reference gives for the whole layer."""
    configuration, experts, top_k, shared_experts, ways = case
    ref = _reference_of(configuration)
    config = {"num_experts_per_tok": top_k, "norm_topk_prob": True,
              "published": {"num_experts": experts}}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    flat = x.reshape(24, 16)

    def layer(held):
        return MoeMlp(
            experts, top_k=top_k, dispatch_impl="sorted", expert_dim=8,
            expert_act="swiglu", normalize_gates=True,
            shared_experts=shared_experts, shared_gate=bool(shared_experts),
            held_experts=held, held_rows=24 * top_k)

    whole = layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    assert params["w_gate"].shape == (experts, 16, 8)
    want, want_balance, _ = ref.expert_layer(
        flat, params, config, (0, experts))
    got, aux = whole.apply({"params": params}, x)
    np.testing.assert_allclose(got.reshape(24, 16), want, atol=1e-5)
    shared = ref.shared_expert(flat, params) if shared_experts else 0.0
    for chips in ways:
        count = experts // chips
        total = 0.0
        for chip in range(chips):
            first = chip * count
            mine = dict(params, **{
                name: params[name][first:first + count]
                for name in ("w_gate", "w_up", "w_down")})
            part, part_aux = layer((first, count)).apply(
                {"params": mine}, x)
            # the reference is given the same share
            ref_part, _, _ = ref.expert_layer(
                flat, mine, config, (first, count))
            np.testing.assert_allclose(
                part.reshape(24, 16), ref_part, atol=1e-5)
            # every chip sees every expert's load and the same loss
            np.testing.assert_allclose(
                part_aux["load_balancing"], want_balance, rtol=1e-5)
            assert float(part_aux["routing"]["dropped"]) == 0
            total = total + part.reshape(24, 16) - shared
        np.testing.assert_allclose(total + shared, want, atol=2e-5)
        assert float(jnp.abs(total).max()) > 1e-2


# (the configuration whose reference gives the uncut layer, experts,
# top-k, chips, the experts' body, the layer's own fields, the
# reference's config, the kernels a share holds its own rows of)
SIGMOID_CASES = {
    # Kimi Linear's expert layer at its published counts and a small
    # width: 256 sigmoid-routed experts, top-8 by score + balancing
    # bias, gates renormalised and scaled by 2.446, one SwiGLU shared
    # expert; 32 chips hold 8 experts each
    "kimi-256-top8-thirty-two-shares": (
        "kimi-linear-48b-a3b-1chip", 256, 8, 32,
        dict(expert_act="swiglu", gate_scale=2.446, seq_aux=True,
             shared_experts=1),
        {"num_experts_per_token": 8, "moe_renormalize": True,
         "routed_scaling_factor": 2.446},
        ("w_gate", "w_up", "w_down")),
    # Nemotron-3-Nano's at its published counts: 128 experts, top-6,
    # scaled by 2.5, every body ``relu(x W_up)^2 W_down``, the shared
    # expert twice an expert's width; 16 chips hold 8 experts each
    "nemotron-128-top6-relu2-sixteen-shares": (
        "nemotron-3-nano-30b-a3b-1chip", 128, 6, 16,
        dict(expert_act="relu2", gate_scale=2.5, shared_experts=2,
             router_float32=True),
        {"num_experts_per_tok": 6, "routed_scaling_factor": 2.5},
        ("w_up", "w_down")),
}


@pytest.mark.parametrize(
    "case", list(SIGMOID_CASES.values()), ids=list(SIGMOID_CASES))
def test_the_shares_of_a_sigmoid_routed_layer_add_up(case):
    """The shares' parts of a sigmoid-routed layer with a balancing
    bias, the shared expert counted once, sum to what the uncut
    reference gives for the whole layer: the output and the gradient
    that reaches the layer's input."""
    configuration, experts, top_k, chips, fields, config, stacked = case
    ref = _reference_of(configuration)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 16))
    weight = jax.random.normal(jax.random.PRNGKey(2), (24, 16))

    def layer(held):
        return MoeMlp(
            experts, top_k=top_k, dispatch_impl="sorted", expert_dim=8,
            normalize_gates=True, scoring="sigmoid",
            bias_update_speed=0.001, held_experts=held,
            held_rows=24 * top_k, **fields)

    variables = layer(None).init(jax.random.PRNGKey(1), x)
    params = variables["params"]
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (experts,))
    state = {"moe_state": {"e_score_correction_bias": bias}}
    assert params[stacked[0]].shape == (experts, 16, 8)
    assert {name for name in params if name.startswith("w_")} == set(stacked)
    def uncut(x):
        flat = x.reshape(24, 16)
        y, _, chosen = ref.expert_layer(
            flat, params, bias, config, (0, experts))
        return ((y + ref.shared_expert(flat, params)) * weight).sum(), chosen

    (_, chosen), want_dx = jax.value_and_grad(uncut, has_aux=True)(x)
    # the bias chooses: without it the same router picks other experts
    _, _, unbiased = ref.expert_layer(
        x.reshape(24, 16), params, 0.0 * bias, config, (0, experts))
    assert bool((jnp.sort(chosen) != jnp.sort(unbiased)).any())
    want, _ = uncut(x)
    count = experts // chips

    def part(x, first):
        mine = dict(params, **{
            name: params[name][first:first + count] for name in stacked})
        y, aux = layer((first, count)).apply(
            {"params": mine, **state}, x)
        return (y.reshape(24, 16) * weight).sum(), aux["routing"]["dropped"]

    shared = lambda x: (
        ref.shared_expert(x.reshape(24, 16), params) * weight).sum()
    total, total_dx = 0.0, 0.0
    for chip in range(chips):
        first = chip * count
        (value, dropped), dx = jax.value_and_grad(
            lambda x: part(x, first), has_aux=True)(x)
        assert float(dropped) == 0
        total, total_dx = total + value, total_dx + dx
    # every share added the shared expert: count it once
    total = total - (chips - 1) * shared(x)
    total_dx = total_dx - (chips - 1) * jax.grad(shared)(x)
    # float32 sums of the parts less all but one of the shared experts
    np.testing.assert_allclose(total, want, rtol=1e-4)
    np.testing.assert_allclose(total_dx, want_dx, atol=1e-4)
    assert float(jnp.abs(want_dx).max()) > 1e-2
