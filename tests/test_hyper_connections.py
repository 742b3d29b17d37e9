"""The hyper-connected residual path, the q latent under YaRN and the
multi-token-prediction module (PR 37, Xing4.0-29B-A4B's block): the
module's own properties at a small size on the CPU. The comparison with
the plain reference is ``tests/benchmark_harness/test_xing_reference.py``.
"""

import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models import moe_transformer as M
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.train import step_fns

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, DIM = 32, 16


def streams_of(seed, n, batch=2):
    return jax.random.normal(
        jax.random.PRNGKey(seed), (batch, n, SEQ, DIM), jnp.float32)


def trained(params, seed):
    """Gates and biases where a trained run's would be (the benchmark's
    check moves them the same way): at the initial values ``H_res`` is
    the identity to 1e-3 whatever the iterations do."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = dict(params)
    for name in ("a_pre", "a_post", "a_res"):
        params[name] = jnp.float32(0.8)
    params["b_res"] = jax.random.normal(keys[0], params["b_res"].shape)
    params["b_pre"] = jax.random.normal(keys[1], params["b_pre"].shape)
    params["b_post"] = jax.random.normal(keys[2], params["b_post"].shape)
    return params


def coefficients(dims, x, seed=3, y=None):
    """The module's reading ``u``, what it writes back of ``y`` (of
    zeros where none is given), its facts, ``H_res`` and the
    parameters: one program for the twenty iterations."""
    module = T.HyperConnection(dims)
    params = trained(
        jax.jit(module.init)(jax.random.PRNGKey(0), x)["params"], seed)

    @jax.jit
    def run(params, x, y):
        (u, write, facts), sown = module.apply(
            {"params": params}, x, mutable=["intermediates"])
        return u, write(y), facts, sown["intermediates"]["h_res"][0]

    y = jnp.zeros_like(x[:, 0]) if y is None else y
    return run(params, x, y) + (params,)


@pytest.mark.parametrize("n", [2, 4])
def test_h_res_is_doubly_stochastic(n):
    x = streams_of(1, n)
    _, _, facts, h_res, _ = coefficients(T.HyperDims(n), x)
    assert h_res.shape == (n, n, 2, SEQ)
    assert float(h_res.min()) > 0
    # the columns are normalised last; the rows are as near as twenty
    # iterations bring the slowest token's matrix (a nearly diagonal
    # one converges slowly: 7e-4 at n = 2 here)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=2e-3)
    assert float(jnp.abs(h_res.sum(axis=1) - 1).mean()) < 1e-4
    np.testing.assert_allclose(h_res.sum(axis=0), 1.0, atol=1e-5)
    assert float(facts["row_err"]) == pytest.approx(
        float(jnp.abs(h_res.sum(axis=1) - 1).max()), abs=1e-7)
    assert 0.0 < float(facts["diag_mean"]) < 1.0


def test_fewer_sinkhorn_iterations_leave_the_rows_off():
    """What the benchmark's check has to tell apart: 20 iterations from
    2 (the columns are normalised last, so they read 1 either way)."""
    x = streams_of(1, 4)
    _, _, full, _, _ = coefficients(T.HyperDims(4, sinkhorn_iters=20), x)
    _, _, cut, h_res, _ = coefficients(T.HyperDims(4, sinkhorn_iters=2), x)
    # twenty iterations leave the slowest of these 64 tokens 2e-3 off
    # (gates of 0.8 spread H~_res widely), two leave it 30 times that
    assert float(full["row_err"]) < 5e-3
    assert float(cut["row_err"]) > 10 * float(full["row_err"])
    np.testing.assert_allclose(h_res.sum(axis=0), 1.0, atol=1e-5)


def test_the_mixes_are_the_equations():
    n = 4
    x = streams_of(2, n)
    y = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, DIM))
    u, written, _, h_res, params = coefficients(T.HyperDims(n), x, y=y)
    flat = x.transpose(0, 2, 1, 3).reshape(2, SEQ, n * DIM)
    flat = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + 1e-6)
    project = lambda name: flat @ params[name].reshape(n * DIM, -1)
    h_pre = jax.nn.sigmoid(0.8 * project("p_pre") + params["b_pre"])
    h_post = 2 * jax.nn.sigmoid(0.8 * project("p_post") + params["b_post"])
    np.testing.assert_allclose(
        u, jnp.einsum("bsn,bnsc->bsc", h_pre, x), atol=1e-5)
    want = (jnp.einsum("mnbs,bnsc->bmsc", h_res, x)
            + jnp.einsum("bsm,bsc->bmsc", h_post, y))
    np.testing.assert_allclose(written, want, atol=1e-5)


def test_initial_values_read_and_write_one_stream():
    x = streams_of(4, 4)
    module = T.HyperConnection(T.HyperDims(4), select=6)
    variables = module.init(jax.random.PRNGKey(0), x)
    params = variables["params"]
    assert params["p_res"].shape == (4, DIM, 16)
    assert params["p_pre"].shape == params["p_post"].shape == (4, DIM, 4)
    assert float(params["a_res"]) == pytest.approx(T.HC_GATE_INIT)
    np.testing.assert_array_equal(params["b_pre"], [-4, -4, 4, -4])
    np.testing.assert_array_equal(params["b_post"], [-4, -4, 0, -4])
    (u, write, facts) = module.apply(variables, x)
    # stream 6 % 4 is read nearly alone, H_res starts at the identity
    assert float(facts["diag_mean"]) > 0.998
    np.testing.assert_allclose(u, x[:, 2], atol=0.2)
    np.testing.assert_allclose(write(jnp.zeros_like(u)), x, atol=0.02)


def block_fields():
    return dict(mixer=dict(num_heads=2, attention_impl="xla"),
                norm="rmsnorm", mlp_act="swiglu", mlp_dim=24)


def test_one_stream_with_unit_coefficients_is_the_plain_residual():
    """n = 1, H_pre = H_post = H_res = 1: ``x + F(norm(x))``."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, DIM))
    plain = T.Block(**block_fields())
    hyper = T.Block(hc=T.HyperDims(1), **block_fields())
    params = hyper.init(jax.random.PRNGKey(0), x[:, None])["params"]
    for name in ("hc_attn", "hc_mlp"):
        part = dict(params[name])
        for gate in ("a_pre", "a_post", "a_res"):
            part[gate] = jnp.float32(0.0)
        part["b_pre"] = jnp.full((1,), 40.0)   # sigmoid -> 1
        part["b_post"] = jnp.zeros((1,))       # 2 sigmoid(0) = 1
        params = dict(params, **{name: part})
    got, aux = hyper.apply({"params": params}, x[:, None])
    facts = aux["mhc"]
    rest = {k: v for k, v in params.items() if not k.startswith("hc_")}
    # the plain block's tree is the hyper-connected one's less the modules
    assert jax.tree_util.tree_structure(rest) == jax.tree_util.tree_structure(
        plain.init(jax.random.PRNGKey(0), x)["params"])
    want, _ = plain.apply({"params": rest}, x)
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-4, atol=1e-4)
    assert float(facts["row_err"].max()) < 1e-5


# --------------------------------------------------------------------- YaRN

def scaling(**changes):
    fields = dict(factor=64.0, original_max_position_embeddings=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                  mscale_all_dim=1.0)
    fields.update(changes)
    return T.YarnScaling(**fields)


def test_yarn_table_against_its_closed_form():
    """Xing4.0's numbers: 64 rope lanes, base 10,000, factor 64 over
    4,096: pairs 0-10 keep their frequency, 23-31 take it over 64, the
    twelve between blend linearly."""
    freqs = np.asarray(T.yarn_frequencies(64, 10000.0, scaling()))
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    turns = lambda r: 64 * math.log(4096 / (r * 2 * math.pi)) / (
        2 * math.log(10000.0))
    assert (math.floor(turns(32)), math.ceil(turns(1))) == (10, 23)
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (np.arange(32) - 10) / 13.0
    np.testing.assert_allclose(
        freqs[11:23],
        (plain * (1 - ramp) + plain / 64 * ramp)[11:23], rtol=1e-6)
    assert T.yarn_mscale(64.0, 1.0) == pytest.approx(0.1 * math.log(64) + 1)
    assert T.yarn_mscale(1.0, 1.0) == 1.0


def test_factor_one_is_the_rotary_embedding_of_today():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 48, 64))
    np.testing.assert_allclose(
        T.rotary_embedding(x, base=10000.0, scaling=scaling(factor=1.0)),
        T.rotary_embedding(x, base=10000.0), atol=1e-6)
    # cos and sin are unscaled where mscale equals mscale_all_dim, and
    # scaled by their ratio where it does not
    turned = T.rotary_embedding(x, base=10000.0, scaling=scaling())
    np.testing.assert_allclose(
        jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1),
        rtol=1e-4)
    louder = T.rotary_embedding(
        x, base=10000.0, scaling=scaling(mscale_all_dim=0.0))
    np.testing.assert_allclose(
        louder, turned * (0.1 * math.log(64) + 1), rtol=1e-4, atol=1e-6)


def test_q_latent_and_yarn_in_latent_attention():
    dims = T.LatentDims(kv_lora_rank=16, qk_nope_head_dim=8,
                        qk_rope_head_dim=8, v_head_dim=8, q_lora_rank=12)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, DIM))
    module = T.LatentAttention(
        2, dims, attention_impl="xla",
        rope_scaling=scaling(original_max_position_embeddings=8))
    variables = module.init(jax.random.PRNGKey(1), x)
    params = variables["params"]
    assert params["q_down"]["kernel"].shape == (DIM, 12)
    assert params["q_norm"]["scale"].shape == (12,)
    assert params["q_proj"]["kernel"].shape == (12, 2, 16)
    out = module.apply(variables, x)
    # the softmax scale carries mscale squared: without it the output
    # is another one
    flat = T.LatentAttention(
        2, dims, attention_impl="xla", rope_scaling=scaling(
            original_max_position_embeddings=8, mscale_all_dim=0.0,
            mscale=0.0))
    assert float(jnp.abs(flat.apply(variables, x) - out).max()) > 1e-4
    # no q latent: Moonlight's tree, unchanged
    plain = T.LatentAttention(2, T.LatentDims(16, 8, 8, 8))
    assert set(plain.init(jax.random.PRNGKey(1), x)["params"]) == {
        "q_proj", "kv_down", "kv_norm", "kv_up", "out_proj"}


# ------------------------------------------------------------ the model

def tiny_model(**changes):
    fields = dict(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=DIM,
        latent=T.LatentDims(16, 8, 8, 8, q_lora_rank=12),
        rope_scaling=scaling(original_max_position_embeddings=8),
        first_k_dense=1, dense_act="swiglu", dense_dim=24, num_experts=8,
        held_experts=(0, 2), held_rows=256, top_k=2, expert_dim=8,
        expert_act="swiglu", shared_experts=1, moe_every=1, norm="rmsnorm",
        scoring="sigmoid", gate_scale=2.0, bias_update_speed=0.001,
        dispatch_impl="sorted", aux_loss_weight=0.0, attention_impl="xla",
        hc=T.HyperDims(4), mtp_layers=1)
    fields.update(changes)
    return M.MoeTransformerLM(**fields)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, SEQ)), jnp.int32)


@pytest.fixture(scope="module")
def variables(tokens):
    return jax.jit(lambda t: tiny_model().init(
        jax.random.PRNGKey(0), t, training=False))(tokens)


@pytest.fixture(scope="module")
def outputs(tokens, variables):
    """The training call's outputs, one program for the file: eagerly
    the model is 2,700 dispatches and 260 small compiles a call."""
    return jax.jit(lambda v, t: tiny_model().apply(
        v, t, training=True, mutable=["moe_state"])[0])(variables, tokens)


def test_the_model_owns_the_module_and_shares_embedding_and_head(
        tokens, variables, outputs):
    params = variables["params"]
    assert {"mtp_proj", "mtp_hnorm", "mtp_enorm", "mtp_norm",
            "mtp_block"} <= set(params)
    assert params["mtp_proj"]["kernel"].shape == (2 * DIM, DIM)
    assert {"hc_attn", "hc_mlp"} <= set(params["mtp_block"])
    assert {"hc_attn", "hc_mlp"} <= set(params["block_0"])
    assert sum(1 for name in params if "wte" in name or "lm_head" in name
               ) == 2
    assert set(variables["moe_state"]) == {"block_1", "mtp_block"}
    assert outputs["mtp_logits"].shape == outputs["logits"].shape
    # a fact a block, the module's last
    assert outputs["mhc"]["row_err"].shape == (3,)
    assert outputs["mhc"]["diag_mean"].shape == (3,)
    # an eval call returns bare logits and runs no module
    assert jax.eval_shape(
        tiny_model().apply, variables, tokens).shape == (2, SEQ, 64)
    total, terms = jax.jit(M.loss)(tokens, outputs)
    from elasticdl_tpu.train.losses import sparse_softmax_cross_entropy as ce
    main = ce(tokens[:, 1:-1], outputs["logits"][:, :-2]).mean(-1)
    mtp = ce(tokens[:, 2:], outputs["mtp_logits"][:, :-2]).mean(-1)
    np.testing.assert_allclose(terms["mtp_loss"], mtp, rtol=1e-6)
    np.testing.assert_allclose(total, main + 0.1 * mtp, rtol=1e-6)


def test_the_module_is_causal_in_its_shifted_tokens(tokens, variables):
    """Position i reads t_(i+1): changing t_(j) moves the module's
    logits from position j - 1 on and nothing before."""
    model = tiny_model()
    run = jax.jit(lambda t: model.apply(
        variables, t, training=True, mutable=["moe_state"])[0])
    changed = tokens.at[:, 20].set((tokens[:, 20] + 1) % 64)
    a, b = run(tokens), run(changed)
    moved = np.abs(np.asarray(a["mtp_logits"] - b["mtp_logits"])).max(-1)
    assert moved[:, :19].max() == 0 and moved[:, 19].min() > 0
    main = np.abs(np.asarray(a["logits"] - b["logits"])).max(-1)
    assert main[:, :20].max() == 0 and main[:, 20].min() > 0


@pytest.mark.parametrize("changes", [
    dict(objective="block_diffusion", bd_mask_id=63, first_k_dense=0,
         latent=None, rope_scaling=None, mtp_layers=0),
    dict(objective="block_diffusion", bd_mask_id=63, first_k_dense=0,
         latent=None, rope_scaling=None, hc=None),
    dict(linear=T.GatedDeltaDims(2, 2, 8, 8, 4), layer_kinds=("linear",),
         latent=None, rope_scaling=None, first_k_dense=0, mtp_layers=0),
    dict(linear=T.GatedDeltaDims(2, 2, 8, 8, 4), layer_kinds=("linear",),
         latent=None, rope_scaling=None, first_k_dense=0, hc=None),
    dict(mtp_layers=2),
], ids=["hc-under-block-diffusion", "mtp-under-block-diffusion",
        "hc-beside-a-linear-mixer", "mtp-beside-a-linear-mixer",
        "two-modules"])
def test_what_is_not_built_is_refused_by_name(tokens, changes):
    with pytest.raises(ValueError, match="hc|mtp_layers"):
        tiny_model(**changes).init(
            jax.random.PRNGKey(0), tokens, training=False)


def test_the_step_hands_out_the_second_loss_and_the_facts(
        tokens, variables, outputs):
    import optax

    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.train.train_state import TrainState

    model, tx = tiny_model(), optax.sgd(0.1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=variables["params"],
        model_state={"moe_state": variables["moe_state"]},
        opt_state=tx.init(variables["params"]))
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((2,), jnp.float32)}
    step = jax.jit(step_fns.make_train_step(model, M.loss, tx, health=True))
    new_state, loss, scalars = step(state, batch)
    assert set(scalars) >= {"routing", "mhc", "loss_terms", "grad_norm"}
    assert "noise" not in scalars
    assert scalars["mhc"]["row_err"].shape == (3,)
    mtp = float(scalars["loss_terms"]["mtp_loss"])
    assert 0 < mtp and np.isfinite(float(loss))
    # the logged loss is the sum of its terms
    total, terms = jax.jit(M.loss)(tokens, outputs)
    assert float(loss) == pytest.approx(float(total.mean()), rel=1e-5)
    assert mtp == pytest.approx(float(terms["mtp_loss"].mean()), rel=1e-5)
    # a model without either compiles the step it compiled before: no
    # new outputs (the step's outputs are read from its trace)
    plain = tiny_model(hc=None, mtp_layers=0)

    def plain_step(tokens):
        init = plain.init(jax.random.PRNGKey(0), tokens, training=False)
        plain_state = TrainState(
            step=jnp.zeros((), jnp.int32), params=init["params"],
            model_state={"moe_state": init["moe_state"]},
            opt_state=tx.init(init["params"]))
        return step_fns.make_train_step(
            plain, M.loss, tx, health=True)(plain_state, batch)

    _, _, scalars = jax.eval_shape(plain_step, tokens)
    assert "mhc" not in scalars and "loss_terms" not in scalars


def test_the_worker_logs_the_terms_beside_the_loss():
    """The harness reads ``step N loss X`` (``benchmark/lib/logs.py``):
    the named terms follow it on the same line."""
    import re

    line = "step %d loss %.6f%s" % (8, 7.5, "".join(
        " %s %.6f" % item for item in sorted({"mtp_loss": 6.25}.items())))
    assert line == "step 8 loss 7.500000 mtp_loss 6.250000"
    m = re.search(r"step (\d+) loss (\S+)", line)
    assert (m.group(1), float(m.group(2))) == ("8", 7.5)
    from elasticdl_tpu.observability import events

    assert {"mhc", "loss_terms"} <= set(events.EVENT_TYPES)


@pytest.mark.parametrize("hc,lines", [(T.HyperDims(4), 1), (None, 0)],
                         ids=["hyper-connected", "plain"])
def test_a_model_with_hc_logs_its_path_once(caplog, tokens, hc, lines):
    """The counter that says which path a program got (PR 38), from
    where ``ops/hyper_connection.py:mix_impl`` chooses: six sublayers
    (two blocks' and the prediction module's), one line; none from a
    model without ``hc``."""
    import logging

    from elasticdl_tpu.ops import hyper_connection

    hyper_connection.log_choice.cache_clear()
    with caplog.at_level(logging.INFO):
        jax.eval_shape(lambda t: tiny_model(hc=hc).init(
            jax.random.PRNGKey(0), t, training=False), tokens)
    hyper_connection.log_choice.cache_clear()
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("hyper-connections")]
    assert said == ["hyper-connections streams=4 dim=16 iters=20 impl=xla "
                    "(tokens=32)"] * lines


# ------------------------------------------- the older configurations

# (leaves, sha256 of the sorted (path, shape, dtype) list) of every
# older configuration's parameter tree at the parent of PR 37
# (5827fd3): none goes through the new module
OLDER_TREES = {
    "pythia-1b": (164, "81fdcfb10c4e23bf"),
    "pythia-1b-1chip": (84, "1b7856fbde745359"),
    "olmoe-1b-7b-1chip": (15, "fc3c44ac43dd7576"),
    "moonlight-16b-a3b-1chip": (28, "038442c73d93fed0"),
    "qwen3-next-80b-a3b-1chip": (70, "1c521b15f31be855"),
    "sdar-30b-a3b-1chip": (75, "a2b5fb2f83911209"),
}


# and Xing4.0's own at PR 37 (691b774): PR 38 moved the hyper-
# connection's work into kernels and not a leaf of its tree, so a
# checkpoint of PR 37 restores
XING_TREE = {"xing4.0-29b-a4b-1chip": (212, "e869de3eb4a386c1")}


def parameter_tree(name):
    """(leaves, digest) of configuration ``name``'s parameter tree and
    the sorted (path, shape, dtype) list itself."""
    from benchmark.lib.refcheck import load_by_path

    with open(os.path.join(
            REPO, "benchmark", "configs", name, "config.json")) as f:
        config = json.load(f)
    zoo = load_by_path(
        "zoo_tree_" + name.replace("-", "_").replace(".", "_"),
        os.path.join(REPO, config["zoo"]))
    model = zoo.model_from_config(config)
    tree = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t, training=False),
        jax.ShapeDtypeStruct((1, 128), jnp.int32))
    flat = sorted(
        ("/".join(str(getattr(k, "key", k)) for k in path),
         tuple(leaf.shape), str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])
    return (len(flat), hashlib.sha256(
        repr(flat).encode()).hexdigest()[:16]), flat


@pytest.mark.parametrize("name", sorted(OLDER_TREES))
def test_the_older_configurations_parameter_trees_are_unchanged(name):
    digest, flat = parameter_tree(name)
    assert not any("hc_" in path or "mtp_" in path for path, _, _ in flat)
    assert digest == OLDER_TREES[name]


@pytest.mark.parametrize("name", sorted(XING_TREE))
def test_the_hyper_connected_configuration_s_tree_is_pr_37_s(name):
    digest, flat = parameter_tree(name)
    # five blocks' and the module's two hyper-connections, nine leaves each
    assert sum(1 for path, _, _ in flat if "/hc_" in path) == 12 * 9
    assert digest == XING_TREE[name]
