"""The one residual block (``models/transformer.py:Block``), the two
values a model hands it whole (the mixer's fields, the experts'), and
the two questions every kernel's chooser asks in one place
(``common/jax_compat.py``). Shapes and fields alone are read: no model
is drawn, no kernel runs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import jax_compat
from elasticdl_tpu.models import transformer as T
from elasticdl_tpu.models.moe_transformer import (
    EXPERT_FIELDS,
    MoeMlp,
    MoeTransformerLM,
)

DIM, SEQ = 32, 16
EXPERTS = dict(
    num_experts=4, top_k=2, dispatch_impl="sorted", expert_dim=16,
    expert_act="swiglu")


def tree_of(block, x):
    """(the parameter tree's paths, the keys of ``aux``) of ``block``
    over ``x``, from shapes alone."""
    def run(x):
        (_, aux), variables = block.init_with_output(
            jax.random.PRNGKey(0), x)
        return variables["params"], aux

    params, aux = jax.eval_shape(run, x)
    paths = {
        "/".join(k.key for k in path) for path, _ in
        jax.tree_util.tree_flatten_with_path(params)[0]}
    return paths, set(aux)


@pytest.mark.parametrize("mixer", ["attention", "conv"])
@pytest.mark.parametrize("hc", [None, T.HyperDims(2)], ids=["plain", "hc"])
@pytest.mark.parametrize("second", ["dense", "experts"])
def test_the_one_block_s_tree_and_what_it_returns(second, hc, mixer):
    """Every parameter path a checkpoint, a sharding rule or a
    ``check.py`` reads, and the keys of ``aux``, over {dense, experts}
    x {plain, hc} x {attention, conv}."""
    fields = dict(num_heads=2, attention_impl="xla")
    attn = {"attn/query/kernel", "attn/key/kernel", "attn/value/kernel",
            "attn/out_proj/kernel"}
    if mixer == "conv":
        fields["conv"] = T.ShortConvDims(3)
        attn = {"attn/in_proj/kernel", "attn/conv_kernel",
                "attn/proj_out/kernel"}
    sublayer = dict(mlp_act="swiglu", mlp_dim=24)
    want = {"mlp_gate/kernel", "mlp_up/kernel", "mlp_down/kernel"}
    keys = set()
    if second == "experts":
        sublayer = dict(experts=EXPERTS)
        want = {"moe_mlp/router/kernel", "moe_mlp/w_gate", "moe_mlp/w_up",
                "moe_mlp/w_down"}
        keys = {"load_balancing", "router_z", "routing"}
    want |= attn | {"ln_attn/scale", "ln_mlp/scale"}
    shape = (2, SEQ, DIM)
    if hc is not None:
        shape = (2, hc.streams, SEQ, DIM)
        keys.add("mhc")
        want |= {
            "hc_%s/%s_%s" % (sub, kind, part) for sub in ("attn", "mlp")
            for kind in "abp" for part in ("pre", "post", "res")}
    block = T.Block(fields, norm="rmsnorm", hc=hc, **sublayer)
    paths, aux = tree_of(block, jax.ShapeDtypeStruct(shape, jnp.float32))
    assert paths == want
    assert aux == keys


def test_the_block_states_fewer_fields_than_a_mixer_has():
    """The mixer's and the experts' fields travel as one value each:
    the block names neither's."""
    own = {f.name for f in dataclasses.fields(T.Block)} - {"parent", "name"}
    assert own == {
        "mixer", "experts", "mlp_act", "mlp_dim", "mlp_ratio", "dropout",
        "norm", "norm_eps", "hc", "layer_index", "mesh", "sandwich",
        "residual_scale", "only"}
    attention = {f.name for f in dataclasses.fields(T.Attention)}
    assert not own & (attention - {"mesh", "dropout", "norm_eps",
                                   "parent", "name"})


def test_every_expert_field_is_the_model_s_with_the_same_default():
    """The experts' value is built from ``dataclasses.fields(MoeMlp)``:
    a field added to the layer and not to the model fails here by name,
    and one added to both reaches the layer with no edit between."""
    layer = {f.name: f for f in dataclasses.fields(MoeMlp)}
    model = {f.name: f for f in dataclasses.fields(MoeTransformerLM)}
    assert set(EXPERT_FIELDS) == set(layer) - {"mesh", "parent", "name"}
    # MoeMlp's one field without a default is the model's 8
    assert layer["num_experts"].default is dataclasses.MISSING
    for name in EXPERT_FIELDS:
        assert name in model, (
            "MoeMlp.%s is no field of MoeTransformerLM" % name)
        if name != "num_experts":
            assert model[name].default == layer[name].default, name


def test_a_model_s_expert_fields_reach_the_layer(monkeypatch):
    """Each of the model's expert fields, set to a value of its own,
    is the layer's of that name: read off the blocks the model builds."""
    seen = []
    real = MoeMlp.__call__

    def spy(self, x, training=False):
        seen.append({name: getattr(self, name) for name in EXPERT_FIELDS})
        return real(self, x, training)

    monkeypatch.setattr(MoeMlp, "__call__", spy)
    stated = dict(
        num_experts=4, mlp_ratio=2, top_k=1, capacity_factor=2.0,
        dispatch_impl="sorted", expert_dim=16, expert_act="swiglu",
        normalize_gates=False, scoring="sigmoid", gate_scale=1.5,
        bias_update_speed=0.01, seq_aux=True, shared_experts=1,
        held_experts=(0, 2), held_rows=64, shared_gate=True,
        exchange_rows=None, router_float32=True)
    assert set(stated) == set(EXPERT_FIELDS)
    model = MoeTransformerLM(
        vocab_size=64, num_layers=2, num_heads=2, embed_dim=DIM,
        moe_every=1, attention_impl="xla", **stated)
    jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((2, SEQ), jnp.int32))
    assert len(seen) == 2 and all(fields == stated for fields in seen)


@pytest.mark.parametrize("place,nothing", [
    ("no-mesh", True), ("one-device", True), ("four-devices", False),
    ("manual-region", True)])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_two_questions_of_a_kernel_s_chooser(
        monkeypatch, backend, place, nothing):
    """``nothing_to_partition``: no mesh, one device, or a region
    already manual over the whole mesh; ``kernels_can_run``: that, on a
    TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    devices = {"one-device": 1, "four-devices": 4, "manual-region": 4}
    mesh = None if place == "no-mesh" else Mesh(
        np.array(jax.devices()[:devices[place]]), ("data",))
    seen = []

    def ask(x):
        seen.append((jax_compat.nothing_to_partition(mesh),
                     jax_compat.kernels_can_run(mesh)))
        return x

    if place == "manual-region":
        ask = jax_compat.shard_map(
            ask, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    jax.eval_shape(ask, jnp.zeros(4))
    assert seen == [(nothing, nothing and backend == "tpu")]
