"""Pipeline parallelism: schedule correctness and end-to-end training.

Mirrors the reference's tier-2 strategy (SURVEY.md §4) — distributed
behavior exercised without hardware, here on the 8-virtual-device CPU
mesh — for the pp axis the reference never had (SURVEY.md §2.12).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.models import pipeline_transformer, transformer
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh
from elasticdl_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_stage_params,
    unstack_stage_params,
)
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer


def _affine_stages(num_stages, dim=8, seed=0):
    rng = np.random.RandomState(seed)
    return [
        dict(
            W=jnp.asarray(rng.randn(dim, dim) * 0.3, jnp.float32),
            b=jnp.asarray(rng.randn(dim) * 0.1, jnp.float32),
        )
        for _ in range(num_stages)
    ]


def _stage_fn(p, x):
    return jnp.tanh(x @ p["W"] + p["b"])


def _sequential(params_list, x):
    for p in params_list:
        x = _stage_fn(p, x)
    return x


def test_pipeline_forward_matches_sequential():
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    params = _affine_stages(4)
    stacked = stack_stage_params(params)
    x = jnp.asarray(np.random.RandomState(1).randn(16, 8), jnp.float32)

    out = jax.jit(
        lambda sp, x: pipeline_apply(
            _stage_fn, sp, x, num_microbatches=4, mesh=mesh
        )
    )(stacked, x)
    ref = _sequential(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )


def test_pipeline_gradients_match_sequential():
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    params = _affine_stages(4, seed=2)
    stacked = stack_stage_params(params)
    x = jnp.asarray(np.random.RandomState(3).randn(8, 8), jnp.float32)

    g_pipe = jax.jit(
        jax.grad(
            lambda sp: jnp.mean(
                pipeline_apply(_stage_fn, sp, x, 2, mesh) ** 2
            )
        )
    )(stacked)
    g_seq = jax.grad(
        lambda ps: jnp.mean(_sequential(ps, x) ** 2)
    )(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_pipe),
        jax.tree_util.tree_leaves(stack_stage_params(g_seq)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        )


def test_microbatch_count_independence():
    """The schedule must be a pure implementation detail: any M gives
    identical outputs."""
    mesh = build_mesh(MeshConfig(dp=1, pp=4), num_devices=4)
    params = _affine_stages(4, seed=4)
    stacked = stack_stage_params(params)
    x = jnp.asarray(np.random.RandomState(5).randn(12, 8), jnp.float32)
    outs = [
        np.asarray(
            jax.jit(
                lambda sp, x, m=m: pipeline_apply(
                    _stage_fn, sp, x, m, mesh
                )
            )(stacked, x)
        )
        for m in (1, 2, 4, 6)
    ]
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, atol=1e-5)


def test_stack_unstack_roundtrip():
    params = _affine_stages(3, seed=6)
    stacked = stack_stage_params(params)
    unstacked = unstack_stage_params(stacked, 3)
    for orig, back in zip(params, unstacked):
        for a, b in zip(
            jax.tree_util.tree_leaves(orig),
            jax.tree_util.tree_leaves(back),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _lm_batch(batch=8, seq=16, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, vocab, size=(batch, seq)).astype(np.int32)
    return {
        "features": tokens,
        "labels": tokens,
        "_mask": np.ones((batch,), np.float32),
    }


def test_pipelined_lm_matches_sequential_fallback():
    """Same params through the pp=4 pipeline and the meshless sequential
    path must produce identical logits."""
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    kwargs = dict(
        vocab_size=64,
        num_layers=8,
        num_stages=4,
        num_heads=2,
        embed_dim=16,
        num_microbatches=2,
        attention_impl="xla",
    )
    piped = pipeline_transformer.PipelinedTransformerLM(
        mesh=mesh, **kwargs
    )
    seq_model = pipeline_transformer.PipelinedTransformerLM(
        mesh=None, **kwargs
    )
    batch = _lm_batch()
    variables = piped.init(jax.random.PRNGKey(0), batch["features"])
    out_piped = jax.jit(
        lambda v, t: piped.apply(v, t, training=False)
    )(variables, batch["features"])
    out_seq = jax.jit(
        lambda v, t: seq_model.apply(v, t, training=False)
    )(variables, batch["features"])
    np.testing.assert_allclose(
        np.asarray(out_piped), np.asarray(out_seq), atol=1e-4
    )


def test_pipelined_lm_runs_the_flash_kernel_in_its_manual_region(
        monkeypatch):
    """attention_impl="pallas" (what "auto" resolves to on a TPU) under
    pp=4: the stage body is already inside pipeline_apply's VMA-checked
    manual region, so the dispatcher must run the kernel there as is —
    no nested shard_map over the block's mesh — with pallas_call
    outputs that declare their own vma.
    Loss and gradients equal the meshless XLA-attention model's.
    Pallas runs in interpret mode on the CPU."""
    import functools

    monkeypatch.setattr(
        transformer,
        "dot_product_attention",
        functools.partial(
            transformer.dot_product_attention, interpret=True
        ),
    )
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    kwargs = dict(
        vocab_size=64, num_layers=4, num_stages=4, num_heads=2,
        embed_dim=16, num_microbatches=2,
    )
    tokens = jnp.asarray(_lm_batch(batch=8, seq=128)["features"])

    def loss_and_grads(impl, mesh):
        model = pipeline_transformer.PipelinedTransformerLM(
            attention_impl=impl, mesh=mesh, **kwargs
        )
        # one program: an operation at a time the init dispatches the
        # interpreted kernel and the pipeline's region piece by piece
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)

        def loss_fn(params):
            logits = model.apply({"params": params}, tokens)
            return jnp.mean(
                transformer.loss(tokens, logits).astype(jnp.float32)
            )

        return jax.jit(jax.value_and_grad(loss_fn))(variables["params"])

    ref_loss, ref_grads = loss_and_grads("xla", None)
    loss, grads = loss_and_grads("pallas", mesh)
    assert np.isclose(float(loss), float(ref_loss), rtol=1e-5)
    for got, ref in zip(
        jax.tree_util.tree_leaves(grads),
        jax.tree_util.tree_leaves(ref_grads),
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-5
        )


def test_zoo_contract_mesh_injection():
    """The model-zoo entry must build a pipeline matching the mesh's pp
    extent when given a mesh (the worker passes its trainer mesh), and a
    sequential model when not."""
    from elasticdl_tpu.models.registry import get_model_spec

    spec = get_model_spec("elasticdl_tpu.models.pipeline_transformer")
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    model = spec.custom_model(mesh=mesh)
    assert model.num_stages == 4
    assert model.mesh is mesh
    assert spec.custom_model().mesh is None
    config = spec.mesh_config(8)
    assert config.pp == 4 and config.dp == 2


def test_param_layout_is_topology_independent():
    """Checkpoints must restore across pp extents: init() leaf shapes
    cannot depend on num_stages, and a non-divisor pp must raise rather
    than silently change depth."""
    batch = _lm_batch()
    kwargs = dict(
        vocab_size=64, num_layers=8, num_heads=2, embed_dim=16
    )
    v4 = pipeline_transformer.PipelinedTransformerLM(
        num_stages=4, **kwargs
    ).init(jax.random.PRNGKey(0), batch["features"])
    v2 = pipeline_transformer.PipelinedTransformerLM(
        num_stages=2, **kwargs
    ).init(jax.random.PRNGKey(0), batch["features"])
    for a, b in zip(
        jax.tree_util.tree_leaves(v4), jax.tree_util.tree_leaves(v2)
    ):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    with pytest.raises(ValueError, match="not divisible"):
        pipeline_transformer.PipelinedTransformerLM(
            num_stages=3, **kwargs
        )


def test_pipelined_lm_trains_on_pp_mesh():
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    model = pipeline_transformer.PipelinedTransformerLM(
        vocab_size=64,
        num_layers=4,
        num_stages=4,
        num_heads=2,
        embed_dim=16,
        num_microbatches=2,
        attention_impl="xla",
        mesh=mesh,
    )
    trainer = SpmdTrainer(
        model=model,
        loss_fn=pipeline_transformer.loss,
        optimizer=transformer.optimizer(),
        mesh=mesh,
        seed=0,
        sharding_rules=pipeline_transformer.sharding_rules(),
    )
    batch = _lm_batch(batch=8, seq=16)
    state = trainer.create_state(batch["features"])

    # Stage params (and their optimizer state) must actually shard over pp.
    blocks_sh = trainer.state_shardings.params["blocks"]
    leaf = jax.tree_util.tree_leaves(blocks_sh)[0]
    assert leaf.spec[0] == "pp"

    losses = []
    for _ in range(5):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_schedules_agree():
    """gpipe and 1f1b are different execution schedules of the same
    math: outputs and gradients must match each other exactly."""
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    params = _affine_stages(4, seed=7)
    stacked = stack_stage_params(params)
    x = jnp.asarray(np.random.RandomState(8).randn(8, 8), jnp.float32)

    outs, grads = [], []
    for schedule in ("gpipe", "1f1b"):
        def loss(sp, schedule=schedule):
            return jnp.mean(
                pipeline_apply(
                    _stage_fn, sp, x, 2, mesh, schedule=schedule
                ) ** 2
            )

        value, grad = jax.jit(jax.value_and_grad(loss))(stacked)
        outs.append(float(value))
        grads.append(grad)
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads[0]),
        jax.tree_util.tree_leaves(grads[1]),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        )


def test_interleaved_chunks_match_sequential():
    """num_chunks=2: 8 virtual chunks over pp=4, microbatches wrap from
    the last device back to the first; forward and gradients must match
    the 8-stage sequential reference."""
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    params = _affine_stages(8, seed=9)
    stacked = stack_stage_params(params)
    x = jnp.asarray(np.random.RandomState(10).randn(16, 8), jnp.float32)

    out = jax.jit(
        lambda sp, x: pipeline_apply(
            _stage_fn, sp, x, num_microbatches=4, mesh=mesh, num_chunks=2
        )
    )(stacked, x)
    ref = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    g_pipe = jax.jit(
        jax.grad(
            lambda sp: jnp.mean(
                pipeline_apply(_stage_fn, sp, x, 4, mesh, num_chunks=2)
                ** 2
            )
        )
    )(stacked)
    g_seq = jax.grad(
        lambda ps: jnp.mean(_sequential(ps, x) ** 2)
    )(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_pipe),
        jax.tree_util.tree_leaves(stack_stage_params(g_seq)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_interleaved_requires_small_m():
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    params = _affine_stages(8, seed=9)
    stacked = stack_stage_params(params)
    x = jnp.asarray(np.random.RandomState(10).randn(16, 8), jnp.float32)
    with pytest.raises(ValueError, match="conflict-free"):
        pipeline_apply(_stage_fn, stacked, x, 8, mesh, num_chunks=2)


def test_bubble_fraction_interleaving_beats_gpipe():
    """The 'measured bubble' contract: tick counts come straight from
    the scan lengths (M + S*V - 1 per direction); interleaving V=2
    strictly beats the V=1/GPipe bubble at M = S."""
    from elasticdl_tpu.parallel.pipeline import schedule_info

    gpipe = schedule_info(num_stages=4, num_microbatches=4, num_chunks=1)
    inter = schedule_info(num_stages=4, num_microbatches=4, num_chunks=2)
    assert gpipe["ticks_per_direction"] == 4 + 4 - 1
    assert inter["ticks_per_direction"] == 4 + 8 - 1
    assert inter["bubble_fraction"] < gpipe["bubble_fraction"]
    # 1f1b linear memory vs gpipe autodiff's O((M+S)*M) carry saves
    assert inter["activations_per_device"] == 8


def _tp_stage_fn(p, x):
    """Megatron-style column+row parallel MLP: W1 sharded on its output
    dim over tp, W2 on its input dim; one manual all-reduce rejoins the
    activation — tensor parallelism INSIDE a pipeline stage. Routed
    through mesh_psum (not bare lax.psum): the schedule differentiates
    the stage body inside the shard_map region, and mesh_psum is the
    collective whose transpose is correct there on every jax version
    (see parallel/collectives.py)."""
    from elasticdl_tpu.parallel.collectives import mesh_psum

    h = jnp.maximum(x @ p["W1"], 0.0)
    return mesh_psum(h @ p["W2"], "tp") + p["b"]


def test_tp_inside_pp():
    """tp composes within a stage: stage params shard over tp via
    param_specs, the stage body psums over tp, gradients match the
    single-device sequential reference."""
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh(MeshConfig(dp=2, pp=2, tp=2))
    rng = np.random.RandomState(11)
    dim, hidden = 8, 16
    params = [
        dict(
            W1=jnp.asarray(rng.randn(dim, hidden) * 0.3, jnp.float32),
            W2=jnp.asarray(rng.randn(hidden, dim) * 0.3, jnp.float32),
            b=jnp.asarray(rng.randn(dim) * 0.1, jnp.float32),
        )
        for _ in range(2)
    ]
    stacked = stack_stage_params(params)
    param_specs = dict(
        W1=P("pp", None, "tp"), W2=P("pp", "tp", None), b=P("pp")
    )
    x = jnp.asarray(np.random.RandomState(12).randn(8, dim), jnp.float32)

    def seq(ps, x):
        for p in ps:
            x = jnp.maximum(x @ p["W1"], 0.0) @ p["W2"] + p["b"]
        return x

    out = jax.jit(
        lambda sp, x: pipeline_apply(
            _tp_stage_fn, sp, x, 2, mesh, param_specs=param_specs
        )
    )(stacked, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(seq(params, x)), atol=1e-5
    )

    g_pipe = jax.jit(
        jax.grad(
            lambda sp: jnp.mean(
                pipeline_apply(
                    _tp_stage_fn, sp, x, 2, mesh, param_specs=param_specs
                ) ** 2
            )
        )
    )(stacked)
    g_seq = jax.grad(lambda ps: jnp.mean(seq(ps, x) ** 2))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_pipe),
        jax.tree_util.tree_leaves(stack_stage_params(g_seq)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_pipeline_mlp_trains_on_pptp_mesh():
    """The pp x tp model family end to end: stage params shard over
    both axes, loss decreases under the SPMD trainer."""
    from elasticdl_tpu.models import pipeline_mlp

    mesh = build_mesh(MeshConfig(dp=2, pp=2, tp=2))
    model = pipeline_mlp.PipelinedMlpNet(
        num_classes=4, dim=16, hidden=32, num_layers=4,
        num_stages=2, num_microbatches=2, mesh=mesh,
    )
    trainer = SpmdTrainer(
        model=model,
        loss_fn=pipeline_mlp.loss,
        optimizer=pipeline_mlp.optimizer(),
        mesh=mesh,
        seed=0,
        sharding_rules=pipeline_mlp.sharding_rules(),
    )
    rng = np.random.RandomState(0)
    features = rng.randn(16, 16).astype(np.float32)
    labels = (features.sum(axis=1) > 0).astype(np.int32)
    batch = {
        "features": features,
        "labels": labels,
        "_mask": np.ones((16,), np.float32),
    }
    state = trainer.create_state(batch["features"])
    # W1 actually sharded over both pp (layer stack) and tp (hidden dim)
    w1_spec = trainer.state_shardings.params["blocks"]["W1"].spec
    assert w1_spec[0] == "pp" and "tp" in tuple(w1_spec)
    losses = []
    for _ in range(30):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_interleaved_transformer_matches_sequential():
    """PipelinedTransformerLM with num_chunks=2: identical logits to
    the meshless sequential path."""
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    kwargs = dict(
        vocab_size=64,
        num_layers=8,
        num_stages=4,
        num_heads=2,
        embed_dim=16,
        num_microbatches=2,
        attention_impl="xla",
    )
    piped = pipeline_transformer.PipelinedTransformerLM(
        mesh=mesh, num_chunks=2, **kwargs
    )
    seq_model = pipeline_transformer.PipelinedTransformerLM(
        mesh=None, **kwargs
    )
    batch = _lm_batch()
    variables = piped.init(jax.random.PRNGKey(0), batch["features"])
    out_piped = jax.jit(
        lambda v, t: piped.apply(v, t, training=False)
    )(variables, batch["features"])
    out_seq = jax.jit(
        lambda v, t: seq_model.apply(v, t, training=False)
    )(variables, batch["features"])
    np.testing.assert_allclose(
        np.asarray(out_piped), np.asarray(out_seq), atol=1e-4
    )


def test_device_major_layout_matches_chunk_major():
    """params_layout='device' (no per-step cross-shard permutation of
    the stage stack) must be numerically identical to the portable
    chunk-major layout: same logits, same loss, and gradients that map
    onto each other under the model's layout conversion."""
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    kwargs = dict(
        vocab_size=64,
        num_layers=8,
        num_stages=4,
        num_heads=2,
        embed_dim=16,
        num_microbatches=2,
        attention_impl="xla",
        mesh=mesh,
        num_chunks=2,
    )
    chunk_model = pipeline_transformer.PipelinedTransformerLM(**kwargs)
    dev_model = pipeline_transformer.PipelinedTransformerLM(
        device_major_params=True, **kwargs
    )
    batch = _lm_batch()
    tokens = batch["features"]
    v_chunk = chunk_model.init(jax.random.PRNGKey(0), tokens)
    v_dev = dev_model.init(jax.random.PRNGKey(0), tokens)

    # same seed: the device-major stack is exactly the portable stack
    # under the model's layout conversion
    for a, b in zip(
        jax.tree_util.tree_leaves(
            dev_model.blocks_to_portable(v_dev["params"]["blocks_device_major"])
        ),
        jax.tree_util.tree_leaves(v_chunk["params"]["blocks"]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def loss_fn(model):
        def fn(variables):
            logits = model.apply(variables, tokens, training=False)
            return jnp.mean(
                transformer.loss(tokens, logits).astype(jnp.float32)
            )
        return fn

    l_chunk, g_chunk = jax.value_and_grad(loss_fn(chunk_model))(v_chunk)
    l_dev, g_dev = jax.value_and_grad(loss_fn(dev_model))(v_dev)
    assert np.isclose(float(l_chunk), float(l_dev), rtol=1e-6)
    g_dev_portable = dict(g_dev["params"])
    g_dev_portable["blocks"] = dev_model.blocks_to_portable(
        g_dev_portable.pop("blocks_device_major")
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(g_dev_portable),
        jax.tree_util.tree_leaves(g_chunk["params"]),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        )


def test_device_major_requires_interleaving():
    with pytest.raises(ValueError, match="device_major_params"):
        pipeline_transformer.PipelinedTransformerLM(
            num_layers=8, num_stages=4, num_chunks=1,
            device_major_params=True,
            mesh=build_mesh(MeshConfig(dp=2, pp=4)),
        )
