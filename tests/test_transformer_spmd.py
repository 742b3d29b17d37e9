"""Transformer LM: TP + SP sharded training matches single-device math.

The strongest correctness check for the parallel layer: the same model,
same init, same batch, trained (a) on one device with plain XLA
attention and (b) GSPMD-sharded over a dp x tp x sp mesh with ring (and
ulysses) attention, must produce the same losses.
"""

import functools

import jax
import numpy as np
import pytest

from elasticdl_tpu.models import transformer
from elasticdl_tpu.parallel.mesh import MeshConfig, build_mesh
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
from elasticdl_tpu.train.optimizers import create_optimizer
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import create_train_state


def _small_lm(**kwargs):
    return transformer.TransformerLM(
        vocab_size=128,
        num_layers=2,
        num_heads=4,
        embed_dim=32,
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


@functools.lru_cache(maxsize=None)
def _reference_losses():
    """The single-device losses of ``_batch()``: computed once for the
    parametrised cases that compare against them."""
    return _single_device_losses(_batch())


def _single_device_losses(batch, steps=3):
    model = _small_lm(attention_impl="xla")
    tx = create_optimizer("Adam", learning_rate=0.01)
    # Same key derivation as SpmdTrainer(seed=0).create_state so both
    # paths start from identical parameters.
    init_rng, _ = jax.random.split(jax.random.PRNGKey(0))
    state = create_train_state(model, tx, init_rng, batch["features"])
    step = jax.jit(make_train_step(model, transformer.loss, tx))
    losses = []
    for _ in range(steps):
        state, loss = step(state, batch)
        losses.append(float(loss))
    return losses


def _spmd_losses(batch, axes, impl, steps=3):
    mesh = build_mesh(
        MeshConfig(**axes), num_devices=int(np.prod(list(axes.values())))
    )
    model = _small_lm(attention_impl=impl, mesh=mesh)
    trainer = SpmdTrainer(
        model=model,
        loss_fn=transformer.loss,
        optimizer=create_optimizer("Adam", learning_rate=0.01),
        mesh=mesh,
        seed=0,
        sharding_rules=transformer.sharding_rules(),
        batch_spec=transformer.batch_spec(),
    )
    state = trainer.create_state(batch["features"])
    losses = []
    for _ in range(steps):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_spmd_tp_sp_matches_single_device(impl):
    np.testing.assert_allclose(
        _spmd_losses(_batch(), dict(dp=2, tp=2, sp=2), impl),
        _reference_losses(), atol=1e-4, rtol=1e-4,
    )


@pytest.mark.parametrize(
    "axes",
    [dict(dp=1, fsdp=4), dict(dp=2, fsdp=2), dict(dp=1, fsdp=2, tp=2)],
    ids=["fsdp4", "dp2-fsdp2", "fsdp2-tp2"],
)
def test_spmd_fsdp_matches_single_device(axes):
    """Under an fsdp extent the activations are pinned to the data
    axes and the weights move (ZeRO-3): where an array lives changes,
    the mathematics does not."""
    np.testing.assert_allclose(
        _spmd_losses(_batch(), axes, "xla"),
        _reference_losses(), atol=1e-4, rtol=1e-4,
    )


def test_spmd_fsdp_transformer_runs():
    batch = _batch(batch=8)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=4))
    model = _small_lm(attention_impl="xla", mesh=mesh)
    trainer = SpmdTrainer(
        model=model,
        loss_fn=transformer.loss,
        optimizer=create_optimizer("Adam", learning_rate=0.01),
        mesh=mesh,
        seed=0,
        sharding_rules=transformer.sharding_rules(),
        batch_spec=transformer.batch_spec(),
    )
    state = trainer.create_state(batch["features"])
    state, loss1 = trainer.train_step(state, batch)
    state, loss2 = trainer.train_step(state, batch)
    assert np.isfinite(float(loss1)) and float(loss2) < float(loss1)


def test_model_contract_loads():
    from elasticdl_tpu.models.registry import get_model_spec

    spec = get_model_spec("elasticdl_tpu.models.transformer")
    assert spec.sharding_rules is not None
    assert spec.batch_spec is not None


@pytest.mark.parametrize("remat_policy", ["full", "dots", "flash"])
@pytest.mark.parametrize("attention_impl", ["xla", "pallas"])
def test_remat_policies_match_no_remat(remat_policy, attention_impl,
                                       monkeypatch):
    """Every remat policy must leave loss/gradients identical, including
    over the pallas flash kernel (whose o/lse the "dots" policy saves
    via checkpoint_name — the _attach custom_vjp machinery in
    ops/flash_attention.py). Pallas runs in interpret mode on CPU."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.models import transformer

    if remat_policy == "flash" and attention_impl == "xla":
        pytest.skip(
            'remat_policy="flash" rejects non-pallas attention '
            "(covered by test_remat_policy_validated)"
        )

    if attention_impl == "pallas":
        orig = transformer.dot_product_attention
        monkeypatch.setattr(
            transformer,
            "dot_product_attention",
            functools.partial(orig, interpret=True),
        )

    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, 64, (2, 16)), jnp.int32
    )

    def loss_and_grads(remat):
        model = transformer.TransformerLM(
            vocab_size=64, num_layers=2, num_heads=2, embed_dim=32,
            attention_impl=attention_impl, remat=remat,
            remat_policy=remat_policy,
        )
        variables = model.init(jax.random.PRNGKey(0), tokens)

        def loss_fn(params):
            logits = model.apply({"params": params}, tokens)
            return jnp.mean(
                transformer.loss(tokens, logits).astype(jnp.float32)
            )

        return jax.value_and_grad(loss_fn)(variables["params"])

    v0, g0 = loss_and_grads(False)
    v1, g1 = loss_and_grads(True)
    assert np.isclose(float(v0), float(v1), rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_remat_policy_validated():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest as _pytest

    from elasticdl_tpu.models import transformer

    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (1, 8)), jnp.int32
    )
    model = transformer.TransformerLM(
        vocab_size=64, num_layers=1, num_heads=2, embed_dim=32,
        attention_impl="xla", remat=True, remat_policy="Dots",
    )
    with _pytest.raises(ValueError, match="remat_policy"):
        model.init(jax.random.PRNGKey(0), tokens)

    # "flash" saves the pallas kernel's named outputs; under xla
    # attention the policy would match nothing and silently run as
    # "full" — the model must reject the contradiction loudly
    model = transformer.TransformerLM(
        vocab_size=64, num_layers=1, num_heads=2, embed_dim=32,
        attention_impl="xla", remat=True, remat_policy="flash",
    )
    with _pytest.raises(ValueError, match="flash"):
        model.init(jax.random.PRNGKey(0), tokens)
