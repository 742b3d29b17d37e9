"""The Xing4.0 check's names against faults of the kinds ISSUE 37's
equations rule out, at the preset size of ``test_xing_reference.py``
(whose helpers these are): a Sinkhorn cut short, coefficients in
bfloat16, a softmax scale without ``mscale`` squared, rotary without
YaRN's table, another weight on the second loss. Each is a SYSTEM built
wrong against the true configuration's reference."""

import jax.numpy as jnp
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.models import transformer as T
from tests.benchmark_harness.test_xing_reference import (  # noqa: F401
    build,
    reference,
    run,
    small_config,
    tokens,
    zoo,
)


def _wrong(tokens, reference, patch=lambda m: None, **changes):
    """A system built wrong against the true configuration's
    reference."""
    parts, _, _, _ = reference
    model = zoo().model_from_config(small_config()).clone(**changes)
    with pytest.MonkeyPatch.context() as monkeypatch:
        patch(monkeypatch)
        wrong = build(small_config(), tokens, model=model)
        _, got, want = run(wrong, tokens, reference=parts)
    return refcheck.compare(got, want, parts["tolerance"])


def _bfloat16_coefficients(monkeypatch):
    true = T.sinkhorn
    monkeypatch.setattr(
        T, "sinkhorn", lambda matrix, iters, eps: true(
            matrix.astype(jnp.bfloat16), iters, eps).astype(jnp.float32))


def _yarn(**changes):
    scaling = dict(small_config()["rope_scaling"])
    scaling.pop("type")
    scaling.update(changes)
    return T.YarnScaling(**{k: float(v) if k != (
        "original_max_position_embeddings") else v
        for k, v in scaling.items()})


@pytest.mark.parametrize("patch,changes,name", [
    (None, dict(hc=T.HyperDims(4, sinkhorn_iters=3)), "row_err_plus_one"),
    (_bfloat16_coefficients, {}, "row_err_plus_one"),
    (None, dict(rope_scaling=_yarn(mscale_all_dim=0.0, mscale=0.0)),
     "logits"),
    (None, dict(rope_scaling=_yarn(factor=1.0)), "logits"),
    (None, dict(mtp_loss_weight=0.3), "loss"),
], ids=["sinkhorn-cut-to-3", "bfloat16-coefficients", "no-mscale-squared",
        "plain-rotary", "another-loss-weight"])
def test_a_wrong_step_is_outside_the_tolerances(
        tokens, reference, patch, changes, name):
    errors, ok = _wrong(
        tokens, reference, patch or (lambda m: None), **changes)
    assert not ok
    worst = max(e for n, e in errors.items() if n.split(":")[0] == name)
    assert worst > reference[0]["tolerance"][name], errors
