"""The Laguna-XS.2 check's names against faults of the kinds ISSUE 42's
equations rule out, at the preset size of ``test_laguna_reference.py``
(whose helpers these are): the band ignored, the band off by one block,
YaRN's amplitude or blend left out, the whole head rotated in a full
layer, the full layers' heads in a window layer, a lower precision.
Each is a SYSTEM built wrong (``scripts/laguna_precision.py:
wrong_model``, what the script runs on the chip) against the true
configuration's reference."""

import importlib.util
import os

import pytest

from benchmark.lib import refcheck
from tests.benchmark_harness.test_laguna_reference import (  # noqa: F401
    REPO,
    build,
    reference,
    run,
    small_config,
    tokens,
    zoo,
)


def script():
    spec = importlib.util.spec_from_file_location(
        "laguna_precision",
        os.path.join(REPO, "scripts", "laguna_precision.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _wrong(tokens, reference, variant, **changes):
    """A system built wrong against the true configuration's
    reference."""
    parts, _, _, _ = reference
    config = small_config(**changes)
    model = script().wrong_model(
        zoo().model_from_config(config), variant, config)
    wrong = build(config, tokens, model=model)
    _, got, want = run(wrong, tokens, reference=parts)
    return refcheck.compare(got, want, parts["tolerance"])


# at this size a block is 16 positions for the script's 1024: the
# script's "+ 1024" leaves no band in 128 tokens, which the first case
# already is, so the off-by-a-block case is run through the config
@pytest.mark.parametrize("variant,name", [
    ("band_ignored", "logits"),
    ("no_yarn_amplitude", "logits"),
    ("no_yarn_blend", "logits"),
    ("whole_head_rotated", "logits"),
    ("float8_weights", "logits"),
])
def test_a_wrong_step_is_outside_the_tolerances(
        tokens, reference, variant, name):
    errors, ok = _wrong(tokens, reference, variant)
    assert not ok
    worst = max(e for n, e in errors.items() if n.split(":")[0] == name)
    assert worst > reference[0]["tolerance"][name], errors
    # the window layers' and the full layers' own gradients see it too
    assert max(e for n, e in errors.items() if n.startswith("grad")) > (
        reference[0]["tolerance"]["grad"]), errors


def test_a_band_off_by_one_block_is_outside(tokens, reference):
    """The system's window one 16-position block wider than the
    config's (the script's ``band_off_by_block`` at the cell's 1024)."""
    parts, _, _, _ = reference
    model = zoo().model_from_config(small_config(sliding_window=24 + 16))
    wrong = build(small_config(), tokens, model=model)
    _, got, want = run(wrong, tokens, reference=parts)
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    assert not ok and errors["logits"] > parts["tolerance"]["logits"]


def test_a_window_off_by_one_key_is_seen(tokens, reference):
    """511 or 513 keys for 512: one key in the window, far inside every
    tolerance at the cell's size; at this size it is one key in 24 and
    the logits read it."""
    parts, _, _, _ = reference
    for window in (23, 25):
        model = zoo().model_from_config(small_config(sliding_window=window))
        _, got, want = run(
            build(small_config(), tokens, model=model), tokens,
            reference=parts)
        errors, _ = refcheck.compare(got, want, parts["tolerance"])
        assert errors["logits"] > 1e-3, errors


def test_the_full_layers_heads_in_a_window_layer_are_refused(
        tokens, reference):
    """48 heads where 64 are due (6 for 8 here): the tree is another
    model's, and the reference says so instead of following it."""
    parts, _, _, _ = reference
    config = small_config()
    model = script().wrong_model(
        zoo().model_from_config(config), "heads48_in_window", config)
    wrong = build(config, tokens, model=model)
    with pytest.raises(ValueError, match="sliding_attention layer: the "
                       "config gives W_qg"):
        run(wrong, tokens, reference=parts)


def test_the_script_knows_its_variants():
    config = small_config()
    model = zoo().model_from_config(config)
    module = script()
    assert module.wrong_model(model, "stated", config) is model
    wide = module.wrong_model(model, "band_off_by_block", config)
    assert wide.kind_fields["window"].window == 24 + 1024
    assert wide.kind_fields["full"] == model.kind_fields["full"]
    with pytest.raises(ValueError, match="unknown variant"):
        module.wrong_model(model, "nothing", config)
