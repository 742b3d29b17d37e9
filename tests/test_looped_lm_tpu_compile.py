"""The Ouro cell's train step compiled for a v5e that is described, not
attached (the TPU compiler is installed here), as ``JaxTrainer`` jits it:
published widths (d 2048, 16 heads of 128, SwiGLU 5632, the vocabulary
of 49,152 whole), 16,384 positions, ``flash`` remat, four passes -- over
ONE layer, for the tests' clock: the whole depth compiles in two
minutes and more (``python scripts/step_fingerprint.py --config
benchmark/configs/ouro-2.6b-1chip/config.json --batch 1 --seq 16384
--remat flash --as-tpu --peak-live`` takes the depth rule's readings
that way before a chip run: PERF.md Section 4 has them). What a depth
multiplies is asserted a layer here: ONE pass in the program (a flash
kernel a layer forward and backward, inside the loops over the passes),
ONE parameter tree, no buffer of 16,384 x 49,152
elements, nothing of a sublayer's float32 output alive at the end of the
forward pass, and a peak that leaves the cell's depth its room.

One file, one fixture: only the process that runs this file loads the
TPU's library (on-chip-measurement guide, section 2)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import refcheck
from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.train.step_fns import make_train_step
from elasticdl_tpu.train.train_state import abstract_train_state
from tests.kernel_common import (  # noqa: F401 (fixtures)
    chip, mosaic_kernels as kernels, topology)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(
    REPO, "benchmark", "configs", "ouro-2.6b-1chip", "config.json")
SEQ, LAYERS, PASSES = 16384, 1, 4
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return dict(json.load(f), num_hidden_layers=LAYERS)


@pytest.fixture(scope="module")
def compiled(chip, config):
    zoo = refcheck.load_by_path(
        "edlbench_zoo_ouro", os.path.join(REPO, config["zoo"]))
    kept = jax.default_backend
    # the choosers take the branches a chip gets (the Pallas kernels)
    jax.default_backend = lambda: "tpu"
    try:
        model = zoo.model_from_config(
            config, remat_policy="flash", attention_impl="pallas")
        tx = zoo.optimizer()

        def on_chip(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        tokens = on_chip((1, SEQ), jnp.int32)
        state = jax.tree_util.tree_map(
            lambda a: on_chip(a.shape, a.dtype),
            abstract_train_state(model, tx, jax.random.PRNGKey(0), tokens))
        batch = {"features": tokens, "labels": tokens,
                 MASK_KEY: on_chip((1,), jnp.float32)}
        step = make_train_step(
            model, zoo.loss, tx, jnp.bfloat16, health=True,
            guard_nonfinite=True)
        return jax.jit(step, donate_argnums=(0,)).lower(
            state, batch).compile()
    finally:
        jax.default_backend = kept


def test_the_step_compiles_one_pass_that_runs_four_times(compiled):
    """The passes are one scan: the program holds a flash kernel a LAYER
    forward and backward, under ``looped/pass`` inside a loop's body,
    and the loops over the passes have ``PASSES`` trips (28 forwards and
    28 backwards a step in the device trace at the cell's seven
    layers). Since PR 56 the rotation of q and k is a kernel pair too
    (``ops/rotary.py``): forward, in the policy's recompute and
    backward, a layer."""
    text = compiled.as_text()
    names = kernels(text)
    forward = [n for n in names if n.endswith("flash_fwd/pallas_call")]
    backward = [n for n in names if n.endswith("flash_bwd/pallas_call")]
    assert len(forward) == len(backward) == LAYERS, names
    turned = [n for n in names if n.endswith("rotary_fwd/pallas_call")]
    turned_back = [n for n in names if n.endswith("rotary_bwd/pallas_call")]
    assert len(turned) == 2 * LAYERS and len(turned_back) == LAYERS, names
    assert len(names) == 5 * LAYERS
    assert all(re.search(
        r"\._looped/while/body/.*looped/pass/.*block_\d+/attn/", n)
        for n in names), names
    # the forward's loop over the passes and the backward's
    loops = [line for line in text.splitlines()
             if " while(" in line and "._looped/while" in line]
    assert len(loops) >= 2, loops


def test_one_tree_and_room_for_the_cell_s_depth(compiled):
    memory = device_obs.compiled_memory(compiled)
    parameters = 2 * 49152 * 2048 + 2048 + 2049 + LAYERS * LAYER
    # parameters and AdamW's two moments, float32: ONE tree of LAYERS
    assert memory["arguments"] == pytest.approx(12 * parameters, rel=1e-3)
    # 5.96 GB at one layer and 16.23 at the cell's eight (PR 55): a
    # layer costs ~1.5 GB (0.62 of state, 0.3 of gradients and the
    # compute copy, four applications' saved inputs and flash outputs,
    # 0.54, and what the scheduler keeps beside them), so 5% more here
    # is the cell's depth lost
    assert memory["peak"] < 6.3e9, memory


def test_nothing_as_wide_as_the_vocabulary_is_whole(compiled):
    """No buffer of 16,384 x 49,152 elements (an exit's logits); the
    head runs 512 positions at a time."""
    text = compiled.as_text()
    shapes = set(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text))
    sizes = {}
    for shape in shapes:
        count = 1
        for dim in shape.split(","):
            count *= int(dim)
        sizes[shape] = count
    # the largest things there are: the passes' stacked states and the
    # head's kernel
    assert max(sizes.values()) == PASSES * SEQ * 2048, max(
        sizes, key=sizes.get)
    rows = {sizes[shape] // 49152 for shape in shapes
            if shape.endswith(",49152")}
    assert rows == {512, 2048}, rows


def test_no_sublayer_s_float32_output_outlives_the_forward(compiled):
    """What ``ops/looped_exit.py``'s barrier is for: without it XLA
    fills the exits' copies by running the residual path again from
    every sublayer's float32 output, all kept to the end of the forward
    pass (0.4 GB an application)."""
    memory = device_obs.compiled_memory(compiled)
    live = device_obs.peak_live(compiled.as_text(), memory["peak"])
    assert 0.85 <= live["walk_over_compiler"] <= 1.15
    norms = sum(
        g["bytes"] for g in live["groups"]
        if re.search(r"ln_(attn|mlp)_out$", g["scope"])
        and g["direction"] == "forward")
    # a block's saved input carries the norm's name: 64 MB an
    # application, never 200
    assert norms < PASSES * LAYERS * 100e6, live["groups"]
