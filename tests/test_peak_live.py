"""``observability/device.py:peak_live`` (ISSUE 47): what a scheduled
program holds in HBM at its fullest point, by the program's own scopes.

``data/peak_live_step.hlo.txt`` is a small module in the TPU compiler's
own print (the lines are those of ``pythia1b-s2k``'s train step
compiled for a described v5e, cut down to one weight and one batch):
``is_scheduled=true``, a donated parameter whose output shares its
buffer, a tuple with an element in fast memory (``S(1)``), two
asynchronous copies (into fast memory and out of it), a ``while``, a
fusion that writes into its operand. The expected peak is worked out by
hand below."""

import os

import pytest

from elasticdl_tpu.observability import device as device_obs

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "peak_live_step.hlo.txt")) as f:
    STEP = f.read()

W = 256 * 256 * 4   # state.params['w'], donated: output 0 is its buffer
X = 64 * 256 * 4    # batch['x'], and every activation's size


def test_the_peak_worked_out_by_hand():
    """Temporaries by schedule position (parameters W + X throughout):

     4  fusion.1        h0 = X born (its second element is in S(1): 0)
     7  fusion.2        h0 + h1                             = 2X
    10  while.1         its state IS tuple.1's buffers: nothing born
    13  copy-start.2    a copy out of fast memory is born at its start
                        h0 + h1 + copy                      = 3X
    14  fusion.8        + the recomputed block_0            = 4X
    15  copy-done.2     h1 died after 14                    = 3X
    16  fusion.3        + d(block_1)                        = 4X
    17  fusion.4        h0, copy, recompute died; d(block_1) + dW
                                                            = X + W
    18  fusion.9        writes into dW (aliasing_operands)  = W
    19  fusion.5        the new w is the donated w: nothing = W
    20  fusion.6        + the norm, 4 bytes
    """
    live = device_obs.peak_live(STEP, compiler_peak=700_000)
    assert live["walk_peak"] == (W + X) + (X + W) == 655_360
    assert live["position"] == 17 and live["instructions"] == 22
    assert live["instruction"] == "fusion.4"
    assert live["op_name"] == (
        "jit(step)/transpose(jvp(forward))/Model/block_0/mlp/dot_general")
    assert live["walk_over_compiler"] == round(655_360 / 700_000, 4)
    assert live["groups"] == [
        # both blocks' gradients fold into one scope: block_*
        {"scope": "forward/Model/block_*/mlp", "direction": "backward",
         "bytes": X + W, "buffers": 2},
        {"scope": "state.params", "direction": "argument", "bytes": W,
         "buffers": 1},
        {"scope": "batch", "direction": "argument", "bytes": X,
         "buffers": 1},
    ]
    # the loop's body holds 2X + X beside its parameter; not counted
    assert live["bodies_not_counted"] == {
        "instructions": 1, "largest_body_peak": 3 * X}
    assert device_obs.peak_live(STEP)["walk_over_compiler"] is None


@pytest.mark.parametrize("edit, expected, position", [
    # without the donation the new w is a buffer of its own from 19,
    # beside dW and the norm's 4 bytes
    (("input_output_alias={ {0}: (0, {}, may-alias) }, ", ""),
     (W + X) + 2 * W + 4, 20),
    # without the fusion's aliasing its result is one too, at 18
    (('{"lists":[{"indices":["0","1"]}]}', '{"lists":[]}'),
     (W + X) + 2 * W, 18),
    # in HBM, block_1's activation would live from 12 until its copy
    # is done: 5X at 14, which is X + W, reached earlier
    (("%fusion.7 = f32[64,256]{1,0:T(8,128)S(1)}",
      "%fusion.7 = f32[64,256]{1,0:T(8,128)}"), (W + X) + 5 * X, 14),
])
def test_what_each_rule_is_worth(edit, expected, position):
    assert edit[0] in STEP
    live = device_obs.peak_live(STEP.replace(*edit))
    assert live["walk_peak"] == expected
    assert live["position"] == position


def test_a_module_that_is_not_scheduled_gives_none():
    assert device_obs.peak_live(
        STEP.replace("is_scheduled=true, ", "")) is None
    assert device_obs.peak_live("HloModule m, is_scheduled=true\n") is None
    assert device_obs.peak_live("") is None


def test_the_groups_are_bounded_and_name_the_rest():
    """Fourteen scopes at the peak: eleven groups, ``other`` for the
    smallest three, and ``unnamed`` for a buffer without an
    ``op_name`` whose operands have none either."""
    lines = [
        "HloModule jit_f, is_scheduled=true", "",
        "ENTRY %main.1 (x.1: f32[8,128]) -> f32[8,128] {",
        "  %x.1 = f32[8,128]{1,0:T(8,128)} parameter(0)",
    ]
    for i in range(14):
        lines.append(
            "  %%fusion.%d = f32[%d,128]{1,0:T(8,128)} fusion(%%x.1), "
            "kind=kLoop, calls=%%fused_computation.%d, metadata={op_name="
            "\"jit(f)/scope%c/mul\"}" % (i, 8 * (i + 1), i, 97 + i))
    lines.append(
        "  %iota.1 = f32[800,128]{1,0:T(8,128)} iota(), iota_dimension=0")
    lines.append(
        "  ROOT %%fusion.99 = f32[8,128]{1,0:T(8,128)} fusion(%s, %%iota.1)"
        ", kind=kLoop, calls=%%fused_computation.99" % ", ".join(
            "%%fusion.%d" % i for i in range(14)))
    lines.append("}")
    live = device_obs.peak_live("\n".join(lines) + "\n")
    groups = live["groups"]
    assert len(groups) == device_obs.PEAK_GROUPS_MAX
    assert [g["scope"] for g in groups[:3]] == ["scopen", "scopem", "scopel"]
    # scopes a to d, the smallest four
    assert groups[-2] == {
        "scope": "other", "direction": "", "buffers": 4,
        "bytes": (8 + 16 + 24 + 32) * 128 * 4}
    assert groups[-1]["scope"] == "unnamed"
    # the iota, the root's result and the parameter: only a copy takes
    # its operand's name
    assert groups[-1]["buffers"] == 3
    assert sum(g["bytes"] for g in groups) == live["walk_peak"]
    text = device_obs.peak_live_text(live)
    assert text.startswith("live at the peak (fusion.99): scopen 0.00 GB x1")
    assert text.count(" GB x") == 4


@pytest.mark.parametrize("op_name, expected", [
    ("jit(train_step)/jvp(forward)/TransformerLM/block_3/mlp_up/"
     "dot_general", ("forward/TransformerLM/block_*/mlp_up", "forward")),
    ("jit(train_step)/transpose(jvp(forward))/TransformerLM/block_0/attn/"
     "out_proj/dot_general",
     ("forward/TransformerLM/block_*/attn/out_proj", "backward")),
    # a backward that recomputes names the forward's scopes twice
    ("jit(train_step)/transpose(jvp(forward))/TransformerLM/jvp(forward)/"
     "TransformerLM/checkpoint/block_7/attn/value/dot_general",
     ("forward/TransformerLM/block_*/attn/value", "recompute")),
    ("jit(train_step)/jvp(forward)/M/rematted_computation/block_1/mul",
     ("forward/M/block_*", "recompute")),
    ("jit(train_step)/jvp(forward)/TransformerLM/wte/jit(_take)/gather",
     ("forward/TransformerLM/wte", "forward")),
    ("jit(train_step)/optimizer/add", ("optimizer", "forward")),
    ("jit(train_step)/transpose(jvp(loss))/mul", ("loss", "backward")),
    ("jit(train_step)/jvp()/max", ("unscoped", "forward")),
])
def test_scopes_are_cut_to_the_program_s_names(op_name, expected):
    assert device_obs.op_scope(op_name) == expected


@pytest.mark.parametrize("shape, expected", [
    (("f32", "2048,8,256", "{2,1,0:T(8,128)}"), 2048 * 8 * 256 * 4),
    (("bf16", "4,2048,2048", "{1,2,0:T(8,128)(2,1)}"), 4 * 2048 * 2048 * 2),
    # the tile pads: 100 -> 128 lanes, 4 -> 8 sublanes
    (("f32", "4,100", "{1,0:T(8,128)}"), 8 * 128 * 4),
    (("f32", "4,2048", "{1,0:T(4,128)}"), 4 * 2048 * 4),
    (("s32", "", "{:T(128)}"), 4),
    (("pred", "16", "{0:T(1024)}"), 1024),
    # another memory space than HBM
    (("bf16", "4,2048,2048", "{1,2,0:T(8,128)(2,1)S(1)}"), 0),
    (("u32", "", "{:S(2)}"), 0),
    (("f32", "8,8", ""), 256),
    (("token", "", ""), 0),
])
def test_bytes_of_one_array(shape, expected):
    assert device_obs._array_bytes(*shape) == expected
