"""The LFM2-8B-A1B configuration's reference check over seeds and under
what it has to refuse, on one chip (~1.5 min a run once compiled):

    chiprun --chips 1 --timeout 2400 -- python scripts/lfm2_precision.py \\
        --seeds 2 --variants stated,float8_weights,mantissa5

Each run is ``benchmark/lib/refcheck.py``'s own (the cell's sequence
from the seed, the zoo's model, ``check.py``'s two sides and
tolerances) with the SYSTEM side changed:

- ``stated``: nothing changed (the cell's own runs read the same);
- ``float8_weights``: the nearest format below the one the
  configuration states: every parameter rounded to float8 (e4m3, by
  ``jax.lax.reduce_precision``: a convert to float8 and back is removed
  by the compiler as excess precision) after the cast to bfloat16;
- ``mantissa5``: the same with two bits less than bfloat16's mantissa,
  a finer probe than any format;
- ``gate_bfloat16``: the short convolution's gates and taps multiplied
  in bfloat16, each product rounded (``ops/short_conv.py`` states
  float32 arithmetic and one rounding);
- ``rope_theta_10000``: the attention layers rotate at 10,000 and not at
  ``rope_theta`` 1,000,000;
- ``no_head_norm``: no RMSNorm over the lanes of the q and k heads; the
  tree then lacks ``q_norm`` / ``k_norm`` and the check's leaf is
  missing (a KeyError is the failure);
- ``taps2``: a convolution of 2 taps; the reference refuses the tree (a
  ValueError is the failure).

Prints one JSON line a run (every name's error beside its tolerance,
the held pairs of the layer with the most) and leaves all of them in
``chiprun_out/lfm2_precision.json``.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/lfm2-8b-a1b-1chip/config.json"
CELL = "benchmark/workloads/lfm2-8b-s32k.json"
TRAFFIC = "benchmark/traffic/s32k-b1.json"
ROUNDED = {"float8_weights": (4, 3), "mantissa5": (8, 5)}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


class GateInBfloat16:
    """The zoo's model with the short convolution's arithmetic in the
    operands' own dtype, every product rounded."""

    def __init__(self, model):
        self.model = model

    def init(self, *args, **kwargs):
        return self.model.init(*args, **kwargs)

    def apply(self, *args, **kwargs):
        from elasticdl_tpu.ops import short_conv

        def narrow(bcx, taps):
            channels = taps.shape[1]
            b, c, x = (bcx[..., i * channels:(i + 1) * channels]
                       for i in range(3))
            return c * short_conv.causal_depthwise_conv(b * x, taps)

        kept = short_conv.gated_short_conv
        # the mixer calls the op through its module
        short_conv.gated_short_conv = narrow
        try:
            return self.model.apply(*args, **kwargs)
        finally:
            short_conv.gated_short_conv = kept


def wrong_model(model, variant, config):
    """The zoo's ``model`` built wrong as ``variant`` says."""
    if variant == "stated":
        return model
    if variant in ROUNDED:
        # the zoo's model with every parameter rounded on the way in
        from scripts.xing_precision import Rounded

        return Rounded(model, *ROUNDED[variant])
    if variant == "gate_bfloat16":
        return GateInBfloat16(model)
    if variant == "rope_theta_10000":
        return model.clone(rope_theta=10000.0)
    if variant == "no_head_norm":
        return model.clone(head_norm=None)
    if variant == "taps2":
        return model.clone(conv=dataclasses.replace(model.conv, taps=2))
    raise ValueError("unknown variant %r" % (variant,))


def one_run(seed, variant):
    import jax

    from benchmark.lib import refcheck

    config = load(CONFIG)
    spec = {
        "config": config, "cell": load(CELL), "traffic": load(TRAFFIC),
        "seed": seed, "zoo": os.path.join(ROOT, config["zoo"]),
        "check": os.path.join(ROOT, config["check"]),
        "reference": os.path.join(ROOT, config["reference"]),
    }
    generator = refcheck.load_by_path(
        "edlbench_traffic", os.path.join(
            ROOT, "benchmark", "traffic", spec["traffic"]["generator"] + ".py"))
    sample = generator.sample(spec["traffic"], config, seed)
    check = refcheck.load_by_path("edlbench_check", spec["check"])
    zoo = refcheck.load_by_path("edlbench_zoo", spec["zoo"])
    model = wrong_model(
        zoo.model_from_config(config, **spec["cell"]["model_params"]),
        variant, config)
    parts = check.build(spec, sample, model=model)
    start = time.time()
    try:
        variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
    except KeyError as e:
        return {"seed": seed, "variant": variant, "ok": False,
                "refused": "the tree has no %s" % e}
    # to the host: the reference needs the room at 32k
    got = jax.device_get(jax.jit(parts["system"])(variables, sample))
    result = {"seed": seed, "variant": variant,
              "held_pairs": float(variables["system_run"]["held_pairs"])}
    try:
        want = jax.block_until_ready(
            jax.jit(parts["reference"])(variables, sample))
    except (ValueError, KeyError) as e:
        return dict(result, ok=False, refused=str(e)[:300],
                    seconds=round(time.time() - start, 1))
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    return dict(
        result, ok=ok, seconds=round(time.time() - start, 1),
        errors={
            name: [error, refcheck.tolerance_of(name, parts["tolerance"])]
            for name, error in errors.items()},
        outside=sorted(
            name for name, error in errors.items()
            if not error <= refcheck.tolerance_of(name, parts["tolerance"])))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, default=1)
    parser.add_argument("--first-seed", type=int, default=2147490300)
    parser.add_argument(
        "--variants", default="stated,float8_weights,mantissa5")
    args = parser.parse_args(argv)
    runs = []
    for variant in filter(None, args.variants.split(",")):
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            runs.append(one_run(seed, variant))
            print(json.dumps(runs[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "lfm2_precision.json"), "w") as f:
        json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
