"""The Laguna-XS.2 configuration's reference check over seeds and under
what it has to refuse, on one chip (~1.5 min a run once compiled):

    chiprun --chips 1 --timeout 2400 -- python scripts/laguna_precision.py \\
        --seeds 2 --variants stated,mantissa5,band_ignored

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
- ``band_ignored``: the window layers see the whole causal prefix (a
  window longer than the sequence);
- ``band_off_by_block``: the band one block of 1024 wider (a window of
  ``sliding_window`` + 1024);
- ``no_yarn_amplitude``: the full layers' cos and sin not multiplied by
  ``attention_factor``;
- ``no_yarn_blend``: the full layers' frequencies plain
  ``theta^(-2i/64)`` (the amplitude kept);
- ``whole_head_rotated``: the full layers rotate all 128 lanes;
- ``heads48_in_window``: the window layers built with the full layers'
  48 query heads; the reference refuses the tree (a ValueError is the
  failure).

Prints one JSON line a run (every name's error beside its tolerance,
the held pairs of the layer with the most) and leaves all of them in
``chiprun_out/laguna_precision.json``.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/laguna-xs.2-1chip/config.json"
CELL = "benchmark/workloads/laguna-xs2-s32k.json"
TRAFFIC = "benchmark/traffic/s32k-b1.json"
ROUNDED = {"float8_weights": (4, 3), "mantissa5": (8, 5)}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def wrong_model(model, variant, config):
    """The zoo's ``model`` built wrong as ``variant`` says."""
    if variant == "stated":
        return model
    if variant in ROUNDED:
        # the zoo's model with every parameter rounded on the way in
        from scripts.xing_precision import Rounded

        return Rounded(model, *ROUNDED[variant])
    kinds = dict(model.kind_fields)
    full, window = kinds["full"], kinds["window"]
    scaling = full.rope_scaling
    change = dataclasses.replace
    if variant == "band_ignored":
        kinds["window"] = change(window, window=2 ** 30)
    elif variant == "band_off_by_block":
        kinds["window"] = change(window, window=window.window + 1024)
    elif variant == "no_yarn_amplitude":
        kinds["full"] = change(
            full, rope_scaling=change(scaling, mscale=0.0))
    elif variant == "no_yarn_blend":
        # an original context so long that no pair is interpolated
        kinds["full"] = change(full, rope_scaling=change(
            scaling, original_max_position_embeddings=2 ** 40))
    elif variant == "whole_head_rotated":
        kinds["full"] = change(full, rotary_dim=None)
    elif variant == "heads48_in_window":
        kinds["window"] = change(window, num_heads=full.num_heads)
    else:
        raise ValueError("unknown variant %r" % (variant,))
    return model.clone(kind_fields=kinds)


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
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
    # to the host: the reference needs the room at 32k
    got = jax.device_get(jax.jit(parts["system"])(variables, sample))
    result = {"seed": seed, "variant": variant,
              "held_pairs": float(variables["system_run"]["held_pairs"])}
    try:
        want = jax.block_until_ready(
            jax.jit(parts["reference"])(variables, sample))
    except ValueError as e:
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
        "--variants", default="stated,mantissa5,band_ignored")
    args = parser.parse_args(argv)
    runs = []
    for variant in filter(None, args.variants.split(",")):
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            runs.append(one_run(seed, variant))
            print(json.dumps(runs[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "laguna_precision.json"), "w") as f:
        json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
