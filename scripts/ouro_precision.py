"""The Ouro configuration's reference check over seeds and under what it
has to refuse, on one chip (~1 min a run once its program is compiled,
~2.5 min with the compile):

    chiprun --chips 1 --timeout 3000 -- python scripts/ouro_precision.py \\
        --seeds 3 --variants stated,float8_weights,mantissa5,\\
three_passes,untied,ln_f_once,no_inner_norms,gated_last_exit

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
- the names of ``check.py:WRONG``: the looped forward put together from
  the model's own modules with one thing wrong (``check.py:Pieces``).

Prints one JSON line a run (every name's error beside its tolerance)
and leaves all of them in ``chiprun_out/ouro_precision.json``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/ouro-2.6b-1chip/config.json"
CELL = "benchmark/workloads/ouro2.6b-s16k.json"
TRAFFIC = "benchmark/traffic/s16k-b1.json"
ROUNDED = {"float8_weights": (4, 3), "mantissa5": (8, 5)}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def wrong_model(model, variant, check):
    """The zoo's ``model`` built wrong as ``variant`` says."""
    if variant == "stated":
        return model
    if variant in ROUNDED:
        from scripts.xing_precision import Rounded

        return Rounded(model, *ROUNDED[variant])
    return check.Pieces(model, variant)


def one_run(seed, variant, config_path=CONFIG, cell_path=CELL,
            traffic_path=TRAFFIC):
    import jax

    from benchmark.lib import refcheck

    config = load(config_path)
    spec = {
        "config": config, "cell": load(cell_path),
        "traffic": load(traffic_path),
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
        variant, check)
    parts = check.build(spec, sample, model=model)
    start = time.time()
    params = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
    got = jax.device_get(jax.jit(parts["system"])(params, sample))
    want = jax.device_get(jax.jit(parts["reference"])(params, sample))
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    return {
        "seed": seed, "variant": variant, "ok": ok,
        "seconds": round(time.time() - start, 1),
        "errors": {
            name: [error, refcheck.tolerance_of(name, parts["tolerance"])]
            for name, error in errors.items()},
        "outside": sorted(
            name for name, error in errors.items()
            if not error <= refcheck.tolerance_of(name, parts["tolerance"])),
        # the scalars, and the one number compared beside a unit
        "scalars": {
            name: [float(got[name].ravel()[0]), float(want[name].ravel()[0])]
            for name in sorted(want) if want[name].size <= 2},
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, default=1)
    parser.add_argument("--first-seed", type=int, default=2147490300)
    parser.add_argument(
        "--variants", default="stated,float8_weights,mantissa5")
    parser.add_argument("--config", default=CONFIG)
    parser.add_argument("--cell", default=CELL)
    parser.add_argument("--traffic", default=TRAFFIC)
    parser.add_argument(
        "--variant-seeds", type=int, default=1,
        help="seeds of every variant but ``stated``")
    args = parser.parse_args(argv)
    runs = []
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    for variant in filter(None, args.variants.split(",")):
        seeds = args.seeds if variant == "stated" else args.variant_seeds
        for seed in range(args.first_seed, args.first_seed + seeds):
            runs.append(one_run(
                seed, variant, args.config, args.cell, args.traffic))
            print(json.dumps(runs[-1]), flush=True)
            with open(os.path.join(out, "ouro_precision.json"), "w") as f:
                json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
