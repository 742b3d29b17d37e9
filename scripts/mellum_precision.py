"""The Mellum2 configuration's reference check over seeds and under
what it has to refuse, on a four-chip host (~1.5 min a run once
compiled):

    chiprun --chips 4 --timeout 2400 -- python scripts/mellum_precision.py \\
        --seeds 2 --variants stated,float8_weights,band_ignored

Each run is ``benchmark/lib/refcheck.py``'s own (the cell's sequence
from the seed, the zoo's model over ``--mesh ep=4``, ``check.py``'s two
sides and tolerances) with the SYSTEM side changed:

- ``stated``: nothing changed (the cell's own runs read the same);
- ``float8_weights``: the nearest format below the one the
  configuration states: every parameter rounded to float8 (e4m3, by
  ``jax.lax.reduce_precision``: a convert to float8 and back is removed
  by the compiler as excess precision) after the cast to bfloat16;
- ``mantissa5``: the same with two bits less than bfloat16's mantissa,
  a finer probe than any format;
- ``band_ignored``: the window layers see the whole causal prefix (a
  window longer than the sequence);
- ``no_yarn``: the full layer under the window layers' plain table.

Prints one JSON line a run (every name's error beside its tolerance,
the rows the busiest rank received) and leaves all of them in
``chiprun_out/mellum_precision.json``.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/mellum2-12b-a2.5b-ep4/config.json"
CELL = "benchmark/workloads/mellum2-ep4-s8k.json"
TRAFFIC = "benchmark/traffic/s8k-b4.json"
ROUNDED = {"float8_weights": (4, 3), "mantissa5": (8, 5)}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def wrong_model(model, variant):
    """The zoo's ``model`` built wrong as ``variant`` says."""
    if variant == "stated":
        return model
    if variant in ROUNDED:
        from scripts.xing_precision import Rounded

        return Rounded(model, *ROUNDED[variant])
    kinds = dict(model.kind_fields)
    if variant == "band_ignored":
        kinds["window"] = dataclasses.replace(
            kinds["window"], window=2 ** 30)
    elif variant == "no_yarn":
        kinds["full"] = dataclasses.replace(
            kinds["full"], rope_scaling=None)
    else:
        raise ValueError("unknown variant %r" % (variant,))
    return model.clone(kind_fields=kinds)


def one_run(seed, variant):
    import jax

    from benchmark.lib import refcheck
    from elasticdl_tpu.parallel.mesh import build_mesh, parse_mesh_spec

    config, cell = load(CONFIG), load(CELL)
    spec = {
        "config": config, "cell": cell, "traffic": load(TRAFFIC),
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
    mesh = build_mesh(parse_mesh_spec(cell["mesh"]))
    model = wrong_model(
        zoo.model_from_config(config, mesh=mesh, **cell["model_params"]),
        variant)
    parts = check.build(spec, sample, model=model, mesh=mesh)
    start = time.time()
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
    got = jax.jit(parts["system"])(variables, sample)
    want = jax.block_until_ready(
        jax.jit(parts["reference"])(variables, sample))
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    return {
        "seed": seed, "variant": variant, "ok": ok,
        "received_pairs_max": float(
            variables["system_run"]["received_pairs_max"]),
        "seconds": round(time.time() - start, 1),
        "errors": {
            name: [error, refcheck.tolerance_of(name, parts["tolerance"])]
            for name, error in errors.items()},
        "outside": sorted(
            name for name, error in errors.items()
            if not error <= refcheck.tolerance_of(name, parts["tolerance"])),
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, default=1)
    parser.add_argument("--first-seed", type=int, default=2147490300)
    parser.add_argument(
        "--variants", default="stated,float8_weights,band_ignored")
    args = parser.parse_args(argv)
    runs = []
    for variant in filter(None, args.variants.split(",")):
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            runs.append(one_run(seed, variant))
            print(json.dumps(runs[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "mellum_precision.json"), "w") as f:
        json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
