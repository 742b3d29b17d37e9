"""The Xing4.0 configuration's reference check over seeds and under
what it has to refuse, on one chip (~1 min a run once compiled):

    chiprun --chips 1 --timeout 1800 -- python scripts/xing_precision.py \\
        --seeds 4 --variants stated,float8_weights,sinkhorn3

Each run is ``benchmark/lib/refcheck.py``'s own (the cell's sequence
from the seed, the zoo's model, ``check.py``'s two sides and
tolerances) with the SYSTEM side changed:

- ``stated``: nothing changed (the cell's own runs read the same);
- ``float8_weights``: the nearest precision below the one the
  configuration states: every parameter rounded to float8 (e4m3, by
  ``jax.lax.reduce_precision``: a convert to float8 and back is removed
  by the compiler as excess precision) after the cast to bfloat16;
- ``mantissa5``: the same with two bits less than bfloat16's mantissa;
- ``sinkhorn3``: three Sinkhorn iterations in the place of twenty;
- ``bfloat16_coefficients``: the Sinkhorn iterations in bfloat16 (a
  patch of ``models/transformer.py:sinkhorn``, which only the XLA lines
  call: since PR 38 a TPU runs the iterations inside ``mhc_pre_fwd`` and
  this variant reads as ``stated`` there).

Prints one JSON line a run (every name's error beside its tolerance)
and leaves all of them in ``chiprun_out/xing_precision.json``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/xing4.0-29b-a4b-1chip/config.json"
CELL = "benchmark/workloads/xing4-29b-s4k.json"
TRAFFIC = "benchmark/traffic/s4k-b1.json"
ROUNDED = {"float8_weights": (4, 3), "mantissa5": (8, 5)}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


class Rounded:
    """The zoo's model with every parameter rounded on the way in."""

    def __init__(self, model, exponent_bits, mantissa_bits):
        self.model = model
        self.bits = (exponent_bits, mantissa_bits)

    def init(self, *args, **kwargs):
        return self.model.init(*args, **kwargs)

    def apply(self, variables, *args, **kwargs):
        import jax

        params = jax.tree_util.tree_map(
            lambda a: jax.lax.reduce_precision(a, *self.bits),
            variables["params"])
        return self.model.apply(
            dict(variables, params=params), *args, **kwargs)


def one_run(seed, variant):
    import jax

    import jax.numpy as jnp

    from benchmark.lib import refcheck
    from elasticdl_tpu.models import transformer as T

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
    model = zoo.model_from_config(config, **spec["cell"]["model_params"])
    if variant in ROUNDED:
        model = Rounded(model, *ROUNDED[variant])
    elif variant == "sinkhorn3":
        model = model.clone(hc=T.HyperDims(
            model.hc.streams, 3, model.hc.eps, model.hc.res_clamp))
    sinkhorn = T.sinkhorn
    if variant == "bfloat16_coefficients":
        T.sinkhorn = lambda matrix, iters, eps: sinkhorn(
            matrix.astype(jnp.bfloat16), iters, eps).astype(jnp.float32)
    try:
        # the system side is traced, and so reads the patch, in here
        parts = check.build(spec, sample, model=model)
        start = time.time()
        variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
        got = jax.device_get(jax.jit(parts["system"])(variables, sample))
    finally:
        T.sinkhorn = sinkhorn
    want = jax.block_until_ready(
        jax.jit(parts["reference"])(variables, sample))
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    return {
        "seed": seed, "variant": variant, "ok": ok,
        "held_pairs": float(variables["system_run"]["held_pairs"]),
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
    parser.add_argument("--first-seed", type=int, default=2147490100)
    parser.add_argument("--variants", default="stated,float8_weights,sinkhorn3")
    args = parser.parse_args(argv)
    runs = []
    for variant in filter(None, args.variants.split(",")):
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            runs.append(one_run(seed, variant))
            print(json.dumps(runs[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "xing_precision.json"), "w") as f:
        json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
