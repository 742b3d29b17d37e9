"""The Kimi Linear configuration's reference check over seeds and under
what it has to refuse, on one chip (~3 min a variant with its compile,
~1 min a further seed: the reference runs the delta rule one token a
step, 76 s):

    chiprun --chips 1 --timeout 3000 -- python scripts/kimi_precision.py \\
        --seeds 2 --variants stated,decay_bfloat16,state_bfloat16

Each run is ``benchmark/lib/refcheck.py``'s own (the cell's sequence
from the seed, the zoo's model, ``check.py``'s two sides and
tolerances) with one side changed:

- ``stated``: nothing changed (the cell's own runs read the same);
- ``decay_bfloat16``: the system's rule cumulates its decay in bfloat16
  (``gated_delta_rule(decay_dtype=)``), the nearest precision below the
  float32 the configuration states;
- ``state_bfloat16``: the system's rule carries its state in bfloat16
  (``state_dtype=``); in the cell it reads as ``stated`` does and
  passes (``check.py``'s comments say why);
- ``scalar_decay``: the system's rule is handed the MEAN of a head's
  log decays over its channels: the scalar rule, which the four
  ``gdn_*`` kernels compute and a vector decay must never reach;
- ``rotated``: the system's latent layer rotates its rope lanes at
  ``rope_theta`` (``LatentDims.rotary`` true, every older model's);
- ``silu_gate``: the REFERENCE's output gate is ``silu`` (Qwen3-Next's)
  where the model's is a sigmoid: the system as it is against a
  reference built wrong (the mixer has no such switch to flip).

Prints one JSON line a run (every name's error beside its tolerance,
the held pairs of the layer with the most) and leaves all of them in
``chiprun_out/kimi_precision.json``.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/kimi-linear-48b-a3b-1chip/config.json"
CELL = "benchmark/workloads/kimi-linear48b-s32k.json"
TRAFFIC = "benchmark/traffic/s32k-b1.json"


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


class OtherRule:
    """The zoo's model with ``ops/gated_delta.py:gated_delta_rule``
    wrapped by ``change(rule)`` while it is traced."""

    def __init__(self, model, change):
        self.model, self.change = model, change

    def init(self, *args, **kwargs):
        return self.model.init(*args, **kwargs)

    def apply(self, *args, **kwargs):
        from elasticdl_tpu.ops import gated_delta

        kept = gated_delta.gated_delta_rule
        # the mixer calls the rule through its module
        gated_delta.gated_delta_rule = self.change(kept)
        try:
            return self.model.apply(*args, **kwargs)
        finally:
            gated_delta.gated_delta_rule = kept


def wrong_sides(model, variant):
    """(the system's model, the reference's mixer keywords) as
    ``variant`` says."""
    import jax
    import jax.numpy as jnp

    if variant == "stated":
        return model, None
    if variant in ("decay_bfloat16", "state_bfloat16"):
        lowered = {variant.replace("bfloat16", "dtype"): jnp.bfloat16}
        return OtherRule(model, lambda rule: (
            lambda *a, **kw: rule(*a, **lowered, **kw))), None
    if variant == "scalar_decay":
        return OtherRule(model, lambda rule: (
            lambda q, k, v, g, beta, **kw: rule(
                q, k, v, g.mean(axis=-1), beta, **kw))), None
    if variant == "rotated":
        return model.clone(latent=dataclasses.replace(
            model.latent, rotary=True)), None
    if variant == "silu_gate":
        return model, {"kda": {"gate": jax.nn.silu}}
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
    model, variants = wrong_sides(
        zoo.model_from_config(config, **spec["cell"]["model_params"]),
        variant)
    parts = check.build(spec, sample, model=model, variants=variants)
    start = time.time()
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
    # to the host: the reference needs the room at 32k
    got = jax.device_get(jax.jit(parts["system"])(variables, sample))
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
    parser.add_argument("--first-seed", type=int, default=2147491300)
    parser.add_argument(
        "--variants", default="stated,decay_bfloat16,state_bfloat16,"
        "scalar_decay,rotated,silu_gate")
    args = parser.parse_args(argv)
    runs = []
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    for variant in filter(None, args.variants.split(",")):
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            runs.append(one_run(seed, variant))
            print(json.dumps(runs[-1]), flush=True)
            with open(os.path.join(out, "kimi_precision.json"), "w") as f:
                json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
