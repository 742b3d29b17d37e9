"""The granite-4.0-h-micro configuration's reference check over seeds
and under what it has to refuse, on one chip (~2.5 min a variant with
its compile, ~1.5 min a further seed: the reference runs the selective
scan one token a step):

    chiprun --chips 1 --timeout 3000 -- python scripts/granite_precision.py \\
        --seeds 2 --variants stated,decay_bfloat16,state_bfloat16

Each run is ``benchmark/lib/refcheck.py``'s own (the cell's sequence
from the seed, the zoo's model, ``check.py``'s two sides and
tolerances) with one side changed:

- ``stated``: nothing changed (the cell's own runs read the same);
- ``decay_bfloat16``: the system's scan cumulates its log decay ``G`` in
  bfloat16 (``ssd_scan(decay_dtype=)``), the nearest precision below
  the float32 the configuration states;
- ``state_bfloat16``: the system's scan carries its state in bfloat16
  (``state_dtype=``);
- ``gate_after_norm``: the REFERENCE applies ``silu(z)`` after the norm
  (Gated DeltaNet's order) where the model's gate multiplies before it;
- ``norm_a_head``: the REFERENCE's gated norm runs over a head's 64
  lanes where the model's runs over all 4096;
- ``scale_sqrt``: the system's softmax scale is ``1 / 8`` (``head width
  ** -0.5``, every older model's) in place of ``attention_multiplier``
  1 / 64;
- ``rotated``: the system's attention layer rotates q and k at
  ``rope_theta`` (every older model's);
- ``no_residual_multiplier``: the system's blocks add their branches
  unscaled;
- ``no_conv_bias``: the system's convolution has no bias;
- ``no_skip``: the system's scan is handed ``D = 0``.

Prints one JSON line a run (every name's error beside its tolerance)
and leaves all of them in ``chiprun_out/granite_precision.json``.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/granite-4.0-h-micro-1chip/config.json"
CELL = "benchmark/workloads/granite4h-micro-s8k.json"
TRAFFIC = "benchmark/traffic/s8k-b1.json"
VARIANTS = (
    "stated", "decay_bfloat16", "state_bfloat16", "gate_after_norm",
    "norm_a_head", "scale_sqrt", "rotated", "no_residual_multiplier",
    "no_conv_bias", "no_skip")


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


class StandIn:
    """``init`` by the zoo's model (whose tree the check's leaves name),
    ``apply`` by the callable given."""

    def __init__(self, model, apply):
        self.init, self.apply = model.init, apply


def other_scan(model, change):
    """The zoo's model with ``ops/ssd.py:ssd_scan`` wrapped by
    ``change(scan)`` while it is traced."""

    def apply(*args, **kwargs):
        from elasticdl_tpu.ops import ssd

        kept = ssd.ssd_scan
        # the mixer calls the scan through its module
        ssd.ssd_scan = change(kept)
        try:
            return model.apply(*args, **kwargs)
        finally:
            ssd.ssd_scan = kept

    return StandIn(model, apply)


def wrong_sides(model, variant, config):
    """(the system's model, the reference's variants) as ``variant``
    says."""
    import jax.numpy as jnp

    if variant == "stated":
        return model, None
    if variant in ("decay_bfloat16", "state_bfloat16"):
        lowered = {variant.replace("bfloat16", "dtype"): jnp.bfloat16}
        return other_scan(model, lambda scan: (
            lambda *a, **kw: scan(*a, **lowered, **kw))), None
    if variant == "no_skip":
        return other_scan(model, lambda scan: (
            lambda x, dt, a, b, c, skip, **kw: scan(
                x, dt, a, b, c, jnp.zeros_like(skip), **kw))), None
    if variant == "gate_after_norm":
        return model, {"mamba": {"gate_after_norm": True}}
    if variant == "norm_a_head":
        return model, {"mamba": {"norm_lanes": config["mamba_d_head"]}}
    if variant == "scale_sqrt":
        width = config["hidden_size"] // config["num_attention_heads"]
        return model.clone(attention_scale=width ** -0.5), None
    if variant == "rotated":
        return model.clone(rotary=True), None
    if variant == "no_residual_multiplier":
        return model.clone(residual_scale=None), None
    if variant == "no_conv_bias":
        return StandIn(model, model.clone(mamba=dataclasses.replace(
            model.mamba, conv_bias=False)).apply), None
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
        variant, config)
    parts = check.build(spec, sample, model=model, variants=variants)
    start = time.time()
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
    # to the host: the reference has the room then
    got = jax.device_get(jax.jit(parts["system"])(variables, sample))
    want = jax.block_until_ready(
        jax.jit(parts["reference"])(variables, sample))
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
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, default=1)
    parser.add_argument("--first-seed", type=int, default=2147491500)
    parser.add_argument("--variant-seeds", type=int, default=1,
                        help="seeds of every variant but ``stated``")
    parser.add_argument("--variants", default=",".join(VARIANTS))
    parser.add_argument("--out", default="granite_precision.json",
                        help="the runs' file under chiprun_out/")
    args = parser.parse_args(argv)
    runs = []
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    for variant in filter(None, args.variants.split(",")):
        count = args.seeds if variant == "stated" else args.variant_seeds
        for seed in range(args.first_seed, args.first_seed + count):
            runs.append(one_run(seed, variant))
            print(json.dumps(runs[-1]), flush=True)
            with open(os.path.join(out, args.out), "w") as f:
                json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
