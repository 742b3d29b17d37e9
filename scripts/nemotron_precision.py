"""The Nemotron-3-Nano configuration's reference check over seeds and
under what it has to refuse, on one chip (~2.5 min a variant with its
compile, ~1.5 min a further seed: the reference runs the selective scan
one token a step):

    chiprun --chips 1 --timeout 3000 -- python scripts/nemotron_precision.py \\
        --seeds 2 --variants stated,decay_bfloat16,swiglu_expert

Each run is ``benchmark/lib/refcheck.py``'s own (the cell's sequence
from the seed, the zoo's model, ``check.py``'s two sides and
tolerances) with one side changed:

- ``stated``: nothing changed (the cell's own runs read the same);
- ``decay_bfloat16``: the system's scan cumulates its log decay ``G`` in
  bfloat16 (``ssd_scan(decay_dtype=)``), the nearest precision below
  the float32 the configuration states; ``state_bfloat16``: it carries
  its state in bfloat16;
- the REFERENCE's expert layers (``reference.py:layer`` / ``route``):
  ``swiglu_expert`` (``silu(h) h`` in the place of ``relu(h)^2``, the
  routed experts and the shared one), ``relu_expert`` (a plain ReLU),
  ``no_shared`` / ``shared_twice`` (the shared expert left out or
  counted twice), ``gates_not_renormalised``, ``scale_1`` (the gates
  not scaled by 2.5), ``no_bias_selection`` (the experts chosen by ``s``
  without the balancing bias: ``choices`` tells it);
- the REFERENCE's mixers: ``norm_all_lanes`` (the gated norm over all
  4096 lanes in place of a group's 512), ``gate_after_norm``,
  ``groups_1`` (B and C of one group read by all 64 heads);
- the REFERENCE's attention: ``rotated`` (q and k rotated at
  ``rope_theta``), ``kv_group_8`` (query head h reads key / value head
  ``(h // 8) mod 2`` in place of ``h // 16``).

Prints one JSON line a run (every name's error beside its tolerance,
the held pairs of the busiest layer) and leaves all of them in
``chiprun_out/nemotron_precision.json``.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/nemotron-3-nano-30b-a3b-1chip/config.json"
CELL = "benchmark/workloads/nemotron3-nano-s8k.json"
TRAFFIC = "benchmark/traffic/s8k-b1.json"
# variant -> the reference's layers' keywords (``reference.py:forward``)
REFERENCE_VARIANTS = {
    "swiglu_expert": {"experts": {"act": "swiglu"}},
    "relu_expert": {"experts": {"act": "relu"}},
    "no_shared": {"experts": {"shared": 0}},
    "shared_twice": {"experts": {"shared": 2}},
    "gates_not_renormalised": {"experts": {"renormalise": False}},
    "scale_1": {"experts": {"scale": 1.0}},
    "no_bias_selection": {"experts": {"use_bias": False}},
    "norm_all_lanes": {"mamba": {"norm_lanes": 4096}},
    "gate_after_norm": {"mamba": {"gate_after_norm": True}},
    "groups_1": {"mamba": {"groups": 1}},
    "rotated": {"full": {"rotate": True}},
    "kv_group_8": {"full": {"group": 8}},
}
VARIANTS = ("stated", "decay_bfloat16", "state_bfloat16") + tuple(
    REFERENCE_VARIANTS)


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


def wrong_sides(model, variant):
    """(the system's model, the reference's variants) as ``variant``
    says."""
    import jax.numpy as jnp

    if variant == "stated":
        return model, None
    if variant in ("decay_bfloat16", "state_bfloat16"):
        lowered = {variant.replace("bfloat16", "dtype"): jnp.bfloat16}
        return other_scan(model, lambda scan: (
            lambda *a, **kw: scan(*a, **lowered, **kw))), None
    if variant in REFERENCE_VARIANTS:
        return model, REFERENCE_VARIANTS[variant]
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
    held = float(variables[check.RUN][check.HELD])
    # to the host: the reference has the room then
    got = jax.device_get(jax.jit(parts["system"])(variables, sample))
    want = jax.block_until_ready(
        jax.jit(parts["reference"])(variables, sample))
    errors, ok = refcheck.compare(got, want, parts["tolerance"])
    return {
        "seed": seed, "variant": variant, "ok": ok, "held_pairs": held,
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
    parser.add_argument("--first-seed", type=int, default=2147491640)
    parser.add_argument("--variant-seeds", type=int, default=1,
                        help="seeds of every variant but ``stated``")
    parser.add_argument("--variants", default=",".join(VARIANTS))
    parser.add_argument("--out", default="nemotron_precision.json",
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
