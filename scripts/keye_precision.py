"""The Keye-VL-2.0 configuration's reference check over seeds and under
what it has to refuse, on one chip (~2.5 min a run once compiled):

    chiprun --chips 1 --timeout 3000 -- python scripts/keye_precision.py \\
        --seeds 1 --variants stated,float8_weights,mantissa5,\\
bfloat16_softmax,causal_mask,topk_1024

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
- ``bfloat16_softmax``: the attention's masked scores rounded to
  bfloat16 before the float32 softmax, in ``flash_sparse_fwd`` and
  ``flash_sparse_bwd`` (``ops/sparse_attention.py:_masked``);
- ``bfloat16_scores``: the indexer's scores ``I`` rounded to bfloat16
  wherever a kernel forms them (``_score_tile``), before the selection;
- ``causal_mask``: a causal mask in the selection's place: ``topk`` one
  short of the sequence, so a query keeps its whole prefix;
- ``topk_1024``: half the published ``topk``.

Prints one JSON line a run (every name's error beside its tolerance,
the held pairs of the layer with the most) and leaves all of them in
``chiprun_out/keye_precision.json``.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/keye-vl-2.0-30b-a3b-1chip/config.json"
CELL = "benchmark/workloads/keye-vl2-30b-s32k.json"
TRAFFIC = "benchmark/traffic/s32k-b1.json"
ROUNDED = {"float8_weights": (4, 3), "mantissa5": (8, 5)}
# the function of ops/sparse_attention.py whose result a variant rounds
PATCHED = {"bfloat16_softmax": "_masked", "bfloat16_scores": "_score_tile"}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


class Patched:
    """The zoo's model with one function of ``ops/sparse_attention.py``
    rounding its result to bfloat16 while the model is applied."""

    def __init__(self, model, name):
        self.model, self.name = model, name

    def init(self, *args, **kwargs):
        return self.model.init(*args, **kwargs)

    def apply(self, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.ops import sparse_attention

        kept = getattr(sparse_attention, self.name)

        def rounded(*a, **kw):
            # inside a kernel: Mosaic has no reduce_precision, and a
            # convert to bfloat16 and back read as no change on the
            # chip (PR 51), so the rounding is integer arithmetic on the
            # bits: to the nearest even of the upper 16
            bits = jax.lax.bitcast_convert_type(kept(*a, **kw), jnp.int32)
            bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & jnp.int32(
                -0x10000)
            return jax.lax.bitcast_convert_type(bits, jnp.float32)

        setattr(sparse_attention, self.name, rounded)
        try:
            return self.model.apply(*args, **kwargs)
        finally:
            setattr(sparse_attention, self.name, kept)


def wrong_model(model, variant, seq):
    """The zoo's ``model`` built wrong as ``variant`` says."""
    if variant == "stated":
        return model
    if variant in ROUNDED:
        from scripts.xing_precision import Rounded

        return Rounded(model, *ROUNDED[variant])
    if variant in PATCHED:
        return Patched(model, PATCHED[variant])
    if variant == "causal_mask":
        return model.clone(
            indexer=dataclasses.replace(model.indexer, topk=seq - 1))
    if variant == "topk_1024":
        return model.clone(
            indexer=dataclasses.replace(model.indexer, topk=1024))
    raise ValueError("unknown variant %r" % (variant,))


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
        variant, sample.shape[0])
    parts = check.build(spec, sample, model=model)
    start = time.time()
    variables = jax.jit(parts["init"])(jax.random.PRNGKey(seed), sample)
    # to the host: the reference needs the room at 32k
    got = jax.device_get(jax.jit(parts["system"])(variables, sample))
    result = {"seed": seed, "variant": variant,
              "held_pairs": float(variables["system_run"]["held_pairs"])}
    want = jax.block_until_ready(
        jax.jit(parts["reference"])(variables, sample))
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
    parser.add_argument("--config", default=CONFIG)
    parser.add_argument("--cell", default=CELL)
    parser.add_argument("--traffic", default=TRAFFIC)
    args = parser.parse_args(argv)
    runs = []
    for variant in filter(None, args.variants.split(",")):
        for seed in range(args.first_seed, args.first_seed + args.seeds):
            runs.append(one_run(
                seed, variant, args.config, args.cell, args.traffic))
            print(json.dumps(runs[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "keye_precision.json"), "w") as f:
        json.dump(runs, f, indent=1)


if __name__ == "__main__":
    main()
