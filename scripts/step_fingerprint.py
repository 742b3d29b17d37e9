"""Fingerprint of a benchmark configuration's train step, to show that
two checkouts compile the same program (no chip needed).

    python scripts/step_fingerprint.py --root <checkout> \\
        --config benchmark/configs/pythia-1b-1chip/config.json \\
        --batch 4 --seq 2048 --remat dots

Builds the zoo's model as the cell does (attention ``pallas``), makes
``train/step_fns.py:make_train_step`` as ``JaxTrainer`` does (bfloat16
compute, health scalars on), lowers it for one described v5e chip and
compiles it with the TPU compiler installed here. Prints the sha256 of
the lowered StableHLO, of the compiled HLO without its ``metadata``,
the compiler's memory (``observability/device.py:compiled_memory``) and
FLOPs, and with ``--peak-live`` what is live at the step's peak. Source locations are cut to the
innermost frame (``jax_traceback_in_locations_limit`` 0): a Mosaic
kernel's serialized body carries its callers' file and line, so an
edit ABOVE a kernel's call site would otherwise change the bytes of a
program that computes the same thing. Run it once with ``--root`` at
each checkout (``git archive <commit> | tar -x -C build/parent``) and
compare the lines (PR 25 did, for ``pythia-1b-1chip`` at ``s2k-b4``).
"""

import argparse
import hashlib
import json
import os
import re
import sys


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=os.getcwd())
    parser.add_argument("--config", required=True,
                        help="config.json, relative to --root")
    parser.add_argument("--batch", type=int, required=True)
    parser.add_argument("--seq", type=int, required=True)
    parser.add_argument("--remat", default="none")
    parser.add_argument(
        "--as-tpu", action="store_true",
        help="answer jax.default_backend() with 'tpu' while the model is "
             "built and traced, so that the choosers take the branches a "
             "chip gets (Pallas kernels); without it the step is the "
             "CPU's choice of paths compiled for the chip")
    parser.add_argument(
        "--hlo-out", default=None,
        help="write the compiled HLO, metadata and all, to this file")
    parser.add_argument(
        "--peak-live", action="store_true",
        help="also print what is live at the step's peak by scope "
             "(observability/device.py:peak_live)")
    args = parser.parse_args(argv)
    root = os.path.abspath(args.root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib.refcheck import load_by_path
    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.observability import device as device_obs
    from elasticdl_tpu.train.step_fns import make_train_step
    from elasticdl_tpu.train.train_state import abstract_train_state

    jax.config.update("jax_traceback_in_locations_limit", 0)
    if args.as_tpu:
        jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(root, args.config)) as f:
        config = json.load(f)
    zoo = load_by_path("edlbench_zoo", os.path.join(root, config["zoo"]))
    model = zoo.model_from_config(
        config, remat_policy=args.remat, attention_impl="pallas")
    tx = zoo.optimizer()

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    tokens = on_chip((args.batch, args.seq), jnp.int32)
    state = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        abstract_train_state(model, tx, jax.random.PRNGKey(0), tokens))
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: on_chip((args.batch,), jnp.float32)}
    step = make_train_step(model, zoo.loss, tx, jnp.bfloat16, health=True)
    lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
    compiled = lowered.compile()
    text = compiled.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(text)
    hlo = re.sub(r", metadata=\{[^}]*\}", "", text)
    memory = device_obs.compiled_memory(compiled)
    out = {
        "stablehlo_sha256": hashlib.sha256(
            lowered.as_text().encode()).hexdigest(),
        "compiled_hlo_sha256": hashlib.sha256(hlo.encode()).hexdigest(),
        "memory": memory,
        "flops": compiled.cost_analysis()["flops"],
    }
    if args.peak_live:
        out["peak_live"] = device_obs.peak_live(text, memory["peak"])
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
