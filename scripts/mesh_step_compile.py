"""Compile a benchmark configuration's train step for a described
four-chip v5e host on a mesh, as ``SpmdTrainer`` jits it (no chip
needed): what the TPU compiler says of its memory on each device and
which collectives and how many Mosaic kernels the program holds.

    python scripts/mesh_step_compile.py \\
        --config benchmark/configs/mellum2-12b-a2.5b-ep4/config.json \\
        --mesh ep=4 --batch 4 --seq 8192 --remat full

``jax.default_backend()`` answers ``tpu`` while the model is built and
traced, so the choosers take the branches a chip gets (the flash and
grouped-matmul kernels, ``ragged_all_to_all``). Prints one JSON object:
the compiler's count of memory a device (``observability/device.py:
compiled_memory``; ``--peak-live``: what is live at the peak), or its refusal
(``Used 16.68G of 15.75G hbm``), and the counts of the program's
collectives and ``tpu_custom_call``s. PR 45 chose ``mellum2-ep4-s8k``'s
``remat_policy`` by it.
"""

import argparse
import collections
import json
import os
import re
import sys


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=os.getcwd())
    parser.add_argument("--config", required=True,
                        help="config.json, relative to --root")
    parser.add_argument("--mesh", required=True)
    parser.add_argument("--batch", type=int, required=True)
    parser.add_argument("--seq", type=int, required=True)
    parser.add_argument("--remat", default="none")
    parser.add_argument("--hlo-out", default=None)
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
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.lib.refcheck import load_by_path
    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.observability import device as device_obs
    from elasticdl_tpu.parallel.mesh import build_mesh, parse_mesh_spec
    from elasticdl_tpu.parallel.sharding import infer_state_shardings
    from elasticdl_tpu.train.step_fns import make_train_step
    from elasticdl_tpu.train.train_state import abstract_train_state

    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    mesh_config = parse_mesh_spec(args.mesh)
    mesh_config.devices = list(topo.devices)
    mesh = build_mesh(mesh_config)
    with open(os.path.join(root, args.config)) as f:
        config = json.load(f)
    os.environ["EDLBENCH_CONFIG"] = os.path.join(root, args.config)
    zoo = load_by_path("edlbench_zoo", os.path.join(root, config["zoo"]))
    model = zoo.model_from_config(
        config, mesh=mesh, remat_policy=args.remat)
    tx = zoo.optimizer()
    spec = P(*tuple(zoo.batch_spec()))
    tokens = jax.ShapeDtypeStruct(
        (args.batch, args.seq), jnp.int32,
        sharding=NamedSharding(mesh, spec))
    abstract = abstract_train_state(
        model, tx, jax.random.PRNGKey(0), tokens)
    shardings = infer_state_shardings(abstract, mesh, zoo.sharding_rules())
    state = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jax.ShapeDtypeStruct(
                 (args.batch,), jnp.float32,
                 sharding=NamedSharding(mesh, P(spec[0])))}
    step = make_train_step(
        model, zoo.loss, tx, jnp.bfloat16, with_facts=True)
    whole = NamedSharding(mesh, P())
    lowered = jax.jit(
        step, donate_argnums=(0,),
        out_shardings=(shardings, whole, whole)).lower(state, batch)
    out = {"mesh": args.mesh, "remat": args.remat}
    try:
        compiled = lowered.compile()
    except Exception as e:  # the compiler's refusal is the answer
        found = re.search(r"Used [\d.]+\w of [\d.]+\w hbm", str(e))
        out["refused"] = found.group(0) if found else str(e)[-2000:]
        print(json.dumps(out, indent=1))
        return 1
    text = compiled.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(text)
    memory = device_obs.compiled_memory(compiled)
    ops = collections.Counter(re.findall(
        r" (all-gather|all-reduce|reduce-scatter|ragged-all-to-all|"
        r"all-to-all|collective-permute)(?:-start)?\(", text))
    out.update(
        memory=memory, collectives=dict(ops),
        tpu_custom_calls=text.count("tpu_custom_call"))
    if args.peak_live:
        out["peak_live"] = device_obs.peak_live(text, memory["peak"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
