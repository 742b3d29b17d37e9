"""One rank of a two-process CPU world (subprocess side of
tests/test_multihost.py::test_create_state_on_a_mesh_spanning_processes).

Joins ``jax.distributed`` at the given coordinator, builds a
MultiHostSpmdTrainer whose dp axis spans both processes, creates the
state (which logs the placement: every device of the mesh, of which
this process can address only its own) and takes one lockstep step.

Prints ONE JSON line: {"rank": R, "devices": N, "local": N, "loss": F}.
"""

import argparse
import json
import os

# CPU backend, two virtual devices per process: set before any jax import
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--coordinator", required=True)
    parser.add_argument("--rank", type=int, required=True)
    parser.add_argument("--world", type=int, default=2)
    args = parser.parse_args()

    import jax
    import numpy as np

    jax.distributed.initialize(
        args.coordinator, num_processes=args.world, process_id=args.rank,
        initialization_timeout=60,
    )

    from elasticdl_tpu.models import mnist
    from elasticdl_tpu.parallel.mesh import MeshConfig
    from elasticdl_tpu.parallel.multihost_trainer import (
        MultiHostSpmdTrainer,
    )

    trainer = MultiHostSpmdTrainer(
        model=mnist.custom_model(),
        loss_fn=mnist.loss,
        optimizer=mnist.optimizer(),
        seed=0,
        mesh_config=MeshConfig(dp=jax.device_count()),
    )
    rows = 2 * jax.local_device_count()
    rng = np.random.RandomState(args.rank)
    batch = {
        "features": rng.rand(rows, 8, 8).astype(np.float32),
        "labels": rng.randint(0, 4, size=rows),
        "_mask": np.ones(rows, np.float32),
    }
    state = trainer.create_state(batch["features"])
    state, loss = trainer.train_step(state, batch)
    print(json.dumps({
        "rank": args.rank,
        "devices": jax.device_count(),
        "local": jax.local_device_count(),
        "loss": float(loss),
    }), flush=True)


if __name__ == "__main__":
    main()
