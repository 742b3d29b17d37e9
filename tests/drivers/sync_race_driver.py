"""Racing sync-PS worker driver (subprocess side of
tests/test_sync_ps.py::test_two_live_workers_race_the_sync_ps).

Two modes, both free-running against ONE live sync-mode PS
(grads_to_wait=2, tolerance 0) — the reference's multi-worker sync
scenario (/root/reference/elasticdl/python/ps/servicer.py:166-236) with
REAL racing processes:

- ``constant``: pushes grad 1.0 for id 0 every step through PSClient,
  retrying version rejections by re-tagging — exact-arithmetic probe
  (the test asserts the final row value accounts for EVERY push: no
  lost updates).
- ``trainer``: a full single-device SparseTrainer on DeepFM — the
  worker-path rejection/retry loop (train/sparse.py train_step) under
  real concurrency.

Prints ONE JSON line: {"accepted": N, "rejections": N, "version": N}.
"""

import argparse
import json
import os
import sys

# CPU backend: set before any jax import
os.environ["JAX_PLATFORMS"] = "cpu"


def run_constant(ps_addr, steps):
    import numpy as np

    from elasticdl_tpu.worker.ps_client import PSClient

    client = PSClient([ps_addr])
    client.push_embedding_table_infos([("race", 4, "0.0")])
    version = 0
    rejections = 0
    accepted = 0
    grad = np.ones((1, 4), dtype=np.float32)
    ids = np.array([0], dtype=np.int64)
    for _ in range(steps):
        while True:
            ok, response_version, _ = client.push_gradients(
                {"race": (grad, ids)}, model_version=version
            )
            if ok:
                accepted += 1
                version = response_version
                break
            rejections += 1
            version = response_version
    return accepted, rejections, version


def run_trainer(ps_addr, steps, seed):
    import numpy as np

    from elasticdl_tpu.models import deepfm
    from elasticdl_tpu.train.sparse import SparseTrainer
    from elasticdl_tpu.worker.ps_client import PSClient

    trainer = SparseTrainer(
        model=deepfm.custom_model(),
        loss_fn=deepfm.loss,
        optimizer=deepfm.optimizer(),
        specs=deepfm.sparse_embedding_specs(batch_size=32),
        ps_client=PSClient([ps_addr]),
        seed=0,
    )
    rng = np.random.RandomState(seed)
    state = None
    for _ in range(steps):
        batch = {
            "features": {
                "ids": (
                    rng.zipf(1.3, size=(32, deepfm.NUM_FIELDS)) % 1000
                ).astype(np.int64)
            },
            "labels": rng.randint(0, 2, 32).astype(np.float32),
            "_mask": np.ones(32, np.float32),
        }
        state, loss = trainer.train_step(state, batch)
    assert np.isfinite(float(loss))
    return steps, trainer.push_rejections, trainer._version


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode", choices=["constant", "trainer"],
                        required=True)
    parser.add_argument("--ps_addr", required=True)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    if args.mode == "constant":
        accepted, rejections, version = run_constant(
            args.ps_addr, args.steps
        )
    else:
        accepted, rejections, version = run_trainer(
            args.ps_addr, args.steps, args.seed
        )
    print(json.dumps({
        "accepted": int(accepted),
        "rejections": int(rejections),
        "version": int(version),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
