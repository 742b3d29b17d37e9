"""Pipelined-vs-sequential sparse training under controlled PS latency.

The round-2 VERDICT (item 4) asked for the pipelined-sparse claim to be
measured, not extrapolated: this sweeps an injected per-RPC delay at
the PS processes (``--inject_rpc_delay_ms``, emulating worker<->PS
network RTT) and measures both training modes at each point.

MEASURE ON A REAL ACCELERATOR: run with ``--backend default`` and
delays sized against the step time (e.g. ``--delays_ms 0,20,50,100``)
— that is how the docs/PERF_SPARSE.md crossover table was produced.
The default ``--backend cpu`` only validates the
harness: on the CPU backend the "device" compute runs on the same
cores the pull/push threads need, so overlap cannot win by
construction (measured 0.91-1.01x).

Prints one JSON line with the crossover table.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--delays_ms", default="0,5,20",
        help="comma-separated injected per-RPC delays",
    )
    parser.add_argument("--batch_size", type=int, default=512)
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--warmup", type=int, default=10)
    parser.add_argument(
        "--backend", default="cpu", choices=["cpu", "default"],
        help="cpu: force JAX_PLATFORMS=cpu; default: whatever the "
        "machine provides (the real chip here). NOTE the cpu backend "
        "cannot demonstrate overlap — 'device' compute runs on the "
        "same cores the pull/push threads need — it only validates "
        "the harness; measure on a real accelerator.",
    )
    args = parser.parse_args()
    if args.backend == "cpu":
        # must precede any jax import (including the one inside bench)
        os.environ["JAX_PLATFORMS"] = "cpu"

    from bench import deepfm_run

    rows = []
    for delay in [float(d) for d in args.delays_ms.split(",")]:
        sequential, _ = deepfm_run(
            pipelined=False, inject_rpc_delay_ms=delay,
            batch_size=args.batch_size, warmup=args.warmup,
            steps=args.steps,
        )
        pipelined, _ = deepfm_run(
            pipelined=True, inject_rpc_delay_ms=delay,
            batch_size=args.batch_size, warmup=args.warmup,
            steps=args.steps,
        )
        rows.append({
            "rtt_ms": delay,
            "sequential_steps_per_sec": round(sequential, 2),
            "pipelined_steps_per_sec": round(pipelined, 2),
            "speedup": round(pipelined / sequential, 2),
        })
        print("rtt=%5.1fms  seq=%6.2f  pipe=%6.2f  speedup=%.2fx"
              % (delay, sequential, pipelined, pipelined / sequential),
              flush=True)
    print(json.dumps({"backend": args.backend, "batch": args.batch_size,
                      "rows": rows}))


if __name__ == "__main__":
    main()
