"""The hyper-connection's coefficients alone on one TPU chip: what the
Sinkhorn iterations cost as the scan unrolls them, at the Xing4.0 cell's
shape (one sequence of 4,096 tokens, four streams of 3,584 bfloat16).

    python scripts/mhc_coef.py            # on one TPU chip, ~2 min

Times value and gradient (streams and parameters) of one
``HyperConnection`` around an identity sublayer, ms a call over 20
calls, with the Sinkhorn scan's ``unroll`` at 1, 2, 5, 10 and 20 (a
copy of ``models/transformer.py:sinkhorn`` that takes it) and with the tokens folded to (32, 128) so that the n x n small
arrays fill their tiles, and checks every variant's value against the
first; then with ONE iteration in the place of twenty (another value:
what is left is the passes over the streams, the 24-wide matmul and
the two mixes). Writes ``chiprun_out/mhc_coef.json``.
"""

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from elasticdl_tpu.models import transformer as T  # noqa: E402

SEQ, DIM, STREAMS, CALLS = 4096, 3584, 4, 20


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS * 1e3, out


def main():
    iters = {"module": T.HyperConnection(T.HyperDims(STREAMS))}
    module = iters["module"]
    x = jax.random.normal(
        jax.random.PRNGKey(0), (1, STREAMS, SEQ, DIM), jnp.bfloat16)
    params = jax.jit(module.init)(jax.random.PRNGKey(1), x)["params"]
    # gates and biases where a trained run's would be (check.py)
    params = dict(params, a_res=jnp.float32(0.8), a_pre=jnp.float32(0.8),
                  b_res=jax.random.normal(jax.random.PRNGKey(2), (4, 4)))

    def loss(params, x):
        u, write, _ = iters["module"].apply({"params": params}, x)
        return jnp.sum(write(u).astype(jnp.float32) ** 2) * 1e-6

    true_sinkhorn = T.sinkhorn

    def unrolled(unroll, fold):
        def sinkhorn(matrix, iters, eps):
            def step(m, _):
                m = m / (m.sum(axis=1, keepdims=True) + eps)
                return m / (m.sum(axis=0, keepdims=True) + eps), None

            shape = matrix.shape
            if fold:
                matrix = matrix.reshape(shape[:2] + (-1, 128))
            return jax.lax.scan(
                step, matrix, None, length=iters, unroll=unroll
            )[0].reshape(shape)
        return sinkhorn

    results, first = {}, None
    for name, unroll, fold in (
            ("unroll=1", 1, False), ("unroll=2", 2, False),
            ("unroll=5", 5, False), ("unroll=10", 10, False),
            ("unroll=20", 20, False), ("unroll=1 folded", 1, True),
            ("unroll=5 folded", 5, True), ("unroll=20 folded", 20, True),
            ("one iteration", 1, False)):
        if name == "one iteration":
            iters["module"] = T.HyperConnection(
                T.HyperDims(STREAMS, sinkhorn_iters=1))
        T.sinkhorn = unrolled(unroll, fold)
        t0 = time.perf_counter()
        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        ms, (value, _) = timed(fn, params, x)
        first = float(value) if first is None else first
        results[name] = {
            "ms": ms, "value": float(value),
            "compile_and_run_s": time.perf_counter() - t0 - ms * CALLS / 1e3,
            "agrees": (name == "one iteration"
                       or abs(float(value) - first) <= 1e-3 * abs(first))}
        print(name, json.dumps(results[name]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    T.sinkhorn = true_sinkhorn
    with open("chiprun_out/mhc_coef.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
