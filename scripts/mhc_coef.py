"""One hyper-connection alone on one TPU chip at the Xing4.0 cell's
shape (one sequence of 4,096 tokens, four streams of 3,584 bfloat16):
the module both ways and each kernel alone.

    python scripts/mhc_coef.py            # on one TPU chip, ~2 min

Times value and gradient (streams and parameters) of one
``HyperConnection`` around an identity sublayer, ms a call over 20
calls, as ``ops/hyper_connection.py:mix_impl`` chooses on the chip
(``impl=pallas``) and with the choice held to the module's own lines
(``impl=xla``), and checks the first's value, ``H_res`` and every
gradient against the second's; then each ``mhc_...`` kernel alone with
the bytes it has to move and its GB/s. Writes
``chiprun_out/mhc_coef.json``. PR 37's sweep of the scan's ``unroll``
(1-20, tokens folded to (32, 128): 4.57-4.87 ms, nothing) and PR 38's
of the kernels' tile and loop group (256 tokens a grid step, 32 a loop
iteration: within 5% of 128 and 16) are in ``PERF.md`` Section 6 and no
longer run.
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
import numpy as np  # noqa: E402

from elasticdl_tpu.models import transformer as T  # noqa: E402
from elasticdl_tpu.ops import hyper_connection as H  # noqa: E402

SEQ, DIM, STREAMS, CALLS, REPEATS = 4096, 3584, 4, 20, 9


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS * 1e3, out


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def module_both_ways(params, x):
    module = T.HyperConnection(T.HyperDims(STREAMS))

    def loss(params, x):
        (u, write, _), sown = module.apply(
            {"params": params}, x, mutable=["intermediates"])
        out = write(u)
        return (jnp.sum(out.astype(jnp.float32) ** 2) * 1e-6,
                sown["intermediates"]["h_res"][0])

    results, chosen = {}, H.mix_impl
    for impl in ("xla", "pallas"):
        H.mix_impl = chosen if impl == "pallas" else (lambda *a, **k: "xla")
        t0 = time.perf_counter()
        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
        ms, out = timed(fn, params, x)
        results[impl] = {
            "ms": ms, "value": float(out[0][0]),
            "compile_and_run_s": time.perf_counter() - t0 - ms * CALLS / 1e3}
        results[impl + "_out"] = out
        print("impl=" + impl, json.dumps(results[impl]), flush=True)
    H.mix_impl = chosen
    (value, h_res), (d_params, dx) = results.pop("pallas_out")
    (want, want_res), (want_params, want_dx) = results.pop("xla_out")
    results["pallas_against_xla"] = dict(
        {"value": relative(value, want), "h_res": relative(h_res, want_res),
         "dx": relative(dx, want_dx)},
        **{name: relative(d_params[name], want_params[name])
           for name in sorted(d_params)})
    print("pallas against xla", json.dumps(results["pallas_against_xla"]),
          flush=True)
    return results


def kernels_alone(params, x):
    """Each kernel by itself with the bytes its operands and results
    hold: ``REPEATS`` calls in one ``fori_loop`` of one program, each
    reading the last one's result (the host takes ~0.4 ms to launch a
    program here, longer than a kernel runs)."""
    dims = (STREAMS, 20, 1e-6, (-30.0, 30.0))
    kernel = jnp.concatenate(
        [params["p_pre"], params["p_post"], params["p_res"]],
        axis=-1).astype(x.dtype)
    kt, gb = H.operands(
        kernel, (params["a_pre"], params["a_post"], params["a_res"]),
        (params["b_pre"], params["b_post"], params["b_res"]))
    size = lambda *arrays: sum(a.size * a.dtype.itemsize for a in arrays)
    out = {}

    def one(name, call, small, *rest):
        """``call(small, *rest)`` -> (the next ``small``, the kernel's
        results). Only ``small`` is carried: a loop that carries a
        result of the streams' size copies it every iteration."""
        def many(small, *rest):
            small = jax.lax.fori_loop(
                0, REPEATS, lambda _, s: call(s, *rest)[0], small)
            return call(small, *rest)[1]

        ms, results = timed(jax.jit(many), small, *rest)
        ms /= REPEATS + 1
        moved = size(small, *rest, *results)
        out[name] = {"ms": ms, "bytes": moved, "gb_per_s": moved / ms / 1e6}
        print(name, json.dumps(out[name]), flush=True)
        return results

    # what a call hands to the next: a small operand through a value
    # the compiler cannot fold (0 x a result's entry)
    tie = lambda small, result: small + 0.0 * result.reshape(-1)[0].astype(
        small.dtype)

    def pre_fwd(gb, x, kt):
        results = H.mhc_pre_fwd(x, kt, gb, dims)
        return tie(gb, results[1]), tuple(results)

    def post_fwd(coef, x, y):
        new = H.mhc_post_fwd(x, y, coef)
        return tie(coef, new), (new,)

    def post_bwd(coef, dxo, y):
        results = H.mhc_post_bwd(dxo, y, coef)
        return tie(coef, results[1]), tuple(results)

    def pre_bwd(dcoef, x, dxo, du, kt, gb, coef, raw):
        results = H.mhc_pre_bwd(x, dxo, du, kt, gb, coef, raw, dcoef, dims)
        return tie(dcoef, results[2]), tuple(results)

    u, coef, raw = one("mhc_pre_fwd", pre_fwd, gb, x, kt)
    new, = one("mhc_post_fwd", post_fwd, coef, x, u)
    _, dcoef = one("mhc_post_bwd", post_bwd, coef, new, u)
    one("mhc_pre_bwd", pre_bwd, dcoef, x, new, u, kt, gb, coef, raw)
    return out


def main():
    x = jax.random.normal(
        jax.random.PRNGKey(0), (1, STREAMS, SEQ, DIM), jnp.bfloat16)
    module = T.HyperConnection(T.HyperDims(STREAMS))
    params = jax.jit(module.init)(jax.random.PRNGKey(1), x)["params"]
    # gates and biases where a trained run's would be (check.py)
    params = dict(params, a_res=jnp.float32(0.8), a_pre=jnp.float32(0.8),
                  b_res=jax.random.normal(jax.random.PRNGKey(2), (4, 4)))
    results = {"module": module_both_ways(params, x),
               "kernels": kernels_alone(params, x)}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mhc_coef.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "shape": [1, STREAMS, SEQ, DIM], **results}, f, indent=1)


if __name__ == "__main__":
    main()
