"""Do the Pallas kernels compile on this chip and agree with their
references? The probe behind the kernel notes of PERF.md (PR 21).

    python scripts/probe_kernels.py        # on one TPU chip

Each section compiles a kernel with ``interpret=False`` at a shape the
training paths use, runs it, and compares it with its reference:

- flash attention forward + backward (``impl="pallas"``) against the XLA
  attention, at head width 64 (the zoo transformer) and 256, S=1024,
  and alone at S=16384.

A probe, not a benchmark: the ms it prints are a handful of iterations
of the isolated kernel, enough to tell 2x from 1x and nothing finer.
It exits non-zero if a section failed, writes what it printed to
``chiprun_out/probe_kernels.txt``, and fails at the first kernel on a
backend that is not a TPU.
"""

import os
import sys
import time
import traceback

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from elasticdl_tpu.ops.attention import dot_product_attention  # noqa: E402

LINES = []
FAILED = []


def say(text):
    print(text, flush=True)
    LINES.append(text)


def section(name, fn, *args, **kwargs):
    say("=== %s" % name)
    start = time.time()
    try:
        fn(*args, **kwargs)
        say("--- %s OK (%.1fs)" % (name, time.time() - start))
    except Exception:
        FAILED.append(name)
        say("--- %s FAILED (%.1fs)\n%s" % (
            name, time.time() - start, traceback.format_exc()[-3000:]
        ))


def ms_per_call(fn, *args, calls=5):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e3


def max_abs_diff(a, b):
    return float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)
    )))


def flash_case(batch, heads, seq, head_dim, check=True):
    rng = np.random.RandomState(0)
    q, k, v = [
        jnp.asarray(rng.randn(batch, heads, seq, head_dim) * 0.5,
                    jnp.bfloat16)
        for _ in range(3)
    ]

    def value_and_grads(impl):
        def fn(q, k, v):
            out = dot_product_attention(q, k, v, causal=True, impl=impl)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(
            jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True)
        )

    pallas = value_and_grads("pallas")
    start = time.time()
    (loss, out), grads = pallas(q, k, v)
    jax.block_until_ready(grads)
    say("  pallas fwd+bwd compiled and ran in %.1fs, loss %.4f"
        % (time.time() - start, float(loss)))
    assert bool(jnp.isfinite(loss))
    say("  pallas fwd+bwd %.3f ms" % ms_per_call(pallas, q, k, v))
    if not check:  # the O(S^2) reference does not fit at this length
        return
    xla = value_and_grads("xla")
    (_, ref_out), ref_grads = xla(q, k, v)
    say("  xla    fwd+bwd %.3f ms" % ms_per_call(xla, q, k, v))
    out_diff = max_abs_diff(out, ref_out)
    grad_diffs = [max_abs_diff(g, r) for g, r in zip(grads, ref_grads)]
    grad_scale = [float(jnp.max(jnp.abs(r.astype(jnp.float32))))
                  for r in ref_grads]
    say("  max|o diff| %.4g, grad diffs %s (grad max %s)"
        % (out_diff, grad_diffs, grad_scale))
    assert out_diff < 0.05
    assert all(d < 0.05 * max(1.0, s)
               for d, s in zip(grad_diffs, grad_scale))


def main():
    device = jax.devices()[0]
    say("device %s %s x%d, jax %s" % (
        device.platform, device.device_kind, jax.device_count(),
        jax.__version__,
    ))
    section("flash head 64, B8 H12 S1024 (zoo transformer)",
            flash_case, 8, 12, 1024, 64)
    section("flash head 256, B2 H8 S1024", flash_case, 2, 8, 1024, 256)
    section("flash head 256, B1 H8 S16384 (no reference)",
            flash_case, 1, 8, 16384, 256, check=False)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_kernels.txt", "w") as out:
        out.write("\n".join(LINES) + "\n")
    if FAILED:
        say("FAILED: %s" % ", ".join(FAILED))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
