"""The flash kernels under block diffusion's mask, alone, on one chip
(~2 min): forward and backward of ``sdar30b-bd-s8k``'s call (32 query
heads over 4 kv heads of 128, 2 x 8,192 positions, blocks of 4) timed
tile by tile and checked against the XLA path's dense mask at a length
the dense scores fit (2 x 2,048).

    chiprun --chips 1 -- python scripts/bd_flash.py

Prints one JSON line a tile pair: milliseconds of the forward and of
forward + backward, the pairs that run, and the share of their tiles'
entries the mask keeps; then the largest difference to the XLA path.
The isolated kernel gives the sign, not the size: read the step.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from elasticdl_tpu.ops import flash_attention as F  # noqa: E402
from elasticdl_tpu.ops.attention import xla_attention  # noqa: E402

HEADS, KV_HEADS, WIDTH, BLOCK = 32, 4, 128, 4
TILES = ((1024, 1024), (512, 1024), (1024, 512), (512, 512), (256, 512))


def qkv(half_len, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda heads: jnp.asarray(
        rng.normal(size=(1, heads, 2 * half_len, WIDTH)), jnp.bfloat16)
    return mk(HEADS), mk(KV_HEADS), mk(KV_HEADS)


def timed(fn, *args, repeats=10):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / repeats


def main():
    half_len = 8192
    layout = F.BlockDiffusion(half_len, BLOCK)
    q, k, v = qkv(half_len)
    needed = half_len ** 2 + half_len * BLOCK
    for block_q, block_k in TILES:
        call = lambda q, k, v: F.flash_attention(
            q, k, v, mask=layout, block_q=block_q, block_k=block_k)
        forward = jax.jit(call)
        both = jax.jit(jax.grad(
            lambda q, k, v: call(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        run, masked, skipped = F.causal_pairs(
            2 * half_len, 2 * half_len, block_q, block_k, causal=layout)
        print(json.dumps({
            "tiles": [block_q, block_k], "run": run, "masked": masked,
            "skipped": skipped,
            "fill": round(needed / (run * block_q * block_k), 4),
            "forward_ms": round(timed(forward, q, k, v), 3),
            "forward_backward_ms": round(timed(both, q, k, v), 3),
        }), flush=True)
    causal = jax.jit(lambda q, k, v: F.flash_attention(q, k, v, causal=True))
    print(json.dumps({"causal_forward_ms": round(timed(causal, q, k, v), 3)}))
    # against the dense mask, where the dense scores fit
    half_len = 2048
    layout = F.BlockDiffusion(half_len, BLOCK)
    q, k, v = qkv(half_len, seed=1)

    def outputs(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(jnp.ones_like(out))

    got = jax.jit(lambda: outputs(
        lambda q, k, v: F.flash_attention(q, k, v, mask=layout)))()
    want = jax.jit(lambda: outputs(
        lambda q, k, v: xla_attention(q, k, v, mask=layout)))()
    print(json.dumps({"max_abs_difference_to_xla": {
        name: float(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want)}}))


if __name__ == "__main__":
    main()
