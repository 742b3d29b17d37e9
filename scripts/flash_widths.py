"""Flash attention with q / k of one width and v of another (latent
attention: q and k of 192 = 128 nope + 64 rope, v of 128), kernel alone
on one TPU chip: timed, and checked against the XLA reference.

    python scripts/flash_widths.py        # on one TPU chip, ~2 min

Times ``flash_fwd`` and ``flash_bwd`` alone (``_fwd`` / ``_bwd`` under
one jit each; ms a call over 20 calls) at the ``moonlight16b-s8k``
cell's shapes, 16 and 32 heads x 8192, bfloat16, causal, at the blocks
``_blocks`` picks and at 512 / 1024, and compares output and gradients
with ``xla_attention`` at 2048 tokens. Calls of other widths stand
beside it: 256 / 256 (q, k and v zero-padded in HBM: what a caller
without the two widths would do), 256 / 128 (q and k alone padded in
HBM) and 128 / 128 (the work if the rope part were dropped: a floor,
not a candidate). PR 29 ran this with two more layouts of the 192 lanes
inside the kernel (zero-padded to 256 in VMEM; the score as two
contractions, 128 + 64); neither beat 192 as it is and their code was
not kept (PERF.md Section 6, PR 29, has the table). Writes
``chiprun_out/flash_widths.json``.
"""

import json
import math
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from elasticdl_tpu.ops import flash_attention as F  # noqa: E402
from elasticdl_tpu.ops.attention import xla_attention  # noqa: E402

CALLS = 20


def inputs(bh, seq, qk_dim, v_dim, seed=0):
    rng = np.random.RandomState(seed)
    make = lambda d: jnp.asarray(
        rng.randn(bh, seq, d) * 0.5, jnp.bfloat16)
    return make(qk_dim), make(qk_dim), make(v_dim), make(v_dim)


def ms_per_call(fn, *args):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS * 1e3


def kernels(blocks):
    """(forward, backward) jitted on (bh, seq, width) operands."""
    scale = 1.0 / math.sqrt(192)

    def fwd(q, k, v):
        return F._fwd(q, k, v, scale, True, *blocks, False)

    def bwd(q, k, v, o, lse, do):
        return F._bwd(q, k, v, o, lse, do, scale, True, *blocks, False)

    return jax.jit(fwd), jax.jit(bwd)


def time_case(bh, seq, qk_dim, v_dim, blocks=(None, None)):
    q, k, v, do = inputs(bh, seq, qk_dim, v_dim)
    fwd, bwd = kernels(blocks)
    o, lse = fwd(q, k, v)
    return {
        "fwd_ms": ms_per_call(fwd, q, k, v),
        "bwd_ms": ms_per_call(bwd, q, k, v, o, lse, do),
        "blocks_fwd": F._blocks(
            seq, seq, qk_dim, q.dtype, *blocks, v_dim=v_dim),
        "blocks_bwd": F._blocks(
            seq, seq, qk_dim, q.dtype, *blocks, backward=True,
            v_dim=v_dim),
        "schedule": F.backward_schedule(
            seq, seq, qk_dim, q.dtype, *blocks, v_dim=v_dim),
    }


def check_case():
    """Largest |difference| to the XLA reference of o, dq, dk, dv at
    2 x 16 heads x 2048, over the reference's largest |value|."""
    q, k, v, do = [
        t.reshape(2, 16, 2048, -1) for t in inputs(32, 2048, 192, 128, 1)]

    def outputs(attention):
        def loss(q, k, v):
            o = attention(q, k, v)
            return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + grads

    got = outputs(lambda q, k, v: F.flash_attention(q, k, v, causal=True))
    want = outputs(lambda q, k, v: xla_attention(q, k, v, causal=True))
    return {
        name: float(
            jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
            / jnp.max(jnp.abs(b.astype(jnp.float32))))
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want)
    }


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("flash_widths: needs a TPU, found %s" % device.platform)
    report = {"device": device.device_kind, "calls": CALLS, "cases": []}

    def record(**row):
        print(json.dumps(row), flush=True)
        report["cases"].append(row)

    record(check="192/128", **check_case())
    for bh in (16, 32):
        for blocks in ((None, None), (512, 1024)):
            record(bh=bh, seq=8192, widths="192/128", layout=F.QK_LAYOUT,
                   asked=blocks, **time_case(bh, 8192, 192, 128, blocks))
        record(bh=bh, seq=8192, widths="256/256", layout="hbm-padded",
               **time_case(bh, 8192, 256, 256))
        record(bh=bh, seq=8192, widths="256/128", layout="hbm-padded q,k",
               **time_case(bh, 8192, 256, 128))
        record(bh=bh, seq=8192, widths="128/128", layout="floor",
               **time_case(bh, 8192, 128, 128))
    out = os.path.join("chiprun_out", "flash_widths.json")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
