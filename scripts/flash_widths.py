"""Flash attention with q / k of one width and v of another (latent
attention: q and k of 192 = 128 nope + 64 rope, v of 128), kernel alone
on one TPU chip: timed, and checked against the XLA reference.

    python scripts/flash_widths.py        # on one TPU chip, ~4 min
    python scripts/flash_widths.py --rehearse   # the CPU: control flow

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
not kept (PERF.md Section 6, PR 29, has the table).

Since PR 61 also the backward at the two 32,768-token cells' shapes,
32 heads x 32,768 x 192 / 128 (``kimi-linear48b-s32k``) and 16 heads
over 2 kv heads x 32,768 x 256 (``qwen3next80b-s32k``), where
``flash_bwd`` keeps dq's whole-head output block in ONE buffer
(``fused_dq_buffers``): the schedule the tree gives against the split
pair (a budget of 0 while it is traced), ms a call, and dq, dk, dv of
the two compared element for element; beside them two forms no shape
gets, under a limit only a v5e / v6e core has room for: the pipeline's
two buffers (what the single one costs) and 1024 q-rows. Writes
``chiprun_out/flash_widths.json``.
"""

import contextlib
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


def inputs(bh, seq, qk_dim, v_dim, seed=0, kv_bh=None):
    rng = np.random.RandomState(seed)
    make = lambda heads, d: jnp.asarray(
        rng.randn(heads, seq, d) * 0.5, jnp.bfloat16)
    kv_bh = kv_bh or bh
    return (make(bh, qk_dim), make(kv_bh, qk_dim), make(kv_bh, v_dim),
            make(bh, v_dim))


def ms_per_call(fn, *args, calls=CALLS):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e3


def kernels(blocks, interpret=False):
    """(forward, backward) jitted on (bh, seq, width) operands."""
    scale = 1.0 / math.sqrt(192)

    def fwd(q, k, v):
        return F._fwd(q, k, v, scale, True, *blocks, interpret)

    def bwd(q, k, v, o, lse, do):
        return F._bwd(q, k, v, o, lse, do, scale, True, *blocks, interpret)

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


@contextlib.contextmanager
def budget(mib):
    """``_FUSED_VMEM_BYTES`` at ``mib`` MiB while a form is traced and
    compiled (``None``: as the tree has it)."""
    real = F._FUSED_VMEM_BYTES
    if mib is not None:
        F._FUSED_VMEM_BYTES = mib * 2**20
    try:
        yield
    finally:
        F._FUSED_VMEM_BYTES = real


# (name, budget in MiB while traced, blocks asked): the tree's own form
# first; the pair; then what no shape gets
FORMS = (
    ("tree", None, (None, None)),
    ("split", 0, (None, None)),
    ("two-buffers-under-96MiB", 96, (512, 1024)),
    ("1024-q-rows-under-96MiB", 96, (1024, 1024)),
)


def schedules_case(bh, kv_bh, seq, qk_dim, v_dim, forms=FORMS, calls=10,
                   interpret=False):
    """The backward at one long shape under each of ``forms``: ms a
    call, and dq, dk, dv against the first form's (the tree's): the
    largest |difference| and whether every element is equal."""
    q, k, v, do = inputs(bh, seq, qk_dim, v_dim, 2, kv_bh)
    fwd, _ = kernels((None, None), interpret)
    o, lse = fwd(q, k, v)
    rows, first = [], None
    for name, mib, blocks in forms:
        # a new function: jax keeps the traces of the last one
        _, bwd = kernels(blocks, interpret)
        row = {"form": name, "asked": blocks, "budget_mib": mib}
        with budget(mib):
            row["schedule"] = F.backward_schedule(
                seq, seq, qk_dim, q.dtype, *blocks, v_dim=v_dim)
            row["dq_buffers"] = F.fused_dq_buffers(
                seq, seq, qk_dim, q.dtype, *blocks, v_dim=v_dim)
            row["blocks_bwd"] = F._blocks(
                seq, seq, qk_dim, q.dtype, *blocks, backward=True,
                v_dim=v_dim)
            try:
                grads = jax.block_until_ready(bwd(q, k, v, o, lse, do))
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                row["refused"] = str(e)[:300]
                rows.append(row)
                continue
        row["bwd_ms"] = ms_per_call(bwd, q, k, v, o, lse, do, calls=calls)
        grads = [np.asarray(g.astype(jnp.float32)) for g in grads]
        if first is None:
            first = grads
        else:
            for label, a, b in zip(("dq", "dk", "dv"), grads, first):
                row[label + "_equal"] = bool(np.array_equal(a, b))
                row[label + "_max_abs_diff"] = float(np.abs(a - b).max())
        rows.append(row)
    return rows


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
    if "--rehearse" in sys.argv:
        # the long shapes' control flow, tiny, interpreted: no timing
        # is kept and nothing is written
        for row in schedules_case(
                4, 2, 1024, 192, 128, calls=1, interpret=True,
                forms=FORMS[:2] + (("512-q-rows", None, (512, 512)),)):
            del row["bwd_ms"]  # the interpreter's, no device's
            print(json.dumps(row), flush=True)
        return
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
    for cell, shape in (
            ("kimi-linear48b-s32k", (32, 32, 32768, 192, 128)),
            ("qwen3next80b-s32k", (16, 2, 32768, 256, 256))):
        for row in schedules_case(*shape):
            record(cell=cell, bh=shape[0], kv_bh=shape[1], seq=shape[2],
                   widths="%d/%d" % shape[3:], **row)
    out = os.path.join("chiprun_out", "flash_widths.json")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
