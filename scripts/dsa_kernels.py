"""The indexer's selection and the sparse attention kernels alone on one
TPU chip at the Keye cell's shape (32 q heads over 4 kv heads x 32,768
x 128, an indexer of 16 heads of 64, top 2,048, bfloat16).

    python scripts/dsa_kernels.py            # on one TPU chip, ~3 min
    python scripts/dsa_kernels.py --interpret --seq 512 --check-seq 512 \
        --topk 128 --calls 1                 # rehearsal on the CPU

Times, ms a call by the host's clock around ``--calls`` calls:

- ``jax.lax.top_k`` at ``(512, seq) -> topk`` (what a selection by
  XLA's own sort would cost a block of 512 queries; a layer needs
  ``seq / 512`` of them, forward and recomputed),
- each kernel of ``ops/sparse_attention.py`` alone (``dsa_select``;
  ``dsa_mask``, which writes the kept set eight keys a byte by planes;
  ``flash_sparse_fwd``, ``flash_sparse_bwd`` and ``dsa_indexer_loss``,
  which read a tile's bit of it),
- the dense causal ``flash_fwd`` / ``flash_bwd`` at the same shape.

Checks, ON the chip at ``--check-seq`` (dense scores fit there): the
kernels' kept set, unpacked, against ``select_reference``
(``jax.lax.top_k`` itself), every query's count ``min(topk, t + 1)``,
and the output, the term and the six gradients against the
``jax.numpy`` lines. Prints sha256 digests of the whole call's output,
term, facts and gradients at the timed shape (``digest``), to hold two
trees' kernels to the same floats. Off a TPU it refuses to time unless
``--interpret``; every line and the JSON carry ``device_kind``. Writes
``chiprun_out/dsa_kernels.json``, with the figures of the kernels that
held the kept set a byte a key beside (``ms_a_byte_a_key``: PR 51's
chip run at the default shape).
"""

import argparse
import hashlib
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

from elasticdl_tpu.ops import flash_attention as F  # noqa: E402
from elasticdl_tpu.ops import sparse_attention as S  # noqa: E402

HEADS, KV_HEADS, HEAD_DIM, IDX_HEADS, IDX_DIM = 32, 4, 128, 16, 64
# PERF.md Section 6, PR 51: the same calls on a TPU v5 lite at 32,768 /
# 2,048 / bfloat16 while the kept set was an int8 (S, S)
A_BYTE_A_KEY_MS = {
    "dsa_mask": 20.4, "flash_sparse_fwd": 73.0, "flash_sparse_bwd": 134.0,
    "dsa_indexer_loss": 104.0}


def timed(fn, args, calls):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3, out


def operands(seed, seq, dtype):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda key, shape, d=dtype: jax.random.normal(key, shape, d)
    return (
        normal(keys[0], (1, HEADS, seq, HEAD_DIM)),
        normal(keys[1], (1, KV_HEADS, seq, HEAD_DIM)),
        normal(keys[2], (1, KV_HEADS, seq, HEAD_DIM)),
        normal(keys[3], (1, IDX_HEADS, seq, IDX_DIM)),
        normal(keys[4], (1, seq, IDX_DIM)),
        normal(keys[5], (1, seq, IDX_HEADS), jnp.float32)
        * (IDX_HEADS * IDX_DIM) ** -0.5,
    )


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(
        np.sqrt(np.mean((got - want) ** 2))
        / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def digest(seq, topk, dtype, interpret):
    """sha256 of what the call hands back at seed-0 operands, a name a
    value: the output, the term, the three facts and the six gradients
    of ``sum(out^2) + sum(kl)``. Two trees whose kernels compute the
    same floats from the same kept set print the same lines."""
    def fn(*args):
        o, kl, facts = S.dsa_attention(
            *args, topk, impl="pallas", interpret=interpret)
        return (o.astype(jnp.float32) ** 2).sum() + kl.sum(), (
            o, kl, facts)

    (_, (o, kl, facts)), grads = jax.jit(jax.value_and_grad(
        fn, argnums=tuple(range(6)), has_aux=True))(
            *operands(0, seq, dtype))
    values = dict(
        zip(("dq", "dk", "dv", "dqi", "dki", "dw"), grads), out=o, kl=kl,
        **facts)
    return {
        name: hashlib.sha256(np.asarray(
            value.astype(jnp.float32)).tobytes()).hexdigest()[:16]
        for name, value in sorted(values.items())}


def check(seq, topk, dtype, interpret):
    q, k, v, qi, ki, w = operands(1, seq, dtype)
    scores = S.scores_reference(qi, ki, w)
    want = S.select_reference(scores, topk)
    planes = S._planes(seq, HEAD_DIM, dtype)
    threshold, tie = S._select_call(qi, ki, w, topk, interpret)
    mask = S._mask_call(qi, ki, w, threshold, tie, topk, planes, interpret)[0]
    causal = np.tril(np.ones((seq, seq), bool))
    kept = np.asarray(S.unpack_planes(mask, planes)[0])
    got = kept & causal
    counts = got.sum(-1)
    out = {
        "planes": planes,
        "kept_after": int((kept & ~causal).sum()),
        "kept_disagreements": int((got != np.asarray(want[0])).sum()),
        "counts_exact": bool(
            (counts == np.minimum(topk, np.arange(seq) + 1)).all()),
    }

    def loss(impl):
        def fn(*args):
            o, kl, _ = S.dsa_attention(
                *args, topk, impl=impl, interpret=interpret)
            return (o.astype(jnp.float32) ** 2).mean() + kl.sum(), (o, kl)
        return jax.jit(jax.value_and_grad(
            fn, argnums=tuple(range(6)), has_aux=True))

    (_, (o_x, kl_x)), g_x = loss("xla")(q, k, v, qi, ki, w)
    (_, (o_p, kl_p)), g_p = loss("pallas")(q, k, v, qi, ki, w)
    out["out"] = relative(o_p, o_x)
    out["kl"] = [float(kl_p[0]), float(kl_x[0])]
    for name, a, b in zip(("dq", "dk", "dv", "dqi", "dki", "dw"), g_p, g_x):
        out[name] = relative(a, b)
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=32768)
    parser.add_argument("--check-seq", type=int, default=4096)
    parser.add_argument("--topk", type=int, default=2048)
    parser.add_argument("--check-topk", type=int, default=None)
    parser.add_argument("--calls", type=int, default=3)
    parser.add_argument("--interpret", action="store_true")
    parser.add_argument("--dtype", default="bfloat16")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        raise SystemExit(
            "scripts/dsa_kernels.py times kernels on a TPU; on %s pass "
            "--interpret (a rehearsal: its times mean nothing)"
            % device.platform)
    dtype = jnp.dtype(args.dtype)
    seq, topk, interp = args.seq, args.topk, args.interpret
    result = {
        "device_kind": device.device_kind, "interpret": interp,
        "clock": "host", "seq": seq, "topk": topk, "dtype": dtype.name,
        "ms": {},
    }

    def say(name, ms):
        result["ms"][name] = ms
        print("dsa_kernels: %-28s %9.3f ms  (%s)" % (
            name, ms, device.device_kind), flush=True)

    q, k, v, qi, ki, w = operands(0, seq, dtype)
    if not interp:
        block = jax.random.normal(
            jax.random.PRNGKey(9), (512, seq), jnp.float32)
        ms, _ = timed(
            jax.jit(lambda x: jax.lax.top_k(x, min(topk, seq))), (block,),
            args.calls)
        say("lax.top_k(512 rows)", ms)
    select = jax.jit(lambda *a: S._select_call(*a, topk, interp))
    ms, (threshold, tie) = timed(select, (qi, ki, w), args.calls)
    say("dsa_select", ms)
    planes = S._planes(seq, HEAD_DIM, dtype)
    result["planes"] = planes
    mask_fn = jax.jit(lambda *a: S._mask_call(*a, topk, planes, interp))
    ms, (mask, lse_i, _, kept, _) = timed(
        mask_fn, (qi, ki, w, threshold, tie), args.calls)
    say("dsa_mask", ms)
    result["kept_mean"] = float(kept.mean())
    result["kept_set_bytes"] = mask.nbytes
    print("dsa_kernels: kept keys a query %.3f, the kept set %s int8 by %d "
          "planes" % (result["kept_mean"], mask.shape, planes))
    merge = lambda t: t.reshape((-1,) + t.shape[2:])
    scale = HEAD_DIM ** -0.5
    fwd = jax.jit(lambda q, k, v, m: S._fwd_call(
        merge(q), merge(k), merge(v), m, scale, interp))
    ms, (o, lse) = timed(fwd, (q, k, v, mask), args.calls)
    say("flash_sparse_fwd", ms)
    bwd = jax.jit(lambda q, k, v, o, lse, m: S._bwd_call(
        merge(q), merge(k), merge(v), o, lse, o, m, scale, interp))
    ms, _ = timed(bwd, (q, k, v, o, lse, mask), args.calls)
    say("flash_sparse_bwd", ms)
    loss = jax.jit(lambda q, k, lse, m, qi, ki, w, lse_i: S._loss_call(
        q, k, lse.reshape(1, HEADS, 1, seq), m, qi, ki, w, lse_i, scale,
        interp))
    ms, _ = timed(loss, (q, k, lse, mask, qi, ki, w, lse_i), args.calls)
    say("dsa_indexer_loss", ms)
    if not interp:
        dense = jax.jit(lambda q, k, v: F._fwd(
            merge(q), merge(k), merge(v), scale, True, None, None, False))
        ms, (o, lse) = timed(dense, (q, k, v), args.calls)
        say("flash_fwd (dense causal)", ms)
        dense_bwd = jax.jit(lambda q, k, v, o, lse: F._bwd(
            merge(q), merge(k), merge(v), o, lse, o, scale, True, None,
            None, False))
        ms, _ = timed(dense_bwd, (q, k, v, o, lse), args.calls)
        say("flash_bwd (dense causal)", ms)
    result["digest"] = digest(seq, topk, dtype, interp)
    print("dsa_kernels: digest at %d: %s" % (seq, json.dumps(result["digest"])))
    result["check"] = check(
        args.check_seq, args.check_topk or min(topk, args.check_seq // 4),
        dtype, interp)
    print("dsa_kernels: check at %d: %s" % (
        args.check_seq, json.dumps(result["check"])))
    if (seq, topk, dtype.name) == (32768, 2048, "bfloat16"):
        result["ms_a_byte_a_key"] = A_BYTE_A_KEY_MS
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_kernels.json", "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
