"""The Gated DeltaNet layer's convolution, SiLU, norms and head split
alone on one TPU chip at the Qwen3-Next cell's shape (1 x 32,768 x
12,288 bfloat16, 16 key / 32 value heads of 128, 4 taps): each
``qkv_conv_*`` kernel alone, tile by tile, the XLA lines they stand for
alone, and the pair's gradient both ways (``--interpret --tokens 512
--tiles 128 --groups 4 --chunks 64 --check-tokens 256 --calls 1``
rehearses it on the CPU).

    python scripts/qkv_conv.py            # on one TPU chip, ~2 min

Times, ms a call, and GB/s over the bytes a call NEEDS (forward: the
8,192 columns in, q, k, v out, 1.07 GB; backward: the columns and the
three cotangents in, ``dX`` out, 1.61 GB): ``qkv_conv_fwd`` and
``qkv_conv_bwd`` at each ``--tiles`` x ``--groups`` x ``--chunks`` (rows
and heads a grid step takes, rows an iteration of its loop takes);
``qkv_conv_xla`` forward and forward + VJP; ``qkv_conv`` (the custom
VJP, with the pad into ``qkvz``'s width and the taps' sum) forward +
VJP. Checks the kernels' results and gradients against the
XLA lines' ON the chip, and both against the lines in float32. Writes
``chiprun_out/qkv_conv.json``.
"""

import argparse
import functools
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

from elasticdl_tpu.ops import qkv_conv as Q  # noqa: E402

HEADS, TOKENS, TAPS = (16, 32, 128), 32768, 4


def timed(fn, args, calls):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3, out


def size(*arrays):
    return sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(
        arrays))


def relative(got, want):
    """The largest difference over the largest entry, and the rms one
    over the rms entry."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return {
        "max": float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30)),
        "rms": float(np.sqrt(np.mean((got - want) ** 2)
                             / (np.mean(want ** 2) + 1e-30)))}


def inputs(tokens, dtype, seed=0):
    """The projection's output at the scale a normed input gives it,
    taps as the layer initialises them, cotangents of q, k and v."""
    hk, hv, dim = HEADS
    conv_dim = (2 * hk + hv) * dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    qkvz = jax.random.normal(
        keys[0], (1, tokens, conv_dim + hv * dim), jnp.float32)
    taps = jax.random.normal(keys[1], (TAPS, conv_dim)) * TAPS ** -0.5
    grads = [
        jax.random.normal(key, (1, num, tokens, dim), jnp.float32)
        for key, num in zip(keys[2:], (hk, hk, hv))]
    return tuple(x.astype(dtype) for x in [qkvz, taps] + grads)


def compare(name, got, want, exact, out):
    """``got`` (the kernels) and ``want`` (the XLA lines) against each
    other and each against ``exact`` (the lines in float32)."""
    out[name] = {
        "kernel_vs_xla": relative(got, want),
        "kernel_vs_float32": relative(got, exact),
        "xla_vs_float32": relative(want, exact)}
    print(name, json.dumps(out[name]), flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tokens", type=int, default=TOKENS)
    parser.add_argument("--tiles", default="256,512,1024")
    parser.add_argument("--groups", default="4,8")
    parser.add_argument("--chunks", default="64,128,256")
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--check-tokens", type=int, default=8192)
    parser.add_argument(
        "--interpret", action="store_true",
        help="run the kernels in the interpreter (a rehearsal on the CPU)")
    args = parser.parse_args(argv)
    if args.interpret:
        for name in ("qkv_conv_fwd", "qkv_conv_bwd"):
            setattr(Q, name, functools.partial(
                getattr(Q, name), interpret=True))
    dtype = jnp.dtype(args.dtype)
    qkvz, taps, *grads = inputs(args.tokens, dtype)
    conv_dim = taps.shape[1]
    need_fwd = size(qkvz[..., :conv_dim], grads)
    need_bwd = 2 * size(qkvz[..., :conv_dim]) + size(grads)
    out = {"device": jax.devices()[0].device_kind, "tokens": args.tokens,
           "dtype": args.dtype, "bytes": {"fwd": need_fwd, "bwd": need_bwd},
           "kernels": []}

    def one(name, fn, operands, need):
        ms, results = timed(fn, operands, args.calls)
        row = {"ms": ms, "gb_per_s": need / ms / 1e6}
        print(name, json.dumps(row), flush=True)
        return row, results

    ints = lambda text: [int(x) for x in text.split(",")]
    for group in ints(args.groups):
        for tile in ints(args.tiles):
            for chunk in ints(args.chunks):
                row = {"tile": tile, "group": group, "chunk": chunk}
                held = dict(heads=HEADS, tile=tile, group=group, chunk=chunk)
                label = "tile=%d group=%d chunk=%d" % (tile, group, chunk)
                row["fwd"], _ = one(
                    "qkv_conv_fwd " + label,
                    functools.partial(Q.qkv_conv_fwd, **held),
                    (qkvz, taps), need_fwd)
                row["bwd"], _ = one(
                    "qkv_conv_bwd " + label,
                    functools.partial(Q.qkv_conv_bwd, **held),
                    (qkvz, taps, *grads), need_bwd)
                out["kernels"].append(row)

    def gradient(fn):
        def run(qkvz, taps, *grads):
            results, vjp = jax.vjp(
                lambda x, w: fn(x, w, HEADS), qkvz, taps)
            return results, vjp(tuple(grads))
        return jax.jit(run)

    operands = (qkvz, taps, *grads)
    out["xla_fwd"], _ = one(
        "qkv_conv_xla fwd", jax.jit(functools.partial(
            Q.qkv_conv_xla, heads=HEADS)), (qkvz, taps), need_fwd)
    out["xla_fwd_vjp"], want = one(
        "qkv_conv_xla fwd+vjp", gradient(Q.qkv_conv_xla), operands,
        need_fwd + need_bwd)
    out["pallas_fwd_vjp"], got = one(
        "qkv_conv fwd+vjp", gradient(Q.qkv_conv), operands,
        need_fwd + need_bwd)
    # the checks on the sequence's first tokens (several row tiles):
    # the lines in float32 from the same values need four times the
    # memory
    del operands, want, got
    rows = args.check_tokens
    operands = (qkvz[:, :rows], taps, *(g[:, :, :rows] for g in grads))
    want = gradient(Q.qkv_conv_xla)(*operands)
    got = gradient(Q.qkv_conv)(*operands)
    exact = gradient(Q.qkv_conv_xla)(
        *(x.astype(jnp.float32) for x in operands))
    checks = out["checks"] = {}
    for name, g, w, e in zip("qkv", got[0], want[0], exact[0]):
        compare(name, g, w, e, checks)
    compare("dqkv", got[1][0][..., :conv_dim], want[1][0][..., :conv_dim],
            exact[1][0][..., :conv_dim], checks)
    compare("dtaps", got[1][1], want[1][1], exact[1][1], checks)
    checks["dz_is_zero"] = bool(
        (np.asarray(got[1][0][..., conv_dim:], np.float32) == 0).all())
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/qkv_conv.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
