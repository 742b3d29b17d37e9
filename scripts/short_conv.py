"""The gated short convolution alone on one TPU chip at the LFM2 cell's
shape (1 x 32,768 x 6,144 bfloat16, 2,048 channels, 3 taps): each
``short_conv_*`` kernel alone, tile by tile, the XLA lines they stand
for alone, and the pair's gradient both ways (``--interpret --tokens 512
--channels 256 --tiles 128 --chunks 64 --check-tokens 256 --calls 1``
rehearses it on the CPU).

    python scripts/short_conv.py          # on one TPU chip, ~2 min

Times, ms a call, and GB/s over the bytes a call NEEDS (forward: B, C,
X in, ``y`` out, 537 MB; backward: B, C, X and ``dy`` in, ``dB | dC |
dX`` out, 940 MB): ``short_conv_fwd`` and ``short_conv_bwd`` at each
``--tiles`` x ``--chunks`` (rows a grid step takes, rows an
iteration of its loop takes; a tile whose blocks pass the VMEM limit is
said to be refused), with the seconds each took to trace and lower; ``gated_short_conv_xla`` forward and
forward + VJP; ``gated_short_conv`` (the custom VJP, with the taps'
sum) forward + VJP. Checks the kernels' results and gradients against
the XLA lines' ON the chip, and both against the lines in float32.
Writes ``chiprun_out/short_conv.json``.
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

from elasticdl_tpu.ops import short_conv as S  # noqa: E402

TOKENS, CHANNELS, TAPS = 32768, 2048, 3
KERNELS = ("short_conv_fwd", "short_conv_bwd")


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


def inputs(tokens, channels, taps, dtype, seed=0):
    """The projection's output at the scale a normed input gives it,
    taps as the mixer initialises them, a cotangent of ``y``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(keys[0], (1, tokens, 3 * channels), jnp.float32)
    w = jax.random.normal(keys[1], (taps, channels)) * taps ** -0.5
    dy = jax.random.normal(keys[2], (1, tokens, channels), jnp.float32)
    return tuple(x.astype(dtype) for x in (bcx, w, dy))


def compare(name, got, want, exact, out):
    """``got`` (the kernels) and ``want`` (the XLA lines) against each
    other and each against ``exact`` (the lines in float32)."""
    out[name] = {
        "kernel_vs_xla": relative(got, want),
        "kernel_vs_float32": relative(got, exact),
        "xla_vs_float32": relative(want, exact)}
    print(name, json.dumps(out[name]), flush=True)


def gradient(fn):
    def run(bcx, w, dy):
        y, vjp = jax.vjp(fn, bcx, w)
        return y, vjp(dy)
    return jax.jit(run)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tokens", type=int, default=TOKENS)
    parser.add_argument("--channels", type=int, default=CHANNELS)
    parser.add_argument("--taps", type=int, default=TAPS)
    parser.add_argument("--tiles", default="256,512,1024")
    parser.add_argument("--chunks", default="16,32,64")
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--check-tokens", type=int, default=8192)
    parser.add_argument(
        "--interpret", action="store_true",
        help="run the kernels in the interpreter (a rehearsal on the CPU)")
    args = parser.parse_args(argv)
    kernels = {name: getattr(S, name) for name in KERNELS}
    interpret = {"interpret": True} if args.interpret else {}
    if args.interpret:
        S.conv_impl = lambda *a, **kw: "pallas"
        for name in KERNELS:
            setattr(S, name, functools.partial(kernels[name], **interpret))
    dtype = jnp.dtype(args.dtype)
    bcx, w, dy = inputs(args.tokens, args.channels, args.taps, dtype)
    need_fwd = size(bcx, dy)
    need_bwd = 2 * size(bcx) + size(dy)
    out = {"device": jax.devices()[0].device_kind, "tokens": args.tokens,
           "channels": args.channels, "taps": args.taps,
           "dtype": args.dtype, "bytes": {"fwd": need_fwd, "bwd": need_bwd},
           "chosen": {
               "impl_tile": S.conv_choice(
                   dtype, args.channels, args.tokens, args.taps),
               "chunk": S.loop_rows(
                   S.row_tile(args.tokens, args.channels, dtype.itemsize)
                   or args.tokens, args.channels)},
           "fwd": [], "bwd": []}

    def one(name, fn, operands, need):
        ms, results = timed(fn, operands, args.calls)
        row = {"ms": ms, "gb_per_s": need / ms / 1e6}
        print(name, json.dumps(row), flush=True)
        return row, results

    def sweep(name, operands, need, rows, **held):
        """A row of the table, or the compiler's refusal (a tile whose
        blocks pass the VMEM limit)."""
        label = " ".join("%s=%s" % item for item in sorted(held.items()))
        try:
            t0 = time.perf_counter()
            kernels[name].lower(*operands, **held, **interpret)
            seconds = time.perf_counter() - t0
            held["time"], _ = one(
                "%s %s" % (name, label), functools.partial(
                    kernels[name], **held, **interpret), operands, need)
            held["time"]["trace_lower_s"] = seconds
        except Exception as e:  # noqa: BLE001 - Mosaic's, by its own name
            held["refused"] = str(e).splitlines()[0][:200]
            print(name, label, "refused:", held["refused"], flush=True)
        rows.append(held)

    ints = lambda text: [int(x) for x in text.split(",")]
    for tile in ints(args.tiles):
        for chunk in ints(args.chunks):
            if chunk > tile:
                continue
            sweep("short_conv_fwd", (bcx, w), need_fwd, out["fwd"],
                  tile=tile, chunk=chunk)
            sweep("short_conv_bwd", (bcx, w, dy), need_bwd, out["bwd"],
                  tile=tile, chunk=chunk)

    operands = (bcx, w, dy)
    out["xla_fwd"], _ = one(
        "gated_short_conv_xla fwd", jax.jit(S.gated_short_conv_xla),
        (bcx, w), need_fwd)
    out["xla_fwd_vjp"], want = one(
        "gated_short_conv_xla fwd+vjp", gradient(S.gated_short_conv_xla),
        operands, need_fwd + need_bwd)
    out["pallas_fwd_vjp"], got = one(
        "gated_short_conv fwd+vjp", gradient(S.gated_short_conv), operands,
        need_fwd + need_bwd)
    # the checks on the sequence's first tokens (several row tiles):
    # the lines in float32 from the same values need four times the
    # memory
    del operands, want, got
    rows = args.check_tokens
    operands = (bcx[:, :rows], w, dy[:, :rows])
    want = gradient(S.gated_short_conv_xla)(*operands)
    got = gradient(S.gated_short_conv)(*operands)
    exact = gradient(S.gated_short_conv_xla)(
        *(x.astype(jnp.float32) for x in operands))
    checks = out["checks"] = {}
    compare("y", got[0], want[0], exact[0], checks)
    compare("dbcx", got[1][0], want[1][0], exact[1][0], checks)
    compare("dtaps", got[1][1], want[1][1], exact[1][1], checks)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/short_conv.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
