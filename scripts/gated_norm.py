"""The gated output norm of the linear and state-space mixers alone on
one TPU chip at each cell's shape: today's ``jax.numpy`` lines (the
``*/out_norm`` scopes of ``models/transformer.py``, copied here word
for word) against the kernel pair ``gated_norm_fwd`` /
``gated_norm_bwd`` (``ops/gated_norm.py``); ``--interpret --shapes tiny
--calls 1`` rehearses it on the CPU.

    python scripts/gated_norm.py          # on one TPU chip, ~4 min

A row a shape: ms a call and GB/s over the bytes a call NEEDS (forward:
the rule's output and the gate's columns read once, the result written
once; backward: those two and the cotangent read, ``dx`` and ``dz``
written) of the lines forward and forward + VJP, the kernels forward,
backward and through their ``custom_vjp`` (the gate's pad and the
scale's sum included), the seconds a kernel takes to trace and lower,
and the digest: the pair's results against the lines', unequal
elements and the largest difference in roundings of the result's dtype,
and both against the lines in float32. Then the sweep: rows and normed
segments a grid step takes and rows an iteration of the loop takes
(``--tiles``, ``--groups``, ``--chunks``), each kernel. Writes
``chiprun_out/gated_norm.json``.
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

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from elasticdl_tpu.ops import gated_norm as G  # noqa: E402
from elasticdl_tpu.ops.qkv_conv import _whole, rule_segments  # noqa: E402

# (cell, form, batch, tokens, normed segments, lanes of one, the gate's
# array's width, the gate's first column, eps, rows of a chunk where the
# operands lie by columns (``ops/gated_norm.py:gated_norm``), else None)
SHAPES = {
    "cells": (
        ("granite4h-micro-s8k", "silu_norm", 1, 8192, 1, 4096, 8512, 0,
         1e-5, 256),
        ("nemotron3-nano-s8k", "silu_norm", 1, 8192, 8, 512, 10304, 0,
         1e-5, 256),
        ("qwen3next80b-s32k", "norm_silu", 1, 32768, 32, 128, 12288, 8192,
         1e-6, None),
        ("kimi-linear48b-s32k", "norm_sigmoid", 1, 32768, 32, 128, 4096, 0,
         1e-5, None),
    ),
    "tiny": (
        ("whole row", "silu_norm", 1, 256, 1, 256, 384, 0, 1e-5, 128),
        ("groups", "silu_norm", 2, 256, 4, 128, 640, 0, 1e-5, 128),
        ("heads silu", "norm_silu", 1, 256, 4, 128, 1024, 512, 1e-6, None),
        ("heads sigmoid", "norm_sigmoid", 1, 256, 2, 128, 256, 0, 1e-5,
         None),
    ),
}


# ------------------------------ the modules' lines, word for word

class _Lines(nn.Module):
    """``nn.RMSNorm`` as ``GatedDeltaNet`` and ``KimiDeltaAttention``
    call it, with the gate's line after it; ``scale`` is handed in as
    the module's variable."""

    form: str
    eps: float

    @nn.compact
    def __call__(self, o, z):
        dtype = z.dtype
        o = nn.RMSNorm(epsilon=self.eps, name="out_norm")(
            o.transpose(0, 2, 1, 3))  # (B, S, H, D)
        z = z.reshape(o.shape).astype(jnp.float32)
        gate = nn.silu(z) if self.form == "norm_silu" else jax.nn.sigmoid(z)
        return (o.astype(jnp.float32) * gate).astype(dtype)


def delta_lines(o, z, scale, form, lanes, eps, z_offset):
    batch, heads, seq, dim = o.shape
    out = _Lines(form, eps).apply(
        {"params": {"out_norm": {"scale": scale}}}, o,
        z[..., z_offset:z_offset + heads * dim])
    return out.reshape(batch, seq, heads * dim)


def mamba_lines(y, zxbcdt, scale, form, lanes, eps, z_offset):
    batch, seq, inner = y.shape
    groups = inner // lanes
    gated = (
        y.astype(jnp.float32).reshape(batch, seq, inner)
        * nn.silu(zxbcdt[..., :inner].astype(jnp.float32)))
    lanes = gated.reshape(batch, seq, groups, inner // groups)
    var = jnp.mean(lanes * lanes, axis=-1, keepdims=True)
    return ((lanes * jax.lax.rsqrt(var + eps)).reshape(
        gated.shape) * scale).astype(y.dtype)


def lines_of(form):
    return mamba_lines if form == "silu_norm" else delta_lines


# ------------------------------------------------------- the timing

def timed(fn, args, calls):
    """ms a call of ``fn`` on ``args``."""
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3, out


def apart(got, want):
    """Elements of ``got`` that are not ``want``'s, and the largest
    difference in roundings of the dtype at the wanted magnitude (the
    array's mean magnitude at the least: a small element is a
    difference of large ones)."""
    eps = float(jnp.finfo(want.dtype).eps)
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    size = jnp.maximum(jnp.abs(want), jnp.abs(want).mean())
    return {"unequal": int(jnp.sum(got != want)), "of": got.size,
            "roundings": float((jnp.abs(got - want) / size).max() / eps)}


def rms(got, exact):
    """The rms difference from ``exact`` over its rms entry."""
    got, exact = got.astype(jnp.float32), exact.astype(jnp.float32)
    return float(jnp.sqrt(
        jnp.mean((got - exact) ** 2) / (jnp.mean(exact ** 2) + 1e-30)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shapes", default="cells", choices=sorted(SHAPES))
    parser.add_argument("--tiles", default="256,512,1024")
    parser.add_argument("--groups", default="1,2,4,8")
    parser.add_argument("--chunks", default="16,32,64,128,256")
    parser.add_argument("--channels", default="16,32,64,128")
    parser.add_argument(
        "--first", type=int, default=None, help="the first shapes alone")
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument(
        "--no-sweep", action="store_true", help="the chosen block alone")
    parser.add_argument(
        "--interpret", action="store_true",
        help="run the kernels in the interpreter (a rehearsal on the CPU)")
    args = parser.parse_args(argv)
    interpret = {"interpret": True} if args.interpret else {}
    kernels = {"gated_norm_fwd": G.gated_norm_fwd,
               "gated_norm_bwd": G.gated_norm_bwd}
    if args.interpret:
        for name, kernel in kernels.items():
            setattr(G, name, functools.partial(kernel, **interpret))
    dtype = jnp.dtype(args.dtype)
    ints = lambda text: [int(x) for x in text.split(",")]
    out = {"device": jax.devices()[0].device_kind, "dtype": args.dtype,
           "shapes": []}

    def one(name, fn, operands, need, row):
        ms, results = timed(fn, operands, args.calls)
        row[name] = {"ms": ms, "gb_per_s": need / ms / 1e6}
        print(" ", name, json.dumps(row[name]), flush=True)
        return results

    for index, (cell, form, batch, seq, segments, lanes, z_width, z_offset,
                eps, rows) in enumerate(SHAPES[args.shapes][:args.first]):
        keys = jax.random.split(jax.random.PRNGKey(index), 4)
        width = segments * lanes
        columns = rows is not None
        normal = lambda key, shape: jax.random.normal(
            key, shape, jnp.float32)
        # the operands as the kernels take them; ``whole`` turns them to
        # what the module's lines take, (B, H, S, D) or (B, S, C) and
        # (B, S, W), and ``turned`` a result (B, S, C) to the kernels'
        if columns:
            x = normal(keys[0], (batch, seq // rows, width, rows))
            z = normal(keys[1], (batch, z_width, seq))
            whole = lambda x, z: (
                jnp.swapaxes(x, 2, 3).reshape(batch, seq, width),
                jnp.swapaxes(z, 1, 2))
            turned = lambda a: jnp.swapaxes(a, 1, 2)
        else:
            # by the rule's segments, as the cells' rules write it
            runs = rule_segments(seq, 64)
            x = normal(keys[0], (runs, batch, segments, seq // runs, lanes))
            z = normal(keys[1], (batch, seq, z_width))
            whole = lambda x, z: (_whole(x), z)
            turned = lambda a: a
        x, z = x.astype(dtype), z.astype(dtype)
        scale = (1.0 + 0.1 * normal(
            keys[2], (width if columns else lanes,))).astype(dtype)
        grad = turned(normal(keys[3], (batch, seq, width))).astype(dtype)
        size = batch * seq * width * dtype.itemsize
        need = {"fwd": 3 * size, "bwd": 5 * size, "both": 8 * size}
        static = (form, lanes, eps, z_offset, columns)
        block = G._block(
            lanes, segments, seq, dtype.itemsize, z_offset, rows,
            1 if columns else x.shape[0])
        row = {"cell": cell, "form": form, "x": list(x.shape),
               "z": list(z.shape), "z_offset": z_offset, "lanes": lanes,
               "rows": rows, "bytes": need, "block": block,
               "impl": G.gated_norm_impl(
                   dtype, lanes, segments, seq, None, z_offset, rows,
                   1 if columns else x.shape[0])}
        print(json.dumps(row), flush=True)
        by_lines = functools.partial(
            lines_of(form), form=form, lanes=lanes, eps=eps,
            z_offset=z_offset)

        def lines(x, z, scale):
            return turned(by_lines(*whole(x, z), scale))

        pair = lambda x, z, scale: G._pair(
            x, z, scale, *static, "cell/out_norm")

        def gradient(fn):
            def run(x, z, scale, grad):
                y, vjp = jax.vjp(fn, x, z, scale)
                return (y,) + tuple(vjp(grad))
            return jax.jit(run)

        # the digest first
        want = gradient(lines)(x, z, scale, grad)
        got = gradient(pair)(x, z, scale, grad)
        wide = lambda *xs: tuple(a.astype(jnp.float32) for a in xs)
        exact = gradient(lines)(*wide(x, z, scale, grad))
        row["digest"] = {
            name: dict(
                apart(got[i], want[i]),
                kernel_vs_float32=rms(got[i], exact[i]),
                lines_vs_float32=rms(want[i], exact[i]))
            for i, name in enumerate(("out", "dx", "dz", "dscale"))}
        print("  digest", json.dumps(row["digest"]), flush=True)
        del want, got, exact

        row["trace_lower_s"] = {}
        for name, operands in (("gated_norm_fwd", (x, z, scale)),
                               ("gated_norm_bwd", (x, z, scale, grad))):
            t0 = time.perf_counter()
            kernels[name].lower(*operands, *static, **interpret)
            row["trace_lower_s"][name] = time.perf_counter() - t0
        print("  trace_lower_s", json.dumps(row["trace_lower_s"]), flush=True)
        fwd = lambda x, z, scale, **held: G.gated_norm_fwd(
            x, z, scale, *static, **held)
        bwd = lambda x, z, scale, grad, **held: G.gated_norm_bwd(
            x, z, scale, grad, *static, **held)
        # the lines on operands that LIE as the module's do
        lx, lz = jax.jit(whole)(x, z)
        lgrad = jax.jit(lambda a: jnp.swapaxes(a, 1, 2) if columns else a)(
            grad)
        one("lines fwd", jax.jit(by_lines), (lx, lz, scale), need["fwd"], row)
        one("lines fwd+vjp", gradient(by_lines), (lx, lz, scale, lgrad),
            need["both"], row)
        del lx, lz, lgrad
        one("gated_norm_fwd", fwd, (x, z, scale), need["fwd"], row)
        one("gated_norm_bwd", bwd, (x, z, scale, grad), need["bwd"], row)
        one("gated_norm fwd+vjp", gradient(pair), (x, z, scale, grad),
            need["both"], row)
        row["sweep"] = []
        # by heads: rows an iteration and heads a step at the chosen
        # rows a step, then the rows a step at the chosen two; by
        # columns: a group's channels an iteration and groups a step
        if args.no_sweep:
            combos = []
        elif columns:
            combos = [(None, group, chunk) for group in ints(args.groups)
                      for chunk in ints(args.channels)]
        else:
            combos = [(block[0], group, chunk) for group in ints(args.groups)
                      for chunk in ints(args.chunks)] + [
                (tile, block[1], None) for tile in ints(args.tiles)
                if tile != block[0]]
        for tile, group, chunk in combos:
            wide = group * lanes
            if columns:
                skip = 20 * rows * wide * dtype.itemsize > 40 * 2**20
            else:
                skip = (seq % tile or (chunk or 0) > tile
                        or (chunk or 0) * wide > 2**17 or wide > 1024
                        or 20 * tile * wide * dtype.itemsize > 40 * 2**20)
            if skip or segments % group or z_offset % wide:
                continue
            opts = dict(tile=tile, group=group, chunk=chunk)
            held = dict(opts)
            label = " ".join("%s=%s" % item for item in opts.items())
            try:
                one("fwd " + label, functools.partial(fwd, **opts),
                    (x, z, scale), need["fwd"], held)
                one("bwd " + label, functools.partial(bwd, **opts),
                    (x, z, scale, grad), need["bwd"], held)
            except Exception as e:  # noqa: BLE001 - Mosaic's
                held["refused"] = str(e).splitlines()[0][:200]
                print("  ", label, "refused:", held["refused"], flush=True)
            row["sweep"].append(held)
        out["shapes"].append(row)
        del x, z, scale, grad
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gated_norm.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out)[-20000:])


if __name__ == "__main__":
    main()
