"""The rotation of q and k alone on one TPU chip at each cell's shape:
today's ``jax.numpy`` lines (``ops/rotary.py:rotate_xla``), the same
lines with ``cos`` and ``sin`` handed in (what the transcendentals cost
where they are formed again), and the kernel pair ``rotary_fwd`` /
``rotary_bwd`` with their table (``--interpret --shapes tiny --calls 1``
rehearses it on the CPU).

    python scripts/rotary.py          # on one TPU chip, ~3 min

A row a shape: ms a call and GB/s over the bytes a call NEEDS (the
lanes' 128-lane groups of q and k read once and written once) of the
lines forward and forward + VJP, the lines with the table handed in, the
kernels forward, backward and through their ``custom_vjp`` with the
table's forming, q and k as one call and as one each, and the seconds a
kernel takes to trace and lower. Every timed function is given its
operands to keep (``donate_argnums``) and fed its own results, so a
kernel that writes over its operand is timed without a copy in front of
it. At the first shape also: the rows and head blocks a grid step takes
and the rows an iteration takes (``--tiles``, ``--steps``, ``--chunks``),
and what the transposition to (B, H, S, d) costs alone and inside the
projection that writes it. The digest: the kernels' results against the
lines', element for element, forward; backward against the lines' VJP,
against the lines turned by the negated positions (the same arithmetic
the kernel does), and both against the lines in float32. Writes
``chiprun_out/rotary.json``.
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

from elasticdl_tpu.models.transformer import YarnScaling  # noqa: E402
from elasticdl_tpu.ops import rotary as R  # noqa: E402

YARN = dict(factor=64.0, original_max_position_embeddings=4096,
            beta_fast=64.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0)
# (cell and kind, batch, q heads, kv heads, tokens, head, lanes that
# rotate, positions given, YaRN)
SHAPES = {
    "cells": (
        ("ouro2.6b-s16k", 1, 16, 16, 16384, 128, 128, False, False),
        # the Pythia cells rotate the WHOLE 256-wide head (the
        # configuration's departure from rotary_pct 0.25); ISSUE 56 read
        # them as 64 of 256, which no cell of 8 heads runs
        ("pythia1b-s16k", 1, 8, 8, 16384, 256, 256, False, False),
        ("64 of 256 at pythia's heads", 1, 8, 8, 16384, 256, 64, False, False),
        ("laguna-xs2-s32k full", 1, 48, 8, 32768, 128, 64, False, True),
        ("laguna-xs2-s32k window", 1, 64, 8, 32768, 128, 128, False, False),
        ("sdar30b-bd-s8k", 1, 32, 4, 16384, 128, 128, True, False),
        ("qwen3next80b-s32k", 1, 16, 2, 32768, 256, 64, False, False),
        ("olmoe1b7b-s4k", 8, 16, 16, 4096, 128, 128, False, False),
    ),
    "tiny": (
        ("whole", 1, 4, 4, 256, 128, 128, False, False),
        ("partial", 2, 4, 2, 256, 256, 64, True, True),
    ),
}


def timed(fn, args, calls, feed=True):
    """ms a call of ``fn``; with ``feed`` it keeps its operands and is
    fed its own results (as many as it takes)."""
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*(out if feed else args))
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3, out


def lines_with_table(x, cos, sin, rotary_dim):
    """``rotate_xla``'s arithmetic with ``cos`` and ``sin`` (S, half)
    handed in."""
    half = rotary_dim // 2
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]],
        axis=-1).astype(x.dtype)


def unequal(got, want):
    """Elements of ``got`` that are not ``want``'s, and the largest
    difference."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return {"unequal": int(jnp.sum(got != want)), "of": got.size,
            "max": float(jnp.abs(got - want).max())}


def rms(got, exact):
    """The rms difference from ``exact`` over its rms entry."""
    got, exact = got.astype(jnp.float32), exact.astype(jnp.float32)
    return float(jnp.sqrt(
        jnp.mean((got - exact) ** 2) / (jnp.mean(exact ** 2) + 1e-30)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shapes", default="cells", choices=sorted(SHAPES))
    parser.add_argument("--tiles", default="256,512,1024")
    parser.add_argument("--steps", default="1,2,4,8,16")
    parser.add_argument("--chunks", default="64,128,256,512")
    parser.add_argument(
        "--first", type=int, default=None, help="the first shapes alone")
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument(
        "--interpret", action="store_true",
        help="run the kernels in the interpreter (a rehearsal on the CPU)")
    args = parser.parse_args(argv)
    interpret = {"interpret": True} if args.interpret else {}
    kernels = {"rotary_fwd": R.rotary_fwd, "rotary_bwd": R.rotary_bwd}
    if args.interpret:
        R.rotary_impl = lambda *a, **kw: "pallas"
        for name, kernel in kernels.items():
            setattr(R, name, functools.partial(kernel, **interpret))
    dtype = jnp.dtype(args.dtype)
    ints = lambda text: [int(x) for x in text.split(",")]
    out = {"device": jax.devices()[0].device_kind, "dtype": args.dtype,
           "shapes": []}
    keep = lambda fn, n: jax.jit(fn, donate_argnums=tuple(range(n)))

    def one(name, fn, operands, need, row, feed=True):
        ms, results = timed(fn, operands, args.calls, feed)
        row[name] = {"ms": ms, "gb_per_s": need / ms / 1e6}
        print(" ", name, json.dumps(row[name]), flush=True)
        return results

    for index, (cell, batch, heads, kv_heads, seq, dim, lanes, given,
                yarn) in enumerate(SHAPES[args.shapes][:args.first]):
        keys = jax.random.split(jax.random.PRNGKey(index), 4)
        make = lambda key, h: jax.random.normal(
            key, (batch, h, seq, dim), jnp.float32).astype(dtype)
        q, k = make(keys[0], heads), make(keys[1], kv_heads)
        gq, gk = make(keys[2], heads), make(keys[3], kv_heads)
        # block diffusion's two copies of one sequence
        positions = (
            jnp.tile(jnp.arange(seq // 2, dtype=jnp.int32), 2) if given
            else None)
        rope = dict(base=1e6, positions=positions,
                    scaling=YarnScaling(**YARN) if yarn else None)
        rotary_dim = None if lanes == dim else lanes
        width = R.lane_groups(lanes)
        need = 2 * (q.size + k.size) * dtype.itemsize * width // dim
        row = {"cell": cell, "q": list(q.shape), "kv_heads": kv_heads,
               "lanes": lanes, "positions": given, "yarn": yarn,
               "bytes": need, "impl": R.rotary_impl(
                   dtype, dim, lanes, seq), "block": R.step_block(
                   seq, (heads, kv_heads), width, dtype.itemsize)}
        print(json.dumps(row), flush=True)
        lines = lambda q, k: (R.rotate_xla(q, rotary_dim, **rope),
                              R.rotate_xla(k, rotary_dim, **rope))
        table = lambda: R.rotary_table(seq, lanes, width, **rope)
        pair = lambda q, k: R._rotate_pallas((q, k), *table(), lanes)

        def gradient(fn):
            def run(q, k, gq, gk):
                y, vjp = jax.vjp(fn, q, k)
                return tuple(y) + tuple(vjp((gq, gk)))
            return run

        # the digest first, on fresh operands
        want = jax.jit(gradient(lines))(q, k, gq, gk)
        got = jax.jit(gradient(pair))(q, k, gq, gk)
        wide = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
        exact = jax.jit(gradient(lines))(*wide(q, k, gq, gk))
        back = dict(rope, positions=-(
            jnp.arange(seq) if positions is None else positions))
        turned_back = jax.jit(
            lambda gq, gk: (R.rotate_xla(gq, rotary_dim, **back),
                            R.rotate_xla(gk, rotary_dim, **back)))(gq, gk)
        row["digest"] = {
            name: dict(
                unequal(got[i], want[i]),
                kernel_vs_float32=rms(got[i], exact[i]),
                lines_vs_float32=rms(want[i], exact[i]),
                **({"unequal_to_lines_turned_back": unequal(
                    got[i], turned_back[i - 2])["unequal"]} if i > 1 else {}))
            for i, name in enumerate(("q", "k", "dq", "dk"))}
        print("  digest", json.dumps(row["digest"]), flush=True)
        del want, got, exact, turned_back

        cos, sin = jax.jit(table)()
        half_cos, half_sin = jax.jit(functools.partial(
            R.cos_sin, seq, lanes, rope["base"], positions, rope["scaling"],
            (1, 1, seq, lanes // 2)))()
        fwd = lambda *xs, **held: tuple(R.rotary_fwd(
            xs, cos, sin, lanes, **held))
        bwd = lambda *xs, **held: tuple(R.rotary_bwd(
            xs, cos, sin, lanes, **held))
        row["trace_lower_s"] = {}
        for name in kernels:
            t0 = time.perf_counter()
            kernels[name].lower((q, k), cos, sin, lanes, **interpret)
            row["trace_lower_s"][name] = time.perf_counter() - t0
        print("  trace_lower_s", json.dumps(row["trace_lower_s"]), flush=True)
        q, k = one("lines fwd", keep(lines, 2), (q, k), need, row)
        q, k = one(
            "lines fwd, the kernel's form (jnp.roll, its table)", keep(
                lambda q, k: tuple(jnp.concatenate([
                    x[..., :width] * cos + jnp.roll(
                        x[..., :width].astype(jnp.float32), lanes // 2, -1)
                    * sin, x[..., width:]], -1).astype(x.dtype)
                    for x in (q, k)), 2), (q, k), need, row)
        q, k = one(
            "lines fwd, table handed in", keep(
                lambda q, k: tuple(lines_with_table(
                    x, half_cos, half_sin, lanes) for x in (q, k)), 2),
            (q, k), need, row)
        q, k, gq, gk = one(
            "lines fwd+vjp", keep(gradient(lines), 4), (q, k, gq, gk),
            2 * need, row)
        q, k = one("rotary_fwd", keep(fwd, 2), (q, k), need, row)
        q, k = one(
            "rotary_fwd q, k apart", keep(
                lambda q, k: fwd(q) + fwd(k), 2), (q, k), need, row)
        q, k = one("rotary_bwd", keep(bwd, 2), (q, k), need, row)
        q, k, gq, gk = one(
            "rotate fwd+vjp, the table formed", keep(gradient(pair), 4),
            (q, k, gq, gk), 2 * need, row)
        if index == 0:
            row["sweep"] = []
            for tile in ints(args.tiles):
                for steps in ints(args.steps):
                    for chunk in ints(args.chunks):
                        if (chunk > tile or heads % steps
                                or kv_heads % steps):
                            continue
                        opts = dict(tile=tile, steps=steps, chunk=chunk)
                        held = dict(opts)
                        label = " ".join(
                            "%s=%d" % item for item in opts.items())
                        try:
                            t0 = time.perf_counter()
                            kernels["rotary_fwd"].lower(
                                (q, k), cos, sin, lanes, **opts, **interpret)
                            held["trace_lower_s"] = time.perf_counter() - t0
                            for name, fn in (("fwd", fwd), ("bwd", bwd)):
                                q, k = one(
                                    "rotary_%s %s" % (name, label), keep(
                                        functools.partial(fn, **opts), 2),
                                    (q, k), need, held)
                        except Exception as e:  # noqa: BLE001 - Mosaic's
                            held["refused"] = str(e).splitlines()[0][:200]
                            print("  ", label, "refused:", held["refused"],
                                  flush=True)
                        row["sweep"].append(held)
            # the transposition: alone, and inside the projection
            model = heads * dim
            x = jax.random.normal(
                keys[0], (batch, seq, model), jnp.float32).astype(dtype)
            w = (jax.random.normal(keys[1], (model, heads, dim), jnp.float32)
                 * model ** -0.5).astype(dtype)
            t = jnp.swapaxes(q, 1, 2)
            moved = need // 2
            one("transposition alone (B, S, H, d) -> (B, H, S, d)",
                jax.jit(lambda t: jnp.swapaxes(t, 1, 2)), (t,), moved, row,
                feed=False)
            for layout in ("bshk", "bhsk"):
                one("projection writing %s" % layout, jax.jit(
                    lambda x, w, layout=layout: jnp.einsum(
                        "bsd,dhk->" + layout, x, w)), (x, w), moved, row,
                    feed=False)
            del x, w, t
        out["shapes"].append(row)
        del q, k, gq, gk, cos, sin, half_cos, half_sin
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/rotary.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
