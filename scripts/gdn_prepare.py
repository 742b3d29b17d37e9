"""The chunks' operands of the gated delta rule alone on one TPU chip at
the Qwen3-Next cell's shape (a segment: 16 key / 32 value heads x 128
chunks x 64 x 128, bfloat16): each ``gdn_prepare_*`` kernel alone, the
XLA lines they stand for alone, and the rule's forward and gradient both
ways.

    python scripts/gdn_prepare.py            # on one TPU chip, ~3 min

Times, ms a call: ``gdn_prepare_fwd`` (as the step's forward calls it
and, with ``T`` written, as the segment's recompute does) and
``gdn_prepare_bwd`` with the bytes their operands and results hold and
their GB/s; ``_chunk_operands`` with the casts ``_scan_pallas`` adds
(the inverses the product form by XLA), its forward and its forward +
VJP; then ``gated_delta_rule`` at 32,768
tokens, forward and gradient, as ``prepare_impl`` chooses on the chip
(``prep=pallas``) and with the choice held to the XLA lines
(``prep=xla``). Checks the kernels' results and gradients against the
XLA lines' ON the chip (the interpreter's float32 products are exact,
the MXU's are not). ``--chunk 128`` for the other chunk. Writes
``chiprun_out/gdn_prepare.json``.
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

from elasticdl_tpu.ops import gated_delta as G  # noqa: E402

HK, REP, DIM, TOKENS = 16, 2, 128, 32768


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


def rule_inputs(tokens, seed=0):
    """q, k l2-normalised (q scaled), g and beta as the layer's gates
    give them at initialisation, bfloat16 / float32 as the cell has
    them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (1, HK, tokens, DIM))) * DIM ** -0.5
    k = unit(jax.random.normal(keys[1], (1, HK, tokens, DIM)))
    v = jax.random.normal(keys[2], (1, HK * REP, tokens, DIM))
    g = -jax.random.uniform(keys[3], (1, HK * REP, tokens)) * 0.5
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, HK * REP, tokens)))
    return tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)


def segment_operands(chunk):
    num = G.DEFAULT_SEGMENT
    q, k, v, g, beta = rule_inputs(num * chunk)
    split = lambda x, heads, *rest: x.reshape(
        (1,) + heads + (num, chunk) + rest)
    return (split(q, (HK, 1), DIM), split(k, (HK, 1), DIM),
            split(v, (HK, REP), DIM), split(g, (HK, REP)),
            split(beta, (HK, REP)))


def xla_lines(q, k, v, g, beta):
    """What ``_chunks`` hands ``gdn_scan_fwd`` with ``prep=xla``."""
    return G._scan_operands(
        *G._chunk_operands(q, k, v, g, beta, jnp.float32), q.dtype)


def kernels_alone(chunk, calls):
    args = segment_operands(chunk)
    dtype = args[0].dtype
    out = {"block": G.prepare_block(REP, G.DEFAULT_SEGMENT, chunk, DIM, DIM,
                                    dtype.itemsize)}

    def one(name, fn, *operands):
        ms, results = timed(fn, operands, calls)
        moved = size(operands, results)
        out[name] = {"ms": ms, "bytes": moved, "gb_per_s": moved / ms / 1e6}
        print(name, json.dumps(out[name]), flush=True)
        return results

    one("gdn_prepare_fwd", jax.jit(G.gdn_prepare_fwd), *args)
    *results, inverse = one(
        "gdn_prepare_fwd_residuals",
        jax.jit(functools.partial(G.gdn_prepare_fwd, residuals=True)), *args)
    keys = jax.random.split(jax.random.PRNGKey(7), len(results))
    cotangents = [
        jax.random.normal(key, x.shape, jnp.float32).astype(x.dtype)
        for key, x in zip(keys, results)]
    # du arrives in the compute dtype
    low = cotangents[:-1] + [cotangents[-1].astype(dtype)]
    grads = one("gdn_prepare_bwd", jax.jit(G.gdn_prepare_bwd), *args,
                inverse, *low)

    forward = jax.jit(xla_lines)
    ms, want = timed(forward, args, calls)
    out["xla_lines_fwd"] = {"ms": ms}
    vjp = jax.jit(lambda *a: jax.vjp(xla_lines, *a[:5])[1](tuple(a[5:])))
    cotangents[-1] = low[-1].astype(jnp.float32)
    ms, want_grads = timed(vjp, args + tuple(cotangents), calls)
    out["xla_lines_fwd_and_vjp"] = {"ms": ms}
    print("xla lines", json.dumps(
        {k: out[k] for k in ("xla_lines_fwd", "xla_lines_fwd_and_vjp")}),
        flush=True)
    names = ("decay", "w", "k_onto", "q_into", "p", "u")
    out["kernels_against_xla"] = dict(
        {n: relative(a, b) for n, a, b in zip(names, results, want)},
        **{n: relative(a, b) for n, a, b in zip(
            ("dq", "dk", "dv", "dg", "dbeta"), grads, want_grads)})
    print("kernels against xla", json.dumps(out["kernels_against_xla"]),
          flush=True)
    return out


def rule_both_ways(chunk, calls):
    args = rule_inputs(TOKENS)
    chosen = G.prepare_impl
    out, kept = {}, {}
    for name, impl in (("pallas", chosen), ("xla", lambda *a, **kw: "xla")):
        G.prepare_impl = impl
        try:
            forward = jax.jit(functools.partial(G.gated_delta_rule,
                                                chunk=chunk))
            grad = jax.jit(jax.grad(
                lambda *a: (G.gated_delta_rule(*a, chunk=chunk).astype(
                    jnp.float32) ** 2).sum(), argnums=(0, 1, 2, 3, 4)))
            ms, o = timed(forward, args, calls)
            out["forward_prep_%s_ms" % name] = ms
            ms, grads = timed(grad, args, calls)
            out["grad_prep_%s_ms" % name] = ms
            kept[name] = (o,) + tuple(grads)
        finally:
            G.prepare_impl = chosen
    out["pallas_against_xla"] = {
        n: relative(a, b) for n, a, b in zip(
            ("o", "dq", "dk", "dv", "dg", "dbeta"), kept["pallas"],
            kept["xla"])}
    print("rule", json.dumps(out), flush=True)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--chunk", type=int, default=G.DEFAULT_CHUNK)
    parser.add_argument("--calls", type=int, default=20)
    args = parser.parse_args(argv)
    impl = G.prepare_impl(
        jnp.bfloat16, args.chunk, DIM, DIM, REP, G.DEFAULT_SEGMENT)
    print("prepare_impl", impl, jax.devices()[0].device_kind, flush=True)
    if impl != "pallas":
        raise SystemExit("the kernels are not chosen on this backend")
    results = {"kernels": kernels_alone(args.chunk, args.calls),
               "rule": rule_both_ways(args.chunk, max(3, args.calls // 4))}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gdn_prepare.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "chunk": args.chunk,
                   "shape": [1, HK, REP, G.DEFAULT_SEGMENT, args.chunk, DIM],
                   **results}, f, indent=1)


if __name__ == "__main__":
    main()
