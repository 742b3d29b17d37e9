"""The chunks' operands of the delta rule with a decay a channel alone on
one TPU chip at the Kimi Linear cell's shape (a segment: 32 heads x 64
chunks x 64 x 128, bfloat16 operands, the float32 decay a channel): each
``kda_prepare_*`` kernel alone, the XLA lines they stand for alone, and
the rule's forward and gradient both ways.

    python scripts/kda_prepare.py            # on one TPU chip, ~4 min

Each kernel is timed INSIDE one program: ``REPEATS`` calls in one
``fori_loop``, each reading the last one's ``beta`` (the host takes ~0.4
ms to launch a program, as long as a kernel runs). Reported a kernel:
ms a call, the bytes its operands and results hold and their GB/s, us a
chunk and head, and the vector unit's share: the elementwise and
reduced float32 / int32 elements its body computes a call (counted from
the kernel's own jaxpr, a loop's body by its trips) over ms x the unit's
rate as ASSUMED here, 4 ALUs x 1,024 lanes x 1.5 GHz (the clock at which
four 128 x 128 MXUs give the published 197 TFLOP/s). Then
``_chunk_operands_by_channel`` with the casts ``_scan_pallas_by_channel``
adds, forward and forward + VJP, and ``gated_delta_rule`` at 32,768
tokens, forward and gradient, as ``prepare_impl`` chooses on the chip
(``prep=pallas``) and with the choice held to the XLA lines
(``prep=xla``). Checks the kernels' results and gradients against the XLA
lines' ON the chip. Writes ``chiprun_out/kda_prepare.json``.
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

HEADS, DIM, TOKENS, SEGMENT = 32, 128, 32768, 64
REPEATS = 10
VECTOR_RATE = 4 * 1024 * 1.5e9  # elements a second, assumed (docstring)
_ELEMENTWISE = {
    "add", "sub", "mul", "neg", "exp", "select_n", "convert_element_type",
    "max", "min", "and", "or", "eq", "ne", "ge", "gt", "le", "lt",
    "shift_right_logical", "div", "rem", "roll", "broadcast_in_dim",
    "concatenate", "transpose",
}


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


def vector_elements(jaxpr, trips=1):
    """(elements of elementwise / reduced work, matmul FLOPs) a jaxpr
    computes, a ``scan``'s body by its length."""
    elements = flops = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        inner = [v for v in eqn.params.values()
                 if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        for sub in inner:
            sub = sub if hasattr(sub, "eqns") else sub.jaxpr
            e, f = vector_elements(
                sub, trips * eqn.params.get("length", 1))
            elements, flops = elements + e, flops + f
        if name in _ELEMENTWISE:
            elements += trips * max(v.aval.size for v in eqn.outvars)
        elif name.startswith("reduce_"):
            elements += trips * eqn.invars[0].aval.size
        elif name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            depth = np.prod([eqn.invars[0].aval.shape[i] for i in contract])
            flops += trips * 2 * eqn.outvars[0].aval.size * int(depth)
    return elements, flops


def kernel_work(fn, *args):
    """``vector_elements`` of the one ``pallas_call`` ``fn`` traces to,
    a grid step's body times the grid."""
    for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns:
        inner = eqn.params.get("jaxpr")
        while inner is not None and eqn.primitive.name != "pallas_call":
            inner = getattr(inner, "jaxpr", inner)
            eqn, = [e for e in inner.eqns if "jaxpr" in e.params]
            inner = eqn.params["jaxpr"]
        if eqn.primitive.name == "pallas_call":
            grid = int(np.prod(eqn.params["grid_mapping"].grid))
            elements, flops = vector_elements(eqn.params["jaxpr"])
            return grid * elements, grid * flops
    raise ValueError("no pallas_call")


def rule_inputs(tokens, seed=0):
    """q, k l2-normalised (q scaled), the log decay a channel and beta
    as the layer's gates give them at initialisation, bfloat16 / float32
    as the cell has them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    wide = (1, HEADS, tokens, DIM)
    q = unit(jax.random.normal(keys[0], wide)) * DIM ** -0.5
    k = unit(jax.random.normal(keys[1], wide))
    v = jax.random.normal(keys[2], wide)
    g = -jax.random.uniform(keys[3], wide) * 0.5
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], wide[:3]))
    return tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)


def segment_operands(chunk):
    q, k, v, g, beta = rule_inputs(SEGMENT * chunk)
    split = lambda x, heads, *rest: x.reshape(
        (1,) + heads + (SEGMENT, chunk) + rest)
    return (split(q, (HEADS, 1), DIM), split(k, (HEADS, 1), DIM),
            split(v, (HEADS, 1), DIM), split(g, (HEADS, 1), DIM),
            split(beta, (HEADS, 1)))


def xla_lines(q, k, v, g, beta):
    """What ``_chunks`` hands ``kda_scan_fwd`` with ``prep=xla``."""
    last, w, k_onto, q_into, attn, u = G._chunk_operands_by_channel(
        q, k, v, g, beta, jnp.float32)
    return (jnp.exp(last)[..., None, :], w, k_onto, q_into.astype(q.dtype),
            attn.astype(q.dtype), u)


def kernels_alone(chunk, calls):
    args = segment_operands(chunk)
    dtype = args[0].dtype
    heads = HEADS * SEGMENT
    out = {"block": G.kda_prepare_block(1, SEGMENT, chunk, DIM, DIM,
                                        dtype.itemsize)}

    def one(name, fn, *operands):
        """``fn(*operands)``'s results; the loop carries ``beta`` (the
        fifth operand) through a value the compiler cannot fold, the
        others are the program's arguments (closed over they would be
        its constants: 0.8 GB of executable)."""
        tie = lambda beta, results: beta + 0.0 * results[-1].reshape(
            -1)[0].astype(beta.dtype)
        call = lambda beta, rest: fn(*rest[:4], beta, *rest[4:])

        def many(beta, *rest):
            beta = jax.lax.fori_loop(
                0, REPEATS, lambda _, b: tie(b, call(b, rest)), beta)
            return call(beta, rest)

        ms, results = timed(
            jax.jit(many), (operands[4],) + operands[:4] + operands[5:],
            calls)
        ms /= REPEATS + 1
        moved = size(operands, results)
        elements, flops = kernel_work(fn, *operands)
        out[name] = {
            "ms": ms, "bytes": moved, "gb_per_s": moved / ms / 1e6,
            "us_a_chunk_and_head": ms * 1e3 / heads,
            "vector_elements": elements, "matmul_flops": flops,
            "vector_share_assumed_rate": elements / (ms / 1e3) / VECTOR_RATE}
        print(name, json.dumps(out[name]), flush=True)
        return results

    one("kda_prepare_fwd", G.kda_prepare_fwd, *args)
    *results, inverse = one(
        "kda_prepare_fwd_residuals",
        functools.partial(G.kda_prepare_fwd, residuals=True), *args)
    keys = jax.random.split(jax.random.PRNGKey(7), len(results))
    cotangents = [
        jax.random.normal(key, x.shape, jnp.float32).astype(x.dtype)
        for key, x in zip(keys, results)]
    # du arrives in the compute dtype
    low = cotangents[:-1] + [cotangents[-1].astype(dtype)]
    grads = one("kda_prepare_bwd", G.kda_prepare_bwd, *args, inverse, *low)

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
    finite = lambda xs: bool(all(
        np.isfinite(np.asarray(x, np.float32)).all() for x in xs))
    out["finite"] = finite(results) and finite(grads)
    out["kernels_against_xla"] = dict(
        {n: relative(a, b) for n, a, b in zip(names, results, want)},
        **{n: relative(a, b) for n, a, b in zip(
            ("dq", "dk", "dv", "dg", "dbeta"), grads, want_grads)})
    print("kernels against xla", json.dumps(out["kernels_against_xla"]),
          "finite", out["finite"], flush=True)
    return out


def rule_both_ways(chunk, calls):
    args = rule_inputs(TOKENS)
    chosen = G.prepare_impl
    out, kept = {}, {}
    rule = functools.partial(G.gated_delta_rule, chunk=chunk, segment=SEGMENT)
    for name, impl in (("pallas", chosen), ("xla", lambda *a, **kw: "xla")):
        G.prepare_impl = impl
        try:
            forward = jax.jit(lambda *a: rule(*a))
            grad = jax.jit(jax.grad(
                lambda *a: (rule(*a).astype(jnp.float32) ** 2).sum(),
                argnums=(0, 1, 2, 3, 4)))
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
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--skip-rule", action="store_true")
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes, the kernels interpreted, any backend: the "
             "script's own control flow, no number of it means anything")
    args = parser.parse_args(argv)
    if args.rehearse:
        global HEADS, SEGMENT, TOKENS, REPEATS
        HEADS, SEGMENT, REPEATS = 2, 8, 1
        TOKENS = 2 * SEGMENT * args.chunk
        jax.default_backend = lambda: "tpu"
        for name in ("gdn_scan_fwd", "gdn_scan_bwd", "kda_prepare_fwd",
                     "kda_prepare_bwd"):
            setattr(G, name, functools.partial(
                getattr(G, name), interpret=True))
    impl = G.prepare_impl(
        jnp.bfloat16, args.chunk, DIM, DIM, 1, SEGMENT,
        decay_rank=G.VECTOR_DECAY)
    print("prepare_impl", impl, jax.devices()[0].device_kind, flush=True)
    if impl != "pallas":
        raise SystemExit("the kernels are not chosen on this backend")
    results = {"kernels": kernels_alone(args.chunk, args.calls)}
    if not args.skip_rule:
        results["rule"] = rule_both_ways(args.chunk, max(2, args.calls // 2))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_prepare.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "chunk": args.chunk,
                   "shape": [1, HEADS, 1, SEGMENT, args.chunk, DIM],
                   **results}, f, indent=1)


if __name__ == "__main__":
    main()
