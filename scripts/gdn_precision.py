"""The Qwen3-Next configuration's reference check over several seeds and
under two lower precisions of the chunked gated delta rule, and the
chunk-to-chunk scan alone and the rule alone against the clock, as this
backend runs them and by XLA (one chip, ~20 min).

    python scripts/gdn_precision.py --seeds 8 --variant-seeds 2

For each seed it draws the cell's sample and the model's weights as
``benchmark/lib/refcheck.py`` does and prints every compared name's
relative error (``check.py``'s names; ``--leaves`` compares other
gradients than the configuration's ``check_leaves``). Variants: the
scan's state carried in bfloat16 (``state_dtype``), the decay cumulated
in bfloat16 (``decay_dtype``): what the tolerances have to tell from the
stated precision (PERF.md Section 6, PR 31). Everything goes to
``chiprun_out/gdn_precision.json`` too.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmark/configs/qwen3-next-80b-a3b-1chip/config.json"
CELL = "benchmark/workloads/qwen3next80b-s32k.json"
TRAFFIC = "benchmark/traffic/s32k-b1.json"


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _clock(jax, fn, args, repeats):
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / repeats * 1e3


def rule_inputs(jax, jnp, tokens=32768):
    """q, k, v, g, beta at the cell's widths (16 key and 32 value heads
    of 128), drawn as the layer makes them: l2-normalised keys, decays
    of every size a head's ``A_log`` gives."""
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (1, 16, tokens, 128))) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], (1, 16, tokens, 128)))
    v = jax.random.normal(keys[2], (1, 32, tokens, 128))
    g = -jax.random.uniform(keys[3], (1, 32, tokens)) * jnp.exp(
        jax.random.uniform(keys[4], (1, 32, 1), minval=-4.0, maxval=3.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 32, tokens)))
    return tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)


def _float64_scan(np, state, last, w, k_onto, q_into, attn, u, heads):
    """The recurrence for the first ``heads`` value heads on the host in
    float64, from the operands as the kernels read them (Q~ and P
    rounded to the compute dtype): (o, the leaving state)."""
    wide = lambda x: np.asarray(x.reshape((-1,) + x.shape[3:])[:heads],
                                np.float64)
    state, last, w, k_onto, q_into, attn, u = map(
        wide, (state, last, w, k_onto, q_into, attn, u))
    o = np.zeros_like(u)
    for n in range(u.shape[1]):
        new_v = u[:, n] - w[:, n] @ state
        o[:, n] = q_into[:, n] @ state + attn[:, n] @ new_v
        state = (np.exp(last[:, n])[..., None] * state
                 + np.swapaxes(k_onto[:, n], -1, -2) @ new_v)
    return o, state


def time_scan(jax, jnp, gated_delta, chunk, repeats=20):
    """The chunk-to-chunk recurrence alone over one segment of the
    cell's shape (8,192 tokens: a layer's forward runs four), ms: what
    ``scan_impl`` chooses on this backend (the ``gdn_scan_*`` kernels
    on a TPU) beside the ``lax.scan``, forward, the forward called
    under differentiation with its backward, and that backward alone;
    each path's distance from the float64 recurrence over the same
    operands, and the two paths' gradients from each other."""
    import numpy as np

    q, k, v, g, beta = rule_inputs(jax, jnp, chunk * gated_delta.DEFAULT_SEGMENT)
    split = lambda x, heads, *rest: x.reshape(
        (1,) + heads + (gated_delta.DEFAULT_SEGMENT, chunk) + rest)
    operands = jax.jit(
        lambda *a: gated_delta._chunk_operands(*a, jnp.float32))(
            split(q, (16, 1), 128), split(k, (16, 1), 128),
            split(v, (16, 2), 128), split(g, (16, 2)), split(beta, (16, 2)))
    dtype = q.dtype
    # the kernels read Q~ and P in the compute dtype: every path and the
    # float64 loop get the rounded ones
    operands = tuple(
        x.astype(dtype).astype(x.dtype) if i in (3, 4) else x
        for i, x in enumerate(operands))
    state = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), (1, 16, 2, 128, 128))
    weight = jax.random.normal(jax.random.PRNGKey(6), operands[-1].shape)
    impl = gated_delta.scan_impl(dtype, chunk, 128, 128)
    out = {"impl": impl, "block": {
        kind: gated_delta.scan_block(32, 128, chunk, 128, 128, 2, kind, 2)
        for kind in ("fwd", "fwd_residuals", "bwd")}}
    carries = {"chosen": getattr(gated_delta, "_scan_" + impl),
               "xla": gated_delta._scan_xla}
    want_o, want_state = _float64_scan(np, state, *operands, heads=4)
    rms = lambda got, want: float(
        np.sqrt(np.mean((np.float64(got) - want) ** 2) / np.mean(want ** 2)))
    flat = lambda x: np.asarray(
        x.reshape((-1,) + x.shape[3:])[:4], np.float32)
    grads = {}
    for name, carry in carries.items():
        forward = jax.jit(lambda *a, carry=carry: carry(*a, dtype))

        def loss(*a, carry=carry):
            leaving, o = carry(*a, dtype)
            return (o.astype(jnp.float32) * weight).sum() + leaving.sum()

        grad = jax.jit(jax.grad(loss, argnums=tuple(range(7))))
        leaving, o = forward(state, *operands)
        out["forward_%s_ms" % name] = _clock(
            jax, forward, (state,) + operands, repeats)
        out["grad_%s_ms" % name] = _clock(
            jax, grad, (state,) + operands, repeats)
        # both as the rule hands o on: rounded to the compute dtype
        out["o_from_float64_%s" % name] = rms(
            flat(o.astype(dtype)), want_o)
        out["state_from_float64_%s" % name] = rms(flat(leaving), want_state)
        grads[name] = grad(state, *operands)
    for i, label in enumerate(
            ("state", "last", "w", "k_onto", "q_into", "attn", "u")):
        a, b = (np.asarray(grads[n][i], np.float32) for n in carries)
        out["d_%s_chosen_from_xla" % label] = float(
            np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))
    if impl == "pallas":
        residuals = jax.jit(lambda *a: jax.vjp(
            lambda *a: gated_delta._scan_pallas(*a, dtype), *a)[0])
        out["forward_residuals_chosen_ms"] = _clock(
            jax, residuals, (state,) + operands, repeats)
    return out


def time_rule(jax, jnp, gated_delta, chunk, repeats=3):
    """The rule alone at the cell's shape, forward and forward +
    backward, ms: as this backend runs it (``chosen``: the operands'
    and the scan's kernels on a TPU), and all by XLA with the inverse's
    product form at matmul precision highest and high."""
    args = rule_inputs(jax, jnp)
    chosen = gated_delta.scan_impl
    out = {"impl": chosen(jnp.bfloat16, chunk, 128, 128)}
    xla = lambda *a, **kw: "xla"
    for name, scan, precision in (
            ("chosen", chosen, jax.lax.Precision.HIGHEST),
            ("highest", xla, jax.lax.Precision.HIGHEST),
            ("high", xla, jax.lax.Precision.HIGH)):
        exact = lambda x, y, p=precision: jnp.matmul(x, y, precision=p)
        saved = gated_delta._exact
        gated_delta._exact, gated_delta.scan_impl = exact, scan
        try:
            grad = jax.jit(jax.grad(
                lambda *a: gated_delta.gated_delta_rule(
                    *a, chunk=chunk).astype(jnp.float32).sum(),
                argnums=(0, 1, 2, 3, 4)))
            forward = jax.jit(functools.partial(
                gated_delta.gated_delta_rule, chunk=chunk))
            for fn, label in ((forward, "forward"), (grad, "grad")):
                out["%s_%s_ms" % (label, name)] = _clock(
                    jax, fn, args, repeats)
        finally:
            gated_delta._exact, gated_delta.scan_impl = saved, chosen
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seeds", type=int, default=8)
    parser.add_argument("--variant-seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2147483700)
    parser.add_argument("--leaves", default="")
    parser.add_argument("--last", type=int, default=0)
    parser.add_argument("--no-timing", action="store_true")
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from benchmark.lib import refcheck
    from elasticdl_tpu.common import platform
    from elasticdl_tpu.ops import gated_delta

    platform.configure_compile_cache()

    config, cell, traffic = load(CONFIG), load(CELL), load(TRAFFIC)
    if args.leaves:
        config["check_leaves"] = args.leaves.split(",")
    if args.last:
        cell["last_positions"] = args.last
    spec = {
        "config": config, "cell": cell, "traffic": traffic,
        "zoo": os.path.join(ROOT, config["zoo"]),
        "check": os.path.join(ROOT, config["check"]),
        "reference": os.path.join(ROOT, config["reference"]),
    }
    generator = refcheck.load_by_path(
        "edlbench_traffic", os.path.join(
            ROOT, "benchmark", "traffic", traffic["generator"] + ".py"))
    check = refcheck.load_by_path("edlbench_check", spec["check"])
    rule = gated_delta.gated_delta_rule
    device = jax.devices()[0]
    report = {"device": [device.platform, device.device_kind], "runs": []}
    if not args.no_timing:
        chunk = config["assumed"]["gdn_chunk"]
        report["scan_alone"] = time_scan(jax, jnp, gated_delta, chunk)
        print("scan alone:", json.dumps(report["scan_alone"]), flush=True)
        report["rule_alone"] = time_rule(jax, jnp, gated_delta, chunk)
        print("rule alone:", json.dumps(report["rule_alone"]), flush=True)
    variants = (
        ("stated", {}, args.seeds),
        ("decay_bfloat16", {"decay_dtype": jnp.bfloat16},
         args.variant_seeds),
        ("state_bfloat16", {"state_dtype": jnp.bfloat16},
         args.variant_seeds),
    )
    for name, lowered, seeds in variants:
        gated_delta.gated_delta_rule = (
            functools.partial(rule, **lowered) if lowered else rule)
        jitted = None
        for seed in range(args.first_seed, args.first_seed + seeds):
            sample = generator.sample(traffic, config, seed)
            if jitted is None:
                parts = check.build(spec, sample)
                jitted = {k: jax.jit(parts[k])
                          for k in ("init", "system", "reference")}
            t0 = time.time()
            variables = jitted["init"](jax.random.PRNGKey(seed), sample)
            # on the host: the reference needs the device's memory
            got = jax.device_get(jitted["system"](variables, sample))
            want = jitted["reference"](variables, sample)
            errors, ok = refcheck.compare(got, want, parts["tolerance"])
            run = {"variant": name, "seed": seed, "ok": ok,
                   "errors": errors, "seconds": time.time() - t0}
            report["runs"].append(run)
            print(json.dumps(run), flush=True)
    gated_delta.gated_delta_rule = rule
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "gdn_precision.json"), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
