"""The Qwen3-Next configuration's reference check over several seeds and
under two lower precisions of the chunked gated delta rule, and the
inverse alone and the rule alone against the clock, as this backend runs
them and by XLA's product form (one chip, ~20 min).

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


def time_inverse(jax, jnp, gated_delta, chunk, count=4096, repeats=30):
    """The inverse alone at a segment's batch (``count`` matrices with
    entries of the cell's size), ms: what ``unit_lower_inverse`` runs on
    this backend (the ``gdn_inverse_*`` kernels on a TPU) beside XLA's
    product form, forward and VJP, and the largest difference."""
    a = jnp.tril(0.2 * jax.random.normal(
        jax.random.PRNGKey(0), (count, chunk, chunk)), -1)
    d = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    impl = gated_delta.inverse_impl(a.dtype, chunk)
    out = {"impl": impl}
    forward = jax.jit(gated_delta.unit_lower_inverse)
    xla = jax.jit(gated_delta._inverse_product)
    vjp = lambda impl: jax.jit(
        lambda t, d: gated_delta._inverse_vjp_bwd(impl, t, d)[0])
    t = xla(a)
    out["forward_ms"] = _clock(jax, forward, (a,), repeats)
    out["forward_xla_ms"] = _clock(jax, xla, (a,), repeats)
    out["forward_max_abs_difference"] = float(
        jnp.abs(forward(a) - t).max())
    out["backward_ms"] = _clock(jax, vjp(impl), (t, d), repeats)
    out["backward_xla_ms"] = _clock(jax, vjp("xla"), (t, d), repeats)
    got, want = vjp(impl)(t, d), vjp("xla")(t, d)
    out["backward_max_relative_difference"] = float(
        jnp.abs(got - want).max() / jnp.abs(want).max())
    return out


def time_rule(jax, jnp, gated_delta, chunk, repeats=3):
    """The rule alone at the cell's shape, forward and forward +
    backward, ms: as this backend runs it (``chosen``: the inverse's
    kernels on a TPU), and with the inverse by XLA's product form at
    matmul precision highest and high."""
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (1, 16, 32768, 128))) * 128 ** -0.5
    k = unit(jax.random.normal(keys[1], (1, 16, 32768, 128)))
    v = jax.random.normal(keys[2], (1, 32, 32768, 128))
    g = -jax.random.uniform(keys[3], (1, 32, 32768)) * jnp.exp(
        jax.random.uniform(keys[4], (1, 32, 1), minval=-4.0, maxval=3.0))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 32, 32768)))
    args = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    out = {"impl": gated_delta.inverse_impl(jnp.float32, chunk)}
    chosen = gated_delta.inverse_impl
    for name, impl, precision in (
            ("chosen", chosen, jax.lax.Precision.HIGHEST),
            ("highest", lambda *a: "xla", jax.lax.Precision.HIGHEST),
            ("high", lambda *a: "xla", jax.lax.Precision.HIGH)):
        exact = lambda x, y, p=precision: jnp.matmul(x, y, precision=p)
        saved = gated_delta._exact
        gated_delta._exact, gated_delta.inverse_impl = exact, impl
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
            gated_delta._exact, gated_delta.inverse_impl = saved, chosen
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
        report["inverse_alone"] = time_inverse(jax, jnp, gated_delta, chunk)
        print("inverse alone:", json.dumps(report["inverse_alone"]),
              flush=True)
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
