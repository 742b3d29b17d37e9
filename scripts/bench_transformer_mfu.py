"""Measure TransformerLM training MFU on the chip.

The evidence behind docs/PERF_TRANSFORMER.md. Runs the full train step
— forward, backward, AdamW update — under one jit'd lax.scan, so the
wall-clock between dispatch and the fetched loss is device time with
one dispatch per window.

Model FLOPs are counted exactly from the architecture (matmul FLOPs
only, causal attention halved, embedding gather excluded) — NOT from
the 6NT approximation — so remat recompute never inflates MFU.

Usage:
  python scripts/bench_transformer_mfu.py --d 2048 --layers 12 \
      --seq 2048 --batch 8 --remat dots [--profile /tmp/tlm_trace]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def xla_memory_fields(compiled):
    """Best-effort XLA buffer-assignment sizes as a JSON-ready dict.

    Empty on backends whose compiled executables expose no memory
    analysis (some CPU/GPU jaxlib builds return None or raise).
    """
    try:
        ma = compiled.memory_analysis()
        return {
            "xla_args_gb": round(ma.argument_size_in_bytes / 1e9, 2),
            "xla_temp_gb": round(ma.temp_size_in_bytes / 1e9, 2),
            "xla_aliased_gb": round(ma.alias_size_in_bytes / 1e9, 2),
            # what the program needs resident: args + temps + outputs,
            # minus the donated-argument buffers outputs reuse
            "xla_peak_gb": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 + ma.output_size_in_bytes - ma.alias_size_in_bytes)
                / 1e9, 2
            ),
        }
    except Exception:
        return {}


def xla_cost_flops(compiled, steps):
    """XLA's own cost_analysis() FLOPs for ONE step, or 0.0 where the
    backend exposes none. The compiled program runs ``steps`` scanned
    steps, so the program total divides down. This is the same number
    the ISSUE-18 device-obs layer feeds the worker's MFU gauge — the
    cross-check below keeps the hand count honest."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return 0.0
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return 0.0
    return float(cost.get("flops", 0.0)) / max(steps, 1)


def model_train_flops(d, layers, seq, batch, vocab, mlp_ratio=4):
    """Exact matmul FLOPs for one train step (fwd + bwd = 3x fwd)."""
    tokens = batch * seq
    # per layer: qkv (3 d^2) + out-proj (d^2) + mlp up/down
    # (2 * mlp_ratio * d^2)
    proj = 2 * tokens * ((4 + 2 * mlp_ratio) * d * d) * layers
    # attention: QK^T + PV, causal halves the score matrix
    attn = 2 * (2 * batch * seq * seq * d) * layers / 2
    head = 2 * tokens * d * vocab
    return 3 * (proj + attn + head)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--d", type=int, default=2048)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--mlp_ratio", type=int, default=4)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument(
        "--remat", choices=["none", "full", "dots", "flash"],
        default="dots",
    )
    p.add_argument(
        "--attn", choices=["auto", "pallas", "xla"], default="pallas"
    )
    p.add_argument("--opt", default="AdamW")
    p.add_argument(
        "--grad_accum_steps", type=int, default=1,
        help="split the batch into k sequential microbatches "
             "(exact semantics, train/step_fns.py) — lifts the HBM "
             "ceiling: activations are materialized for batch/k rows "
             "at a time while the optimizer still sees the full-batch "
             "gradient",
    )
    p.add_argument("--profile", default=None, help="trace output dir")
    p.add_argument(
        "--compile_only", action="store_true",
        help="report XLA's buffer-assignment memory analysis without "
             "executing — documents WHY an over-HBM config cannot run",
    )
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.common.platform import (
        configure_compile_cache,
        peak_flops,
    )

    configure_compile_cache()

    from elasticdl_tpu.models.transformer import TransformerLM
    from elasticdl_tpu.train.optimizers import create_optimizer
    from elasticdl_tpu.train.step_fns import make_train_step
    from elasticdl_tpu.train.train_state import create_train_state

    model = TransformerLM(
        vocab_size=args.vocab,
        num_layers=args.layers,
        num_heads=args.heads,
        embed_dim=args.d,
        mlp_ratio=args.mlp_ratio,
        attention_impl=args.attn,
        remat=args.remat != "none",
        remat_policy=args.remat,
    )
    tx = create_optimizer(
        args.opt, learning_rate=3e-4, weight_decay=0.01
    )

    from elasticdl_tpu.models.transformer import loss as loss_fn

    train_step = make_train_step(
        model, loss_fn, tx, compute_dtype=jnp.bfloat16,
        grad_accum_steps=args.grad_accum_steps,
    )

    def run_steps(state, batch, n):
        def body(state, _):
            state, loss = train_step(state, batch)
            return state, loss

        return jax.lax.scan(body, state, None, length=n)

    run = jax.jit(run_steps, static_argnums=(2,), donate_argnums=(0,))

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, args.vocab, size=(args.batch, args.seq)), jnp.int32
    )
    batch = {
        "features": tokens,
        "labels": tokens,
        "_mask": jnp.ones((args.batch,), jnp.float32),
    }
    state = create_train_state(
        model, tx, jax.random.PRNGKey(0), batch["features"]
    )
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(state.params)
    )

    # AOT compile so XLA's buffer-assignment peak is available for
    # --compile_only (a config that over-runs HBM cannot execute):
    # arguments + temps - aliased(donated) bounds the peak HBM the
    # program needs.
    t0 = time.perf_counter()
    compiled = run.lower(state, batch, args.steps).compile()
    config = {
        "d": args.d, "layers": args.layers, "heads": args.heads,
        "seq": args.seq, "batch": args.batch, "vocab": args.vocab,
        "remat": args.remat, "attn": args.attn, "opt": args.opt,
        "grad_accum_steps": args.grad_accum_steps,
    }
    if args.compile_only:
        print(json.dumps({
            "config": config,
            **xla_memory_fields(compiled),
        }))
        return
    state, losses = compiled(state, batch)
    float(losses[-1])
    compile_s = time.perf_counter() - t0
    run = compiled

    start = time.perf_counter()
    state, losses = run(state, batch)
    final_loss = float(losses[-1])
    elapsed = time.perf_counter() - start
    assert np.isfinite(final_loss), final_loss

    step_ms = elapsed / args.steps * 1e3
    flops = model_train_flops(
        args.d, args.layers, args.seq, args.batch, args.vocab,
        args.mlp_ratio,
    )
    kind = jax.devices()[0].device_kind
    peak = peak_flops(kind)
    mfu = flops / (elapsed / args.steps) / peak
    toks_per_sec = args.batch * args.seq / (elapsed / args.steps)

    mem = {}
    try:
        stats = jax.devices()[0].memory_stats() or {}
        if stats.get("peak_bytes_in_use"):
            mem["hbm_peak_gb"] = round(
                stats["peak_bytes_in_use"] / 1e9, 2
            )
    except Exception:
        pass
    mem.update(xla_memory_fields(compiled))

    # cost-model cross-check (ISSUE 18): XLA's own count of the
    # program actually compiled, beside the hand count. Disagreement
    # >10% means one of them is wrong — usually the hand count after
    # an architecture change (new attention kind, remat recompute the
    # hand count deliberately excludes showing up in XLA's total).
    xla_flops = xla_cost_flops(compiled, args.steps)
    if xla_flops:
        mem["xla_tflop_per_step"] = round(xla_flops / 1e12, 2)
        mem["xla_mfu"] = round(
            xla_flops / (elapsed / args.steps) / peak, 4
        )
        disagreement = abs(xla_flops - flops) / max(xla_flops, flops)
        mem["flops_disagreement"] = round(disagreement, 4)
        if disagreement > 0.10:
            print(
                "WARNING: hand-counted FLOPs (%.2f T) and XLA "
                "cost_analysis (%.2f T) disagree by %.0f%% — "
                "re-derive model_train_flops for this config"
                % (flops / 1e12, xla_flops / 1e12,
                   disagreement * 100),
                file=sys.stderr,
            )

    print(json.dumps({
        "config": config,
        "params_m": round(n_params / 1e6, 1),
        "device": kind,
        "peak_tflops": peak / 1e12,
        "model_tflop_per_step": round(flops / 1e12, 2),
        "step_ms": round(step_ms, 2),
        "tokens_per_sec": round(toks_per_sec, 1),
        "mfu": round(mfu, 4),
        "compile_s": round(compile_s, 1),
        **mem,
    }))

    if args.profile:
        from scripts.trace_summary import capture_trace

        def _once():
            _, traced_losses = run(state, batch)
            float(traced_losses[-1])

        capture_trace(_once, args.profile, args.steps)


if __name__ == "__main__":
    main()
