"""MoE-transformer single-chip step bench vs dense at matched ACTIVE
FLOPs (round-4 VERDICT item 5: every capability ships a measured
number; MoE had correctness only).

Two arms, same embed/attention dims, full train step (fwd+bwd+AdamW)
under one jit'd lax.scan:

- moe:   MoeTransformerLM, E experts, top-k=2, capacity_factor cf —
         every token's FFN compute is k*cf x the dense block's
         (static-capacity GShard dispatch runs every slot, full or
         not), plus the dispatch/combine einsums (O(S * E*C * M) —
         the real price of the einsum-dispatch formulation).
- dense: TransformerLM with mlp_ratio scaled by ~k*cf so its FFN FLOPs
         match the MoE arm's ACTIVE FFN FLOPs.

Model FLOPs are counted exactly per arm (routing + dispatch included
for moe), so the reported MFUs are comparable and honest. Prints one
JSON line with both arms + the relative step-time overhead of the MoE
machinery at equal active compute.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def dense_flops(d, layers, seq, batch, vocab, mlp_ratio):
    tokens = batch * seq
    proj = 2 * tokens * ((4 + 2 * mlp_ratio) * d * d) * layers
    attn = 2 * (2 * batch * seq * seq * d) * layers / 2
    head = 2 * tokens * d * vocab
    return 3 * (proj + attn + head)


def moe_flops(d, layers, seq, batch, vocab, mlp_ratio, num_experts, k,
              capacity_factor, sorted_dispatch):
    """Exact matmul FLOPs of MoeTransformerLM: MoE FFN in every other
    block (models/moe_transformer.py), static capacity C per group.

    The sorted (dropless) dispatch executes NO dispatch/combine
    matmuls and has no capacity: its experts compute tokens x k rows.
    Those terms only exist on the one-hot einsum path, so each arm's
    MFU divides by the FLOPs it actually runs."""
    from elasticdl_tpu.ops.moe import expert_capacity

    tokens = batch * seq
    moe_layers = layers // 2
    dense_layers = layers - moe_layers
    capacity = expert_capacity(seq, num_experts, k, capacity_factor)
    ff = mlp_ratio * d
    # attention + out-proj + qkv in EVERY block
    proj_attn = 2 * tokens * (4 * d * d) * layers
    attn = 2 * (2 * batch * seq * seq * d) * layers / 2
    # dense-block FFNs
    ffn_dense = 2 * tokens * (2 * mlp_ratio * d * d) * dense_layers
    # expert FFNs: every (expert, slot) computes, full or not
    slots = tokens * k if sorted_dispatch else (
        batch * num_experts * capacity)
    ffn_moe = 2 * slots * (2 * d * ff) * moe_layers
    # router; dispatch/combine einsums (gsec,gsm->egcm and back) are
    # matmuls only on the one-hot path — the sorted path gathers
    router = 2 * tokens * d * num_experts * moe_layers
    if sorted_dispatch:
        dispatch = 0
    else:
        dispatch = (
            2 * 2 * batch * seq * num_experts * capacity * d * moe_layers
        )
    head = 2 * tokens * d * vocab
    return 3 * (proj_attn + attn + ffn_dense + ffn_moe + router
                + dispatch + head)


def run_arm(model, loss_fn, flops, batch_tokens, args, profile_dir=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.train.optimizers import create_optimizer
    from elasticdl_tpu.train.step_fns import make_train_step
    from elasticdl_tpu.train.train_state import create_train_state

    if args.opt == "AdamW":
        tx = create_optimizer(
            "AdamW", learning_rate=3e-4, weight_decay=0.01
        )
    else:  # decomposition arm: no m/v state traffic (docs/PERF_MOE.md)
        tx = create_optimizer(args.opt, learning_rate=3e-4)
    train_step = make_train_step(
        model, loss_fn, tx, compute_dtype=jnp.bfloat16
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
    state, losses = run(state, batch, args.steps)  # compile + warmup
    float(losses[-1])
    start = time.perf_counter()
    state, losses = run(state, batch, args.steps)
    final_loss = float(losses[-1])
    elapsed = time.perf_counter() - start
    assert np.isfinite(final_loss), final_loss
    if profile_dir:
        from scripts.trace_summary import capture_trace

        def _once():
            _, traced_losses = run(state, batch, args.steps)
            float(traced_losses[-1])

        capture_trace(_once, profile_dir, args.steps)
    from elasticdl_tpu.common.platform import peak_flops

    kind = jax.devices()[0].device_kind
    peak = peak_flops(kind)
    step = elapsed / args.steps
    return {
        "params_m": round(n_params / 1e6, 1),
        "step_ms": round(step * 1e3, 2),
        "tokens_per_sec": round(batch_tokens / step, 1),
        "model_tflop_per_step": round(flops / 1e12, 3),
        "mfu": round(flops / step / peak, 4),
        "device": kind,
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--d", type=int, default=1024)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--mlp_ratio", type=int, default=4)
    p.add_argument("--experts", type=int, default=8)
    p.add_argument("--top_k", type=int, default=2)
    p.add_argument("--capacity_factor", type=float, default=1.25)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--attn", default="pallas")
    p.add_argument(
        "--opt", default="AdamW",
        help="optimizer for BOTH arms (SGD isolates the optimizer-"
             "state-traffic share of the MoE step premium)",
    )
    p.add_argument(
        "--dispatch", default="auto",
        choices=["auto", "onehot", "sorted"],
        help="MoE dispatch impl (auto = the one-hot einsums with a "
             "capacity; sorted = the dropless sort + grouped matmul)",
    )
    p.add_argument(
        "--profile", default=None,
        help="trace dir for the MoE arm (HLO-category summary printed)",
    )
    args = p.parse_args()

    from elasticdl_tpu.common.platform import configure_compile_cache

    configure_compile_cache()

    from elasticdl_tpu.models import moe_transformer, transformer

    batch_tokens = args.batch * args.seq
    moe_model = moe_transformer.MoeTransformerLM(
        vocab_size=args.vocab,
        num_layers=args.layers,
        num_heads=args.heads,
        embed_dim=args.d,
        mlp_ratio=args.mlp_ratio,
        num_experts=args.experts,
        top_k=args.top_k,
        capacity_factor=args.capacity_factor,
        attention_impl=args.attn,
        dispatch_impl=args.dispatch,
    )
    # "auto" resolves to the one-hot einsums (models/moe_transformer.py:
    # the legacy default); only an explicit --dispatch sorted drops
    # the dispatch-einsum FLOPs from the count
    sorted_dispatch = args.dispatch == "sorted"
    moe = run_arm(
        moe_model,
        moe_transformer.loss,
        moe_flops(args.d, args.layers, args.seq, args.batch, args.vocab,
                  args.mlp_ratio, args.experts, args.top_k,
                  args.capacity_factor, sorted_dispatch),
        batch_tokens,
        args,
        profile_dir=args.profile,
    )
    # dense arm at matched ACTIVE FFN FLOPs: half the blocks carry
    # k*cf-times the FFN (the other half already match), i.e. mean
    # mlp_ratio = r * (1 + k*cf) / 2
    dense_ratio = max(
        1, round(args.mlp_ratio * (1 + args.top_k * args.capacity_factor)
                 / 2)
    )
    dense_model = transformer.TransformerLM(
        vocab_size=args.vocab,
        num_layers=args.layers,
        num_heads=args.heads,
        embed_dim=args.d,
        mlp_ratio=dense_ratio,
        attention_impl=args.attn,
    )
    dense = run_arm(
        dense_model,
        transformer.loss,
        dense_flops(args.d, args.layers, args.seq, args.batch,
                    args.vocab, dense_ratio),
        batch_tokens,
        args,
    )
    print(json.dumps({
        "config": {
            "d": args.d, "layers": args.layers, "seq": args.seq,
            "batch": args.batch, "experts": args.experts,
            "top_k": args.top_k,
            "capacity_factor": args.capacity_factor,
            "moe_mlp_ratio": args.mlp_ratio,
            "dense_mlp_ratio_matched": dense_ratio,
            "attn": args.attn,
            "dispatch": args.dispatch,
        },
        "moe": moe,
        "dense_matched_active": dense,
        "moe_step_overhead_vs_dense": round(
            moe["step_ms"] / dense["step_ms"], 3
        ),
    }))


if __name__ == "__main__":
    main()
