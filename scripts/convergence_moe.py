"""MoE expert-balance convergence experiment (round-4 VERDICT item 5).

Question: does the Switch-style aux loss (ops/moe.py top_k_routing)
actually keep expert dispatch balanced over a REAL training run — and
what happens without it? Trains the same small MoeTransformerLM twice
(aux_loss_weight=0.01 vs 0.0) on a learnable synthetic LM task, then
measures routing balance post-hoc by capturing the router logits with
flax ``capture_intermediates``.

Metrics per arm:
- ce_first/ce_last: cross-entropy at start/end (both arms must learn);
- balance = E * sum_e f_e * p_e (1.0 = perfectly uniform; E = fully
  collapsed), f_e = first-choice token fraction, p_e = mean router prob;
- max_share: largest single expert's first-choice share (uniform = 1/E).

Prints one JSON line. CPU-runnable (tiny shapes); the expert layer's
speed is the benchmark's cell `olmoe1b7b-s4k` (PERF.md).
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.models import moe_transformer
from elasticdl_tpu.train.optimizers import create_optimizer
from elasticdl_tpu.worker.trainer import JaxTrainer

VOCAB = 64
NUM_EXPERTS = 4


def make_batch(rng, batch=16, seq=32):
    """Learnable LM stream: next token = (t + stride) % VOCAB with the
    stride switching by region — enough structure that CE falls well
    below uniform."""
    starts = rng.randint(0, VOCAB, size=(batch, 1))
    strides = rng.choice([1, 3, 7], size=(batch, 1))
    pos = np.arange(seq)[None, :]
    tokens = (starts + strides * pos) % VOCAB
    return {
        "features": tokens.astype(np.int32),
        "labels": tokens.astype(np.int32),
        "_mask": np.ones((batch,), np.float32),
    }


def routing_balance(model, params, batch):
    """Post-hoc balance from captured router logits."""
    _, intermediates = model.apply(
        {"params": params},
        batch["features"],
        training=False,
        capture_intermediates=lambda mdl, _: mdl.name == "router",
    )
    flat = jax.tree_util.tree_leaves_with_path(intermediates)
    balances, max_shares = [], []
    for _path, logits in flat:
        logits = np.asarray(logits, np.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        first = np.asarray(jnp.argmax(probs, axis=-1)).reshape(-1)
        f_e = np.bincount(first, minlength=NUM_EXPERTS) / first.size
        p_e = np.asarray(probs).reshape(-1, NUM_EXPERTS).mean(axis=0)
        balances.append(float(NUM_EXPERTS * np.sum(f_e * p_e)))
        max_shares.append(float(f_e.max()))
    return float(np.mean(balances)), float(np.max(max_shares))


def _collapse_routers(params, bias=3.0):
    """Bias every router kernel toward expert 0 — the adversarial init.

    From a random init this tiny task never collapses on its own (both
    arms stay near balance=1.0; measured), so the discriminating
    question is RECOVERY: routing collapse is an attractor (expert 0
    hoards tokens, gets all the gradient, stays best) and only the aux
    loss provides a force out of it."""

    def visit(tree):
        for key, value in tree.items():
            if key == "router":
                kernel = np.array(value["kernel"])  # writable copy
                kernel[:, 1:] -= bias / max(1, kernel.shape[0]) ** 0.5
                value["kernel"] = jnp.asarray(kernel)
            elif isinstance(value, dict):
                visit(value)

    import flax

    params = flax.core.unfreeze(jax.tree_util.tree_map(np.asarray, params))
    visit(params)
    return params


def run_arm(aux_weight, steps, seed=0, collapsed_init=True):
    model = moe_transformer.MoeTransformerLM(
        vocab_size=VOCAB,
        num_layers=2,
        num_heads=2,
        embed_dim=32,
        num_experts=NUM_EXPERTS,
        top_k=2,
        aux_loss_weight=aux_weight,
        attention_impl="xla",
    )
    trainer = JaxTrainer(
        model,
        moe_transformer.loss,
        create_optimizer("Adam", learning_rate=0.01),
        seed=0,
    )
    rng = np.random.RandomState(seed)
    state = None
    ce_first = ce_last = None
    balance0 = share0 = None
    for i in range(steps):
        batch = make_batch(rng)
        if i == 0:
            state = trainer.ensure_state(state, batch)
            if collapsed_init:
                from elasticdl_tpu.train.train_state import TrainState

                state = TrainState(
                    step=state.step,
                    params=_collapse_routers(state.params),
                    model_state=state.model_state,
                    opt_state=state.opt_state,
                )
            balance0, share0 = routing_balance(
                model, state.params, make_batch(np.random.RandomState(999))
            )
        state, loss = trainer.train_step(state, batch)
        if i == 0:
            ce_first = float(loss)
        ce_last = float(loss)
    probe = make_batch(np.random.RandomState(999))
    balance, max_share = routing_balance(model, state.params, probe)
    return {
        "aux_weight": aux_weight,
        "ce_first": round(ce_first, 4),
        "ce_last": round(ce_last, 4),
        "balance_init": round(balance0, 4),
        "max_expert_share_init": round(share0, 4),
        "balance": round(balance, 4),
        "max_expert_share": round(max_share, 4),
    }


def main():
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=200)
    args = parser.parse_args()
    arms = [run_arm(0.01, args.steps), run_arm(0.0, args.steps)]
    print(json.dumps({
        "experiment": "moe_expert_balance",
        "num_experts": NUM_EXPERTS,
        "steps": args.steps,
        "with_aux": arms[0],
        "without_aux": arms[1],
    }))


if __name__ == "__main__":
    main()
