"""Pure train/eval step functions, shared by all trainers.

One implementation serves the single-chip path (worker/trainer.py wraps
with plain jit) and the SPMD path (parallel/spmd_trainer.py wraps with
jit + shardings over a Mesh). The function is written so GSPMD can insert
the gradient reductions: there is no explicit psum — sharding the batch
while replicating (or fsdp-sharding) parameters makes XLA place the
collectives on ICI automatically.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common.annotations import hot_path
from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.train.train_state import TrainState, cast_floating


def global_grad_norm(*grad_trees):
    """Global L2 norm over every leaf of the given gradient trees, in
    fp32 — the health scalar the grad-explosion sentinel watches. One
    extra reduction in-graph; no host transfer of its own."""
    total = jnp.zeros((), jnp.float32)
    for tree in grad_trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            total = total + jnp.sum(
                jnp.square(leaf.astype(jnp.float32))
            )
    return jnp.sqrt(total)


def health_scalars(loss, grad_norm):
    """The in-graph health tuple (ISSUE 15): cheap scalars the trainers
    fetch as ONE small host transfer per batch. ``nonfinite`` covers
    the loss and — because a NaN/Inf anywhere in the gradients makes
    their global norm nonfinite — every gradient leaf."""
    nonfinite = jnp.logical_or(
        jnp.logical_not(jnp.isfinite(loss)),
        jnp.logical_not(jnp.isfinite(grad_norm)),
    )
    return {"grad_norm": grad_norm, "nonfinite": nonfinite}


def guard_nonfinite_state(old_state, new_state, nonfinite):
    """In-graph skip sentinel: when the batch's loss/grads are
    nonfinite, keep the ENTIRE previous state (params, optimizer
    slots, mutable collections, step) — the poisoned batch then
    contributes nothing, matching a run that never saw it. Selected
    per-leaf with jnp.where so the jitted program is branch-free."""
    return jax.tree_util.tree_map(
        lambda old, new: jnp.where(nonfinite, old, new),
        old_state, new_state,
    )


class Fact(NamedTuple):
    """A dict of device values a training step may hand out beside the
    health scalars, and what the journal calls it."""

    # the model's training outputs carry it under this key (the loss
    # function's named terms have no key there), and so do the step's
    # ``scalars`` and a trainer's ``facts``
    key: str
    # the journal's event (``observability/events.py:EVENT_TYPES``)
    event: str
    # (the step's name, the event's) for the fields the event takes,
    # in its order; empty: all of them, under their own names
    fields: tuple = ()
    # parts of the loss: the worker's line names them after ``loss``
    # and the event carries ``loss=`` beside them
    of_loss: bool = False

    def journal(self, value):
        """The event's fields from the fetched dict: floats, and a
        list where the fact is an array (one entry a block)."""
        names = self.fields or tuple((name, name) for name in value)
        return {
            ours: np.asarray(value[theirs], float).tolist()
            for theirs, ours in names if theirs in value}


# the one table of them. An MoE LM's expert-load counters (a layer
# that keeps a balancing bias also reports its magnitude, one that
# holds a share of its experts the pairs that share got, the rows of
# its layers' buffers and those of them the step ran, one whose experts
# are spread over ``ep`` the pairs a rank sent, the rows a rank
# received, those of its receive buffer that its regrouping ran and the
# exchange's bytes, one whose experts' body is ReLU squared the share of
# its experts' and of its shared expert's hidden units above zero), a
# block-diffusion LM's noise facts, a hyper-connected LM's, one a
# block (``models/moe_transformer.py``), a learned sparse-attention
# indexer's, one a layer, a looped stack's exit distribution, one entry
# a pass, a Kimi Delta Attention model's gates, one entry a KDA layer,
# a Mamba-2 model's gates, one entry a Mamba layer,
# and what the loss function names of its own sum (a
# multi-token-prediction module's loss, an indexer's term, a looped
# stack's expected cross-entropy, entropy and cross-entropy an exit)
FACTS = (
    Fact("routing", "moe_routing", (
        ("load_max", "tokens_per_expert_max"),
        ("load_mean", "tokens_per_expert_mean"),
        ("entropy", "router_entropy"),
        ("dropped", "dropped_pairs"),
        ("bias_abs_max", "bias_abs_max"),
        ("held", "held_pairs"),
        ("rows_run", "held_rows_run"),
        ("rows_buffer", "held_rows_buffer"),
        ("sent", "sent_pairs"),
        ("received_max", "received_pairs_max"),
        ("received_mean", "received_pairs_mean"),
        ("received_run", "received_rows_run"),
        ("received_buffer", "received_rows_buffer"),
        ("exchange_bytes", "exchange_bytes"),
        ("relu2_active", "relu2_active_share"),
        ("relu2_shared_active", "relu2_shared_active_share"))),
    Fact("noise", "bd_noise"),
    Fact("mhc", "mhc"),
    Fact("dsa", "dsa_select"),
    Fact("looped", "looped_exit"),
    Fact("kda", "kda_gates"),
    Fact("mamba", "mamba_gates"),
    Fact("loss_terms", "loss_terms", of_loss=True),
)


def facts_of(outputs, terms=None):
    """``{key: dict of device values}`` for the facts that are there:
    the table's keys among a model's training outputs (or a step's
    scalars), the loss function's ``terms`` under the row that is
    ``of_loss``. A model without them adds nothing to its step."""
    if not isinstance(outputs, dict):
        outputs = {}
    found = {
        fact.key: outputs[fact.key] for fact in FACTS
        if outputs.get(fact.key) is not None}
    if terms is not None:
        found.update((fact.key, terms) for fact in FACTS if fact.of_loss)
    return found


def _split_terms(value):
    """A loss function returns the per-sample losses, or those and
    ``{name: per-sample term}`` for the parts of them it names."""
    return value if isinstance(value, tuple) else (value, None)


def step_rngs(step):
    """The random streams a training call may draw from, each folded
    from the step so that a resumed job draws what it would have drawn:
    ``dropout``, and ``noise`` (a diffusion objective's corruption of
    its inputs, ``ops/block_diffusion.py``). A stream no model draws
    from is dead code, which the compiler drops."""
    return {
        "dropout": jax.random.fold_in(jax.random.PRNGKey(0), step),
        "noise": jax.random.fold_in(jax.random.PRNGKey(1), step),
    }


def _apply_model(model, params, model_state, features, training, rngs):
    variables = {"params": params, **model_state}
    if model_state:
        if training:
            outputs, updates = model.apply(
                variables,
                features,
                training=True,
                rngs=rngs,
                mutable=list(model_state.keys()),
            )
            return outputs, dict(updates)
        outputs = model.apply(
            variables, features, training=False, rngs=rngs
        )
        return outputs, model_state
    outputs = model.apply(variables, features, training=training, rngs=rngs)
    return outputs, model_state


@hot_path
def make_train_step(model, loss_fn, tx, compute_dtype=None,
                    grad_accum_steps=1, health=False,
                    guard_nonfinite=False, with_facts=False):
    """Returns train_step(state, batch) -> (new_state, loss).

    ``health=True`` (ISSUE 15) additionally returns a third output —
    the in-graph health scalars dict (global grad norm + nonfinite
    flag); with ``guard_nonfinite`` a nonfinite batch keeps the
    previous state in-graph (the skip sentinel). ``health=False`` is
    the exact pre-health program: no extra outputs (test-asserted).
    Where the model's training outputs carry a fact of ``FACTS``, or
    the loss function returns named terms beside its per-sample losses
    (a prediction module's ``mtp_loss``), the dict has them under the
    table's keys as device values: they leave the step with the health
    scalars and cost no fetch until someone reads them (the worker
    does on the steps it logs). ``with_facts`` without ``health`` hands
    out the facts alone, as a third output (the SPMD trainer's: a model
    without facts adds an empty dict and no operation to its step).

    ``grad_accum_steps=k`` splits the batch into k equal microbatches
    scanned sequentially, accumulating MASK-WEIGHTED gradient sums and
    applying ONE optimizer update — bit-exact large-batch semantics
    (the masked mean is taken over the whole batch's weight, so ragged
    masks don't skew toward emptier microbatches) with activation
    memory divided by k. Mutable model collections (batch stats) see
    per-microbatch statistics, the standard ghost-BN-style trade."""

    if grad_accum_steps < 1:
        raise ValueError(
            "grad_accum_steps must be >= 1, got %r" % (grad_accum_steps,)
        )

    def _loss_sum(params, model_state, features, labels, mask, rngs):
        """(masked loss SUM, (mask weight, new model state, the model's
        facts: ``facts_of``)) — summed (not averaged) so microbatch
        grads add linearly."""
        compute_params = params
        compute_features = features
        # names only (ISSUE 23, ISSUE 62): the scopes go into every
        # operation's ``op_name``, where a device trace reads the cast,
        # forward, loss and optimizer apart
        # (``observability/scopes.py`` is their registry); JAX itself
        # writes ``transpose(jvp(forward))`` on the backward. The
        # program computes the same values
        if compute_dtype is not None:
            with jax.named_scope("cast_params"):
                compute_params = cast_floating(params, compute_dtype)
                compute_features = cast_floating(features, compute_dtype)
        with jax.named_scope("forward"):
            outputs, new_model_state = _apply_model(
                model,
                compute_params,
                model_state,
                compute_features,
                training=True,
                rngs=rngs,
            )
        with jax.named_scope("loss"):
            per_sample, terms = _split_terms(loss_fn(labels, outputs))
            # same row-collapse masked_mean applies (multi-dim
            # per-sample losses average over their trailing dims first)
            collapse = lambda t: t.astype(jnp.float32).reshape(
                mask.shape[0], -1).mean(axis=1)
            loss_sum = jnp.sum(collapse(per_sample) * mask)
            weight = jnp.sum(mask)
            if terms is not None:
                # a named term leaves as this (micro)batch's masked mean
                terms = {
                    name: jax.lax.stop_gradient(
                        jnp.sum(collapse(term) * mask)
                        / jnp.maximum(weight, 1.0))
                    for name, term in terms.items()}
            return loss_sum, (
                weight, new_model_state, facts_of(outputs, terms)
            )

    def _apply_update(state, grads, loss, new_model_state):
        with jax.named_scope("optimizer"):
            grads = cast_floating(grads, jnp.float32)
            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree_util.tree_map(
                lambda p, u: (p + u).astype(p.dtype),
                state.params, updates,
            )
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                model_state=new_model_state,
                opt_state=new_opt_state,
            ),
            loss,
        )

    def train_step(state: TrainState, batch):
        features, labels, mask = (
            batch["features"],
            batch["labels"],
            batch[MASK_KEY],
        )
        rngs = step_rngs(state.step)

        def finish(new_state, loss, grads, facts):
            if not health:
                return (
                    (new_state, loss, facts) if with_facts
                    else (new_state, loss))
            with jax.named_scope("health"):
                scalars = health_scalars(loss, global_grad_norm(grads))
                scalars.update(facts)
                if guard_nonfinite:
                    new_state = guard_nonfinite_state(
                        state, new_state, scalars["nonfinite"]
                    )
            return new_state, loss, scalars

        if grad_accum_steps == 1:
            def compute_loss(params):
                loss_sum, (weight, new_model_state, facts) = _loss_sum(
                    params, state.model_state, features, labels, mask,
                    rngs,
                )
                with jax.named_scope("loss"):
                    loss = loss_sum / jnp.maximum(weight, 1.0)
                return loss, (new_model_state, facts)

            (loss, (new_model_state, facts)), grads = jax.value_and_grad(
                compute_loss, has_aux=True
            )(state.params)
            new_state, loss = _apply_update(
                state, grads, loss, new_model_state
            )
            return finish(new_state, loss, grads, facts)

        k = int(grad_accum_steps)

        def to_micro(leaf):
            if leaf.shape[0] % k:
                raise ValueError(
                    "batch dim %d not divisible by grad_accum_steps=%d"
                    % (leaf.shape[0], k)
                )
            # STRIDED split (microbatch i = rows i::k), not contiguous
            # blocks: under an SPMD trainer the batch dim is sharded
            # over the data axes, and a contiguous microbatch would live
            # on only a subset of devices — GSPMD then reshards the
            # whole input batch every step. The strided split draws each
            # microbatch equally from every device's local block, so
            # splitting stays communication-free. Row-to-microbatch
            # assignment doesn't change the accumulated sums.
            return leaf.reshape(
                (leaf.shape[0] // k, k) + leaf.shape[1:]
            ).swapaxes(0, 1)

        with jax.named_scope("micro_batch"):
            micro = jax.tree_util.tree_map(
                to_micro, (features, labels, mask)
            )
            zero_grads = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
        grad_fn = jax.value_and_grad(_loss_sum, has_aux=True)

        def body(carry, micro_slice):
            grads_acc, loss_acc, weight_acc, model_state, i = carry
            m_features, m_labels, m_mask = micro_slice
            micro_rngs = {
                name: jax.random.fold_in(key, i)
                for name, key in rngs.items()
            }
            (loss_sum, (weight, model_state, facts)), grads = grad_fn(
                state.params, model_state, m_features, m_labels, m_mask,
                micro_rngs,
            )
            with jax.named_scope("micro_batch"):
                grads_acc = jax.tree_util.tree_map(
                    lambda a, g: a + cast_floating(g, jnp.float32),
                    grads_acc,
                    grads,
                )
            return (
                grads_acc,
                loss_acc + loss_sum,
                weight_acc + weight,
                model_state,
                i + 1,
            ), facts

        (grads_sum, loss_sum, weight, new_model_state, _), facts = (
            jax.lax.scan(
                body,
                (zero_grads, 0.0, 0.0, state.model_state, 0),
                micro,
            )
        )
        with jax.named_scope("micro_batch"):
            weight = jnp.maximum(weight, 1.0)
            grads = jax.tree_util.tree_map(
                lambda g: g / weight, grads_sum
            )
        new_state, loss = _apply_update(
            state, grads, loss_sum / weight, new_model_state
        )
        # the facts of the last microbatch stand for the step
        facts = jax.tree_util.tree_map(lambda leaf: leaf[-1], facts)
        return finish(new_state, loss, grads, facts)

    return train_step


@hot_path
def make_eval_step(model, compute_dtype=None):
    """Returns eval_step(state, features) -> outputs."""

    def eval_step(state: TrainState, features):
        compute_params = state.params
        if compute_dtype is not None:
            compute_params = cast_floating(state.params, compute_dtype)
            features = cast_floating(features, compute_dtype)
        outputs, _ = _apply_model(
            model,
            compute_params,
            state.model_state,
            features,
            training=False,
            rngs=None,
        )
        return outputs

    return eval_step
