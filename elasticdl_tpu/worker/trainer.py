"""Single-chip trainer: plain jit around the shared step functions.

This replaces the reference's TF2-eager worker step + gRPC
push_gradients/pull_variables round trip (worker/worker.py:517-649,
ps_client.py) with a single XLA-compiled function: forward, backward,
optimizer update, all on device. For the sharded multi-chip variant see
parallel/spmd_trainer.py — both wrap the same step functions
(train/step_fns.py).
"""

import jax
import numpy as np

from elasticdl_tpu.common import timing_utils
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.train.step_fns import make_eval_step, make_train_step
from elasticdl_tpu.train.train_state import (
    TrainState,
    abstract_train_state,
    create_train_state,
    resolve_dtype,
)


class JaxTrainer:
    def __init__(
        self,
        model,
        loss_fn,
        optimizer,
        compute_dtype=None,
        seed=0,
        grad_accum_steps=1,
        health=None,
    ):
        self._model = model
        self._tx = optimizer
        self._rng = jax.random.PRNGKey(seed)
        # Training-health sentinels (ISSUE 15): None reads EDL_HEALTH
        # (default on), False disables, or pass a HealthTracker. The
        # jitted step then also returns the in-graph health scalars;
        # EDL_HEALTH=0 compiles the exact pre-health program.
        from elasticdl_tpu.train.health import maybe_tracker

        if health is None:
            self.health = maybe_tracker(role="worker")
        elif health is False:
            self.health = None
        else:
            self.health = health
        self._health_on = self.health is not None
        # an MoE model's routing counters of the newest step, still on
        # the device (train/step_fns.py); None for any other model
        self.routing = None
        # a block-diffusion model's noise facts of the newest step, the
        # same way; a hyper-connected model's facts; and what the loss
        # function named of its sum (a prediction module's loss)
        self.noise = None
        self.mhc = None
        self.loss_terms = None
        compute_dtype = resolve_dtype(compute_dtype)
        # recompile sentinels (ISSUE 18): instrumented_jit IS jax.jit
        # when EDL_DEVICE_OBS=0; on, each compile is counted, timed,
        # provenance-diffed, and cost-analyzed
        self._train_step = device_obs.instrumented_jit(
            make_train_step(
                model, loss_fn, optimizer, compute_dtype,
                grad_accum_steps=grad_accum_steps,
                health=self._health_on,
                guard_nonfinite=(
                    self._health_on and self.health.action == "skip"
                ),
            ),
            name="train_step",
            donate_argnums=(0,),
        )
        self._eval_step = device_obs.instrumented_jit(
            make_eval_step(model, compute_dtype), name="eval_step"
        )

    # ------------------------------------------------------------------
    def create_state(self, sample_features) -> TrainState:
        init_rng, self._rng = jax.random.split(self._rng)
        return create_train_state(
            self._model, self._tx, init_rng, sample_features
        )

    def abstract_state(self, sample_features):
        """Restore template: create_state's shapes without the buffers."""
        init_rng, _ = jax.random.split(self._rng)
        return abstract_train_state(
            self._model, self._tx, init_rng, sample_features
        )

    def ensure_state(self, state, batch):
        if state is None:
            with timing_utils.current().phase("state_init"):
                return self.create_state(batch["features"])
        return state

    def train_step(self, state, batch):
        """One step, in the phases of the loop thread's ledger: the
        call of the jitted step until it returns (``dispatch``; the
        batch's transfer to the device is implicit in it), the fetch of
        the health scalars, which waits for the device to finish the
        step (``device_wait``), and the sentinels (``health``)."""
        phase = timing_utils.current().phase
        state = self.ensure_state(state, batch)
        from elasticdl_tpu.testing import faults

        batch = faults.maybe_poison_batch(batch)
        if not self._health_on:
            with phase("dispatch"):
                return self._train_step(state, batch)
        with phase("dispatch"):
            state, loss, scalars = self._train_step(state, batch)
        self.routing = scalars.get("routing")
        self.noise = scalars.get("noise")
        self.mhc = scalars.get("mhc")
        self.loss_terms = scalars.get("loss_terms")
        # one small host transfer per batch; a skip-sentinel batch
        # already kept its state in-graph (nothing else to drop on
        # the dense path — there is no PS push); halt raises
        with phase("device_wait"):
            observed = (
                float(loss),
                float(scalars["grad_norm"]),
                bool(scalars["nonfinite"]),
            )
        with phase("health"):
            self.health.observe(*observed)
        return state, loss

    @property
    def cost_step_flops(self):
        """Executable-reported FLOPs of one train step (0.0 until the
        first compile, or where cost analysis is unavailable) — the
        worker MFU bridge prefers this over a hand-coded table."""
        return float(getattr(self._train_step, "cost_flops", 0.0))

    @property
    def cost_step_bytes(self):
        return float(getattr(self._train_step, "cost_bytes", 0.0))

    def eval_step(self, state, batch):
        outputs = self._eval_step(state, batch["features"])
        nbytes = sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_leaves(outputs)
        )
        with device_obs.transfer_span("d2h", nbytes):
            return jax.tree_util.tree_map(np.asarray, outputs)
