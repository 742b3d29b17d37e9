"""What the worker knows of a trainer (``Trainer``), the one place that
picks and builds one (``trainer_class``, ``build_trainer``), and the
single-chip trainer: plain jit around the shared step functions.

This replaces the reference's TF2-eager worker step + gRPC
push_gradients/pull_variables round trip (worker/worker.py:517-649,
ps_client.py) with a single XLA-compiled function: forward, backward,
optimizer update, all on device. For the sharded multi-chip variant see
parallel/spmd_trainer.py — both wrap the same step functions
(train/step_fns.py).
"""

import inspect
from typing import Any, NamedTuple

import jax
import numpy as np

from elasticdl_tpu.common import timing_utils
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import events
from elasticdl_tpu.train.step_fns import (
    facts_of,
    make_eval_step,
    make_train_step,
)
from elasticdl_tpu.train.train_state import (
    TrainState,
    abstract_train_state,
    create_train_state,
    resolve_dtype,
)

logger = _logger_factory("elasticdl_tpu.worker.trainer")


class PendingStep(NamedTuple):
    """What a dispatched step left on the device for the host to read:
    nothing of it has been fetched. The loop keeps one while the next
    step is dispatched and hands it to ``Trainer.read_step`` a step
    late (``worker/worker.py:Worker._after_train_batch``)."""

    loss: Any
    # ``(grad_norm, nonfinite)`` where the step computes the health
    # scalars, else None
    health: Any = None
    # the step's own facts, by the keys of ``train/step_fns.py:FACTS``
    facts: Any = None


class Trainer:
    """The contract between the worker's loop (``worker/worker.py``)
    and what it drives: every member the loop reads, with the value a
    trainer that has nothing to say leaves in place."""

    # what ``build_trainer`` may hand the constructor besides the
    # model, the loss, the optimizer, ``compute_dtype`` and ``seed``:
    # of "mesh", "sharding_rules", "batch_spec", "grad_accum_steps"
    takes = frozenset()
    # the constructor takes ``specs`` and ``ps_client``: embedding
    # tables on parameter servers, the dense model on the device
    sparse = False
    # the mesh spans jax processes: the loop keeps them in step through
    # ``consensus`` and ``process_count`` and saves what
    # ``checkpoint_state`` returns (``parallel/multihost_trainer.py``)
    lockstep = False
    # ``train_stream`` pipelines the parameter servers' pulls and
    # pushes under the device step (``train/sparse.py``)
    streams = False

    # the dtype the step computes in (None: the parameters' float32)
    compute_dtype = None
    # the newest step's facts still on the device, by the keys of
    # ``train/step_fns.py:FACTS``; they travel with the step's
    # ``PendingStep`` and are fetched on the steps the loop logs
    facts = None
    # the newest step's health scalars still on the device,
    # ``(grad_norm, nonfinite)``, where the step computes them
    health_scalars = None
    # a ``train/health.py:HealthTracker`` where the step is watched
    health = None
    # a ``train/device_tier.py:DeviceEmbeddingTier`` where rows live
    # on the device
    device_tier = None
    # a ledger of the trainer's own (``common/timing_utils.Timing``)
    # where the step has phases the loop cannot see
    timing = None
    # the compiled step by XLA's cost model, 0.0 until it compiled
    cost_step_flops = 0.0
    cost_step_bytes = 0.0
    # the dense plane's mesh and modelled traffic, where there is one
    mesh_shape_str = ""
    collective_bytes_per_step = 0.0
    brownout_skipped_pushes = 0
    # a TrainState-shaped tree of shardings a restore lays the state
    # out with; None: on the default device
    state_shardings = None

    def create_state(self, sample_features):
        raise NotImplementedError

    def ensure_state(self, state, batch):
        if state is None:
            with timing_utils.current().phase("state_init"):
                state = self.create_state(batch["features"])
            # the state is on the device and no step program is
            # loaded: what the state costs
            device_obs.journal_memory("state_init")
            # and what a model of several kinds of mixer is made of
            # and what runs them at this batch's length
            # (``MoeTransformerLM.mixer_kinds``): constants, said once
            kinds = getattr(getattr(self, "_model", None), "mixer_kinds", None)
            kinds = kinds and kinds(
                seq=np.shape(batch["features"])[-1], dtype=self.compute_dtype)
            if kinds:
                events.emit("mixer_kinds", **kinds)
        return state

    def train_step(self, state, batch):
        """``(new state, loss)``: the step is dispatched and NOTHING of
        it is fetched, so the call returns while the device still runs
        it. What the step hands out beside the state (its loss, its
        health scalars, its facts) stays on the device until
        ``read_step`` is given the step's ``pending_step``; the
        worker's loop does that one step late, with the next step
        already queued."""
        raise NotImplementedError

    def pending_step(self, loss):
        """The record of the step ``train_step`` just dispatched, to be
        taken before the next call: its loss, and what the trainer
        holds of it on the device."""
        return PendingStep(loss, self.health_scalars, self.facts)

    def read_step(self, pending, with_facts=False):
        """``(loss, facts)`` of a dispatched step on the host: ONE
        transfer of one tree (the loss, the health scalars where the
        step has them, the facts' leaves on a step that is logged),
        which waits for the device to finish that step
        (``device_wait``), then the sentinels over it (``health``:
        ``train/health.py:HealthTracker.observe``, which raises
        ``HealthSentinelError`` under ``halt``). Each step is read
        once, in the order of the steps."""
        phase = timing_utils.current().phase
        with phase("device_wait"):
            loss, health, facts = jax.device_get((
                pending.loss, pending.health,
                pending.facts if with_facts else None,
            ))
        loss = float(loss)
        if health is not None:
            with phase("health"):
                self.health.observe(loss, *health)
        return loss, facts or {}

    def eval_step(self, state, batch):
        """The model's outputs for the batch, on the host."""
        raise NotImplementedError

    def abstract_state(self, sample_features):
        """The restore's template as shapes alone, or None where the
        trainer has to initialise a state to know them."""
        return None

    def adopt_restored(self, restored):
        return restored

    def join_pushes(self):
        """Wait for gradients still on their way to a parameter
        server."""

    def flush_device_tier(self):
        """Write the device tier's dirty rows back."""

    def close(self):
        """Release what the trainer holds, at the end of its life."""


def trainer_class(processes=1, devices=1, sparse=False, factory=None):
    """The class that trains a dense or a ``sparse`` model on
    ``devices`` devices of ``processes`` jax processes. More than one
    device: the SPMD trainer over the chip mesh (gradients ride ICI
    inside the compiled step); more than one process: the lockstep
    trainer, whose mesh spans them (dp psums ride DCN). ``factory``
    overrides the count's choice; a dense class handed in for a sparse
    model stands for its sparse composition."""
    from elasticdl_tpu.parallel.multihost_trainer import (
        MultiHostSpmdTrainer,
    )
    from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
    from elasticdl_tpu.train.sparse import SparseTrainer
    from elasticdl_tpu.train.sparse_spmd import (
        MultiHostSparseSpmdTrainer,
        SparseSpmdTrainer,
    )

    if factory is None:
        factory = (
            MultiHostSpmdTrainer if processes > 1
            else SpmdTrainer if devices > 1
            else JaxTrainer
        )
    if not sparse or getattr(factory, "sparse", False):
        return factory
    for dense, composed in (
        (MultiHostSpmdTrainer, MultiHostSparseSpmdTrainer),
        (SpmdTrainer, SparseSpmdTrainer),
        (JaxTrainer, SparseTrainer),
    ):
        if isinstance(factory, type) and issubclass(factory, dense):
            return composed
    raise ValueError(
        "trainer factory %r cannot drive the host-PS sparse path and "
        "has no sparse composition; use SparseTrainer, SpmdTrainer, or "
        "MultiHostSpmdTrainer (or a Trainer that is sparse)"
        % (factory,)
    )


def build_trainer(spec, factory=None, *, minibatch_size, compute_dtype=None,
                  seed=0, mesh_config=None, grad_accum_steps=1,
                  ps_client=None, cache_staleness=0):
    """The trainer for a zoo's ``spec``: ``factory`` (None: the
    single-device trainer) or its sparse composition where the model
    declares embedding tables, handed what its class ``takes``."""
    sparse = bool(spec.sparse_embedding_specs)
    factory = trainer_class(sparse=sparse, factory=factory)
    kwargs = dict(
        loss_fn=spec.loss,
        optimizer=spec.optimizer(),
        compute_dtype=compute_dtype,
        seed=seed,
    )
    if sparse:
        kwargs["specs"] = spec.sparse_embedding_specs(
            batch_size=minibatch_size
        )
        kwargs["ps_client"] = ps_client
        if cache_staleness > 0:
            kwargs["cache_staleness"] = cache_staleness
    if grad_accum_steps > 1:
        if "grad_accum_steps" in factory.takes:
            kwargs["grad_accum_steps"] = grad_accum_steps
        else:
            logger.warning(
                "--grad_accum_steps ignored: trainer %s does not "
                "support it", factory.__name__,
            )
    if "sharding_rules" in factory.takes and spec.sharding_rules:
        kwargs["sharding_rules"] = spec.sharding_rules()
    if "batch_spec" in factory.takes and spec.batch_spec:
        kwargs["batch_spec"] = spec.batch_spec()
    mesh = None
    if "mesh" in factory.takes:
        from elasticdl_tpu.parallel.mesh import build_mesh

        if mesh_config is None and spec.mesh_config:
            mesh_config = spec.mesh_config(jax.device_count())
        # built here even without a mesh flag (every device on dp) so
        # a mesh-aware model always receives the mesh its trainer
        # shards over
        mesh = kwargs["mesh"] = build_mesh(mesh_config)
    # Mesh-aware models (pipeline stages over pp, ring attention over
    # sp) take the mesh at construction so their internal shard_map
    # schedules target the same mesh the trainer shards over.
    if "mesh" in inspect.signature(spec.custom_model).parameters:
        kwargs["model"] = spec.custom_model(mesh=mesh)
    else:
        kwargs["model"] = spec.custom_model()
    return factory(**kwargs)


class JaxTrainer(Trainer):
    takes = frozenset({"grad_accum_steps"})

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
        self.compute_dtype = compute_dtype = resolve_dtype(compute_dtype)
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

    def train_step(self, state, batch):
        """One step's dispatch: the call of the jitted step until it
        returns (``dispatch`` in the loop thread's ledger; the batch's
        transfer to the device is implicit in it). Nothing is fetched:
        the health scalars and the facts stay on the device for
        ``read_step``, which the loop calls a step late. A
        skip-sentinel batch already kept its state in-graph (nothing
        else to drop on the dense path: there is no PS push), so a
        skip is only COUNTED when it is read; under ``halt`` the read
        raises, at most one step after the step that tripped and
        before any checkpoint of its state (the loop reads the step in
        flight before it saves)."""
        phase = timing_utils.current().phase
        state = self.ensure_state(state, batch)
        from elasticdl_tpu.testing import faults

        batch = faults.maybe_poison_batch(batch)
        if not self._health_on:
            with phase("dispatch"):
                return self._train_step(state, batch)
        with phase("dispatch"):
            state, loss, scalars = self._train_step(state, batch)
        self.facts = facts_of(scalars)
        self.health_scalars = (scalars["grad_norm"], scalars["nonfinite"])
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
