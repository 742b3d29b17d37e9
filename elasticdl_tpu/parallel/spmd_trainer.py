"""SPMD trainer: the multi-chip data plane.

This is the TPU-native replacement for the reference's entire gradient
communication stack — Horovod allreduce (worker/allreduce_trainer.py) and
the PS push_gradients path (ps/servicer.py, go/pkg/ps/server.go) both
collapse into sharding annotations on one jitted step: batch sharded over
the data axes, parameters replicated (DP) or sharded (fsdp=ZeRO, tp),
and XLA emits the psum/all-gather/reduce-scatter over ICI.

The trainer presents the same create_state/train_step/eval_step surface
as worker/trainer.JaxTrainer, so the Worker is oblivious to whether it
drives one chip or a slice.
"""

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.common import timing_utils
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.parallel.dense_plane import plan_dense_plane
from elasticdl_tpu.parallel.mesh import (
    MeshConfig,
    batch_sharding,
    build_mesh,
    data_parallel_size,
)
from elasticdl_tpu.parallel.sharding import (
    ShardingRules,
    infer_state_shardings,
)
from elasticdl_tpu.train.step_fns import make_eval_step, make_train_step
from elasticdl_tpu.train.train_state import (
    abstract_train_state,
    create_train_state,
    resolve_dtype,
)
from elasticdl_tpu.worker.trainer import Trainer

logger = _logger_factory("elasticdl_tpu.parallel.spmd_trainer")


class SpmdTrainer(Trainer):
    takes = frozenset(
        {"mesh", "sharding_rules", "batch_spec", "grad_accum_steps"}
    )

    def __init__(
        self,
        model,
        loss_fn,
        optimizer,
        compute_dtype=None,
        seed=0,
        mesh=None,
        mesh_config: MeshConfig = None,
        sharding_rules: ShardingRules = None,
        batch_spec=None,
        grad_accum_steps=1,
    ):
        self._model = model
        self._tx = optimizer
        self._rng = jax.random.PRNGKey(seed)
        self.mesh = mesh if mesh is not None else build_mesh(mesh_config)
        self._rules = sharding_rules
        self.compute_dtype = compute_dtype = resolve_dtype(compute_dtype)
        self._train_step_fn = make_train_step(
            model, loss_fn, optimizer, compute_dtype,
            grad_accum_steps=grad_accum_steps,
            # the model's facts (``train/step_fns.py:FACTS``) leave the
            # step replicated and unfetched; no health scalars here
            with_facts=True,
        )
        self._eval_step_fn = make_eval_step(model, compute_dtype)
        # batch_spec overrides the default dim-0-over-data-axes layout
        # (e.g. transformers with sequence parallelism shard dim 1 over
        # sp: P(("dp","fsdp"), "sp")). Applied per leaf, truncated to the
        # leaf's rank (the scalar-per-row _mask ignores the seq axis).
        self._batch_spec = batch_spec
        self._batch_sharding = batch_sharding(self.mesh)
        self._state_shardings = None
        self._train_step = None
        self._eval_step = None
        # dense data plane: derived at create_state (needs the param
        # tree); exported to the worker TelemetryBlob via the
        # dense-plane properties below
        self.dense_plan = None
        logger.info(
            "SPMD mesh %s (%d-way data parallel)",
            dict(self.mesh.shape),
            data_parallel_size(self.mesh),
        )

    # ------------------------------------------------------------------
    def create_state(self, sample_features):
        # Sharded init: shardings are inferred from an eval_shape
        # skeleton (no buffers), then the whole init runs under one jit
        # with out_shardings — XLA materializes every leaf directly in
        # its target layout, so a ZeRO/fsdp-sharded model larger than
        # one device's HBM initializes without ever existing whole on
        # any single device (tests/test_spmd_trainer.py asserts the
        # per-device live-byte bound).
        init_rng, self._rng = jax.random.split(self._rng)
        abstract = abstract_train_state(
            self._model, self._tx, init_rng, sample_features
        )
        self._state_shardings = infer_state_shardings(
            abstract, self.mesh, self._rules
        )
        self._set_dense_plan(abstract.params)
        with self.mesh:
            state = jax.jit(
                lambda rng, feats: create_train_state(
                    self._model, self._tx, rng, feats
                ),
                out_shardings=self._state_shardings,
            )(init_rng, sample_features)
        self._train_step = None
        self._eval_step = None
        self._log_placement(state)
        return state

    def _log_placement(self, state):
        """Where the state landed: leaves and bytes per PartitionSpec,
        and what each device's allocator holds — the multi-chip
        counterpart of the worker's ``devices:`` line (everything on
        device 0 is the failure this makes visible). On a mesh that
        spans processes only this process's devices can be asked:
        ``memory_stats`` raises for a device another process owns."""
        by_spec = {}
        for leaf in jax.tree_util.tree_leaves(state):
            spec = str(getattr(leaf.sharding, "spec", leaf.sharding))
            count, nbytes = by_spec.get(spec, (0, 0))
            by_spec[spec] = (count + 1, nbytes + leaf.nbytes)
        local = [
            device for device in self.mesh.devices.flat
            if device.process_index == jax.process_index()
        ]
        in_use = [
            (device.memory_stats() or {}).get("bytes_in_use")
            for device in local
        ]
        logger.info(
            "SPMD state placement: %s; bytes in use on this process's "
            "%d of %d devices: %s",
            "; ".join(
                "%s x%d %.1f MB" % (spec, count, nbytes / 1e6)
                for spec, (count, nbytes) in sorted(by_spec.items())
            ),
            len(local), self.mesh.size, in_use,
        )

    def _set_dense_plan(self, abstract_params):
        self.dense_plan = plan_dense_plane(
            abstract_params, self.mesh, self._rules
        )
        summary = self.dense_plan.summary()
        logger.info(
            "dense plane: mesh %s, %d reduce-scatter / %d psum / %d "
            "local params, %.1f MB dense state, ~%.1f MB collective "
            "traffic per step (PS carries none of it)",
            summary["mesh_shape"],
            summary["reduce_scatter_params"],
            summary["psum_params"],
            summary["local_params"],
            summary["param_bytes"] / 1e6,
            summary["collective_bytes_per_step"] / 1e6,
        )

    def _log_program_collectives(self):
        """The plan's modelled traffic beside what the compiled step
        holds (the compile ledger's count of the program's collective
        instructions, ``observability/device.py:collective_stats``):
        the model assumes ZeRO (gradients reduce-scatter, float32) and
        the program is what the partitioner made of the activations'
        and parameters' layouts, so a program that moves activations
        instead of weights reads off this line."""
        stats = self.program_collectives
        if stats is None:
            return
        logger.info(
            "dense plane: modelled ~%.1f MB collective traffic per "
            "step; the compiled train step holds, in collective "
            "results a device, %s",
            self.collective_bytes_per_step / 1e6,
            device_obs.collectives_text(stats),
        )

    def abstract_state(self, sample_features):
        """Shape/dtype skeleton of create_state without materializing any
        buffers — the restore template for checkpoint resume. Also
        computes state_shardings over the current mesh (restore re-lays
        the checkpoint out with them, so resume onto a different
        topology never touches the save-time layout)."""
        init_rng, _ = jax.random.split(self._rng)
        abstract = abstract_train_state(
            self._model, self._tx, init_rng, sample_features
        )
        self._state_shardings = infer_state_shardings(
            abstract, self.mesh, self._rules
        )
        self._set_dense_plan(abstract.params)
        self._train_step = None
        self._eval_step = None
        return abstract

    def _leaf_sharding(self, leaf):
        if self._batch_spec is None:
            return self._batch_sharding
        spec = P(*tuple(self._batch_spec)[: np.ndim(leaf)])
        return NamedSharding(self.mesh, spec)

    def _shard_tree(self, tree):
        return jax.tree_util.tree_map(self._leaf_sharding, tree)

    def _build_steps(self, batch):
        # jit wrapping is deferred to the first batch because the batch
        # shardings are per-leaf (rank-dependent) when a batch_spec is
        # set.
        replicated = NamedSharding(self.mesh, P())
        # recompile sentinels (ISSUE 18): the SPMD step carries the
        # same instrumentation as the single-chip JaxTrainer — compile
        # ledger, cost model, signature provenance — so the worker's
        # telemetry and the recompile_storm detector see the dense
        # plane exactly like any other step function
        self._train_step = device_obs.instrumented_jit(
            self._train_step_fn,
            name="spmd_train_step",
            in_shardings=(self._state_shardings, self._shard_tree(batch)),
            out_shardings=(self._state_shardings, replicated, replicated),
            donate_argnums=(0,),
        )
        self._eval_step = device_obs.instrumented_jit(
            self._eval_step_fn,
            name="spmd_eval_step",
            in_shardings=(
                self._state_shardings,
                self._shard_tree(batch["features"]),
            ),
            out_shardings=replicated,
        )

    @property
    def cost_step_flops(self):
        """XLA cost-model FLOPs of the last-compiled train step (0.0
        before the first compile or with device obs off)."""
        return float(getattr(self._train_step, "cost_flops", 0.0))

    @property
    def cost_step_bytes(self):
        return float(getattr(self._train_step, "cost_bytes", 0.0))

    @property
    def program_collectives(self):
        """``collective_stats`` of the last-compiled train step (None
        before the first compile or with device obs off)."""
        return getattr(self._train_step, "collectives", None)

    # dense-plane telemetry (this PR): the worker folds these into the
    # TelemetryBlob so FleetMonitor /statusz and postmortem timelines
    # can show what the dense plane looks like per worker
    @property
    def mesh_shape_str(self):
        return (
            self.dense_plan.mesh_shape_str()
            if self.dense_plan is not None
            else ""
        )

    @property
    def collective_bytes_per_step(self):
        return float(
            self.dense_plan.collective_bytes_per_step
            if self.dense_plan is not None
            else 0.0
        )

    @property
    def state_shardings(self):
        """TrainState-shaped tree of NamedShardings (None before
        create_state); checkpoint restore re-lays state out with these."""
        return self._state_shardings

    # ------------------------------------------------------------------
    def shard_batch(self, batch):
        """Host numpy batch -> sharded device arrays (one transfer)."""
        dp = data_parallel_size(self.mesh)
        leaves = jax.tree_util.tree_leaves(batch)
        if leaves and leaves[0].shape[0] % dp != 0:
            raise ValueError(
                "Global batch %d not divisible by data-parallel size %d"
                % (leaves[0].shape[0], dp)
            )
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, self._leaf_sharding(leaf)),
            batch,
        )

    def train_step(self, state, batch):
        """One step, in the phases of the loop thread's ledger: the
        batch's transfer (``h2d``) and the call of the jitted step
        until it returns (``dispatch``). Nothing is fetched here: the
        step's facts go with its ``pending_step`` to ``read_step``
        (``worker/trainer.py``), a step late and on the steps the loop
        logs alone, so between them the loop runs ahead of the device
        and the runtime's back-pressure is time inside ``dispatch``."""
        phase = timing_utils.current().phase
        state = self.ensure_state(state, batch)
        with phase("h2d"):
            sharded = self.shard_batch(batch)
        first = self._train_step is None
        if first:
            self._build_steps(batch)
            self._log_batch_split(sharded["features"])
        with phase("dispatch"):
            state, loss, self.facts = self._train_step(state, sharded)
        if first:
            self._log_program_collectives()
        return state, loss

    @staticmethod
    def _log_batch_split(features):
        """How the first GLOBAL batch was split (read off the sharded
        arrays: on a mesh that spans processes the host batch is only
        this process's rows)."""
        for leaf in jax.tree_util.tree_leaves(features):
            shard = leaf.sharding.shard_shape(leaf.shape)
            logger.info(
                "SPMD batch: features %s %s split into %d shards of %s",
                leaf.shape, leaf.sharding.spec,
                leaf.size // max(1, int(np.prod(shard))), shard,
            )

    def eval_step(self, state, batch):
        if self._eval_step is None:
            self._build_steps(batch)
        features = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, self._leaf_sharding(leaf)),
            batch["features"],
        )
        outputs = self._eval_step(state, features)
        return jax.tree_util.tree_map(np.asarray, outputs)
