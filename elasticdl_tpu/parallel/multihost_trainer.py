"""Lockstep SPMD trainer over a mesh spanning jax processes.

Reference parity: the AllReduce training mode
(elasticdl/python/worker/allreduce_trainer.py) — every worker executes
the same step and gradients are all-reduced across hosts. TPU redesign:
instead of Horovod ops around an eager step, the *mesh spans the
processes* — each process contributes its local batch as its shard of a
global batch (``jax.make_array_from_process_local_data``) and XLA's
psum over the ``dp`` axis IS the cross-host allreduce (DCN/ICI,
depending on topology).

Lockstep contract: every process must execute the same sequence of
collectives. The elastic task queue hands workers different numbers of
batches, so the worker's lockstep loop (worker.py
``_train_batches_lockstep``) runs a tiny *consensus* collective before
every step — each process reports whether it has a real batch; workers
whose stream ran dry keep stepping on zero-masked empty batches until
the global count reaches zero, and only then does anyone leave the
loop. Partial batches are zero-padded to the fixed minibatch size (the
``_mask`` machinery already weighs padded rows out of the loss).

Failure semantics (measured, not assumed): when any process dies, the
jax coordination service fatally terminates every other process within
its heartbeat timeout. Elastic recovery is therefore *relaunch-based*:
the pod manager restarts workers, they rejoin the master's mesh
rendezvous at the bumped epoch, re-``initialize`` with the new world,
and resume from the checkpoint — exactly the reference's
re-init-and-reload flow (allreduce_trainer.py:66-118), with
checkpoint restore replacing Horovod's broadcast-from-rank-0.

v2 layout contract: data parallelism (``dp``) spans processes/hosts
(gradients psum over DCN); model-parallel axes (fsdp/tp/sp/ep) may take
any extent that fits within one process's local devices — the
"dp rides DCN, model parallelism rides ICI" placement, e.g. a v5p-32
job as 4 processes x 8 chips with ``dp=4, fsdp=8``. Checkpoints are
*make_array-aware*: save hands orbax the global jax.Arrays (its writes
are cross-process collectives) and restore materializes directly into
the current mesh's shardings, so resume onto a different world size
re-shards implicitly. Cross-process *state* sharding (ZeRO over DCN)
also trains/saves/restores; only the process-local eval pull
(``local_state``) rejects it, since a single process no longer holds a
full cover of every leaf.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.common.annotations import hot_path
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer

logger = _logger_factory("elasticdl_tpu.parallel.multihost_trainer")


class LockstepMixin:
    """The cross-process lockstep runtime shared by the dense
    (MultiHostSpmdTrainer) and sparse (MultiHostSparseSpmdTrainer,
    train/sparse_spmd.py) multi-host trainers: the consensus
    collective, global-array plumbing, and the make_array-aware
    checkpoint surface. Hosts must call ``_init_lockstep()`` after
    ``self.mesh`` exists; ``self._state_shardings`` is owned by the
    concrete trainer."""

    lockstep = True

    def _init_lockstep(self):
        self._process_count = jax.process_count()
        self._replicated = NamedSharding(self.mesh, P())
        self._consensus = jax.jit(
            lambda flags: jnp.sum(flags, axis=0),
            out_shardings=self._replicated,
        )
        self._consensus_sharding = NamedSharding(self.mesh, P("dp"))

    @property
    def process_count(self):
        return self._process_count

    # -- global array plumbing -----------------------------------------
    def _put_global(self, tree, shardings):
        """Host numpy -> global jax.Arrays; every process must hold (or
        be able to compute) identical full values for replicated leaves
        and the full array for sharded ones (true for same-seed init
        and for checkpoint restores, which read the same files)."""
        def put(leaf, sharding):
            arr = np.asarray(leaf)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx, arr=arr: arr[idx]
            )

        return jax.tree_util.tree_map(put, tree, shardings)

    # -- lockstep consensus --------------------------------------------
    @hot_path
    def consensus(self, have_data, stream_ended=False):
        """Returns (alive, ended): how many processes hold a real batch
        this round, and how many have PERMANENTLY exhausted their task
        stream. A collective — every process must call it once per loop
        iteration. The two bits are distinct because batch acquisition
        is a non-blocking poll (worker.py _BatchPoller): ``not
        have_data`` can mean "nothing this round" (master said WAIT),
        which must not be mistaken for "done" — a worker exiting on a
        transient all-idle round would strand its peers' next
        consensus forever."""
        flags = jax.make_array_from_process_local_data(
            self._consensus_sharding,
            np.tile(
                np.array(
                    [[1.0 if have_data else 0.0,
                      1.0 if stream_ended else 0.0]],
                    np.float32,
                ),
                (jax.local_device_count(), 1),
            ),
        )
        # flags are per-device; normalize to per-process counts
        sums = np.asarray(self._consensus(flags))
        per = jax.local_device_count()
        return (
            int(round(float(sums[0]) / per)),
            int(round(float(sums[1]) / per)),
        )

    # -- checkpoint surface (make_array-aware, v2) ---------------------
    def checkpoint_state(self, state):
        """What the worker hands the checkpoint manager: the GLOBAL
        jax.Array state, unchanged. orbax's save is a cross-process
        collective — every rank calls it (the lockstep loop guarantees
        same-version alignment) and each process writes the shards it
        holds, so fsdp/tp-sharded state checkpoints without ever being
        gathered onto one host."""
        return state

    def local_state(self, state):
        """Pull the full state to host numpy WITHOUT communication, by
        stitching this process's addressable shards. Valid for the v2
        layout contract (model-parallel axes within a process): every
        leaf's addressable shards cover the whole array. State sharded
        over a cross-process axis raises — a single process does not
        hold it, and pulling it would require a collective the
        per-worker eval path must not issue."""

        def pull(leaf):
            if not isinstance(leaf, jax.Array) or leaf.is_fully_addressable:
                return np.asarray(leaf)
            out = np.empty(leaf.shape, leaf.dtype)
            seen = {}
            for shard in leaf.addressable_shards:
                key = tuple(
                    (s.start, s.stop, s.step) for s in shard.index
                )
                if key in seen:
                    continue
                data = np.asarray(shard.data)
                seen[key] = data.size
                out[shard.index] = data
            if sum(seen.values()) < out.size:
                raise ValueError(
                    "state leaf %s x %s is sharded over a cross-process "
                    "mesh axis; this process holds %d of %d elements. "
                    "Process-local eval/pull supports model-parallel "
                    "axes within a process (dp-over-DCN layout) only."
                    % (leaf.shape, leaf.dtype, sum(seen.values()),
                       out.size)
                )
            return out

        return jax.tree_util.tree_map(pull, state)

    def adopt_restored(self, restored):
        """Accept a restored state: global jax.Arrays (the v2 restore
        path, already laid out by orbax) pass through; host arrays (a
        template-shaped local restore or fresh init) are laid out over
        the global mesh."""
        if self._state_shardings is None:
            raise RuntimeError("call abstract_state/create_state first")
        pairs = zip(
            jax.tree_util.tree_leaves(restored),
            jax.tree_util.tree_leaves(self._state_shardings),
        )
        if all(
            isinstance(leaf, jax.Array) and leaf.sharding == sharding
            for leaf, sharding in pairs
        ):
            # the worker restored into ``state_shardings``: orbax
            # already materialized every leaf into the current mesh's
            # layout (true at any world size, since it materializes
            # into these shardings and not the save-time layout — a
            # host-numpy round trip here would double restore latency
            # for nothing)
            return restored
        restored = jax.tree_util.tree_map(np.asarray, restored)
        return self._put_global(restored, self._state_shardings)


class MultiHostSpmdTrainer(LockstepMixin, SpmdTrainer):
    """SpmdTrainer whose mesh spans every jax process."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._init_lockstep()

    def create_state(self, sample_features):
        # The sharded jit init (SpmdTrainer.create_state) runs as one
        # SPMD program over the process-spanning mesh — no process ever
        # materializes the full state. Features are zeroed first: a jit
        # under a multi-process mesh implicitly replicates host
        # operands, which ASSUMES identical values on every process;
        # zeros make that true (flax init derives parameter values from
        # the rng — shared seed — not from the batch).
        zeros = jax.tree_util.tree_map(
            lambda leaf: np.zeros_like(np.asarray(leaf)), sample_features
        )
        return super().create_state(zeros)

    def shard_batch(self, local_batch):
        """This process's batch is its shard of the global batch: the
        global batch dim is process_count * local rows."""
        return jax.tree_util.tree_map(
            lambda leaf: jax.make_array_from_process_local_data(
                self._leaf_sharding(leaf), np.asarray(leaf)
            ),
            local_batch,
        )

    # abstract_state: inherited — the eval_shape skeleton +
    # infer_state_shardings logic is identical to SpmdTrainer's.

    # -- eval: local compute on the pulled replica ---------------------
    def eval_step(self, state, batch):
        """Eval tasks are per-worker (not collective): run them on a
        process-local jit against the pulled state replica. The pull is
        cached per state object — an eval task's batches all score the
        same state, so the device->host transfer happens once per task,
        not once per batch."""
        if self._local_eval_step is None:
            # _eval_step_fn already carries the trainer's compute dtype
            self._local_eval_step = jax.jit(self._eval_step_fn)
        if self._eval_cache is None or self._eval_cache[0] is not state:
            self._eval_cache = (state, self.local_state(state))
        local = self._eval_cache[1]
        outputs = self._local_eval_step(local, batch["features"])
        return jax.tree_util.tree_map(np.asarray, outputs)

    _local_eval_step = None
    _eval_cache = None
