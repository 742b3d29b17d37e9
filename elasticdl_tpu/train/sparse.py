"""Sparse embedding training: host PS tables + on-device combine.

This is the TPU answer to the reference's EmbeddingDelegate
(elasticdl/python/elasticdl/embedding_delegate.py), which escaped the TF
graph mid-forward via tf.py_function to pull rows. Escaping a jitted XLA
step mid-forward would stall the TPU pipe, so the lookup moves *before*
the step (SURVEY.md §7 "pre-step gather"):

  host:   ids -> unique -> pull rows from PS (PSClient, id-mod sharded)
  device: jitted step takes rows as an INPUT, gathers + combines on the
          MXU-friendly dense side, and returns d(loss)/d(rows)
  host:   push row gradients back to the PS as IndexedSlices

Static shapes: the unique-id buffer is padded to a fixed per-spec
capacity so XLA compiles the step once.
"""

import concurrent.futures

import grpc
import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common import overload
from elasticdl_tpu.common.annotations import hot_path
from elasticdl_tpu.common.env_utils import env_str
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.common.tensor_utils import deduplicate_indexed_slices
from elasticdl_tpu.data.pipeline import MASK_KEY
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import events
from elasticdl_tpu.observability import trace
# HotRowCache lives in the extracted embedding-client library (ISSUE 8)
# so the serving tier shares the training pull/cache stack; re-exported
# here for the long-standing import path.
from elasticdl_tpu.embedding.client import (  # noqa: F401
    EmbeddingClient,
    HotRowCache,
)
from elasticdl_tpu.train.losses import masked_mean
from elasticdl_tpu.train.train_state import (
    TrainState,
    cast_floating,
    create_train_state,
    resolve_dtype,
)
from elasticdl_tpu.worker.trainer import Trainer

logger = _logger_factory("elasticdl_tpu.train.sparse")

# Double-buffered async push (ISSUE 5): step N's gradient push runs on
# a background executor while step N+1's pull/forward/backward
# computes; a depth-1 bounded-staleness barrier (SparseTrainer
# .join_pushes) joins it before the next push is submitted and before
# any eval/checkpoint boundary. Opt-in, async-PS only — the sync PS's
# rejection/retry protocol needs the synchronous step.
ASYNC_PUSH_ENV = "EDL_ASYNC_PUSH"

ROWS_SUFFIX = "__rows"
INDICES_SUFFIX = "__indices"
# planted by SparseBatchPreparer when a spec has mask_feature_key: bool
# [B, F] marking real (non-padding) slots, consumed by embedding_lookup
SLOT_MASK_SUFFIX = "__slotmask"


class SparseEmbeddingSpec:
    """One host-side embedding table used by a model.

    feature_key: the feature holding int ids, shape [B] or [B, F].
    capacity: padded unique-ids buffer size (static shape); defaults to
    batch_size * F at prepare time if 0.
    """

    def __init__(self, name, dim, feature_key=None, combiner="sum",
                 capacity=0, init_scale=0.05, mask_feature_key=None,
                 initializer="uniform"):
        self.name = name
        self.dim = dim
        self.feature_key = feature_key or name
        self.combiner = combiner
        self.capacity = capacity
        self.init_scale = init_scale
        # row initializer kind: uniform / constant / normal /
        # truncated_normal / zeros (reference initializer.go:25-155)
        self.initializer = initializer
        # optional bool feature marking which id slots are real: padded
        # slots are excluded from the unique-id pull/push so padding
        # never creates or updates PS rows (id 0 would otherwise absorb
        # spurious optimizer steps from every padded batch)
        self.mask_feature_key = mask_feature_key


def _wire_initializer(spec):
    """Wire string for EmbeddingTableInfo.initializer: a bare float for
    uniform (the original encoding) else "kind:param". float() first:
    numpy scalars repr as np.float64(...) under numpy 2, which the
    server side cannot parse."""
    if spec.initializer in (None, "uniform"):
        return str(float(spec.init_scale))
    return "%s:%s" % (spec.initializer, float(spec.init_scale))


def embedding_lookup(features, name, combiner=None):
    """Model-side: gather pulled rows and combine over the feature axis.

    rows: [capacity, dim]; indices: [B] or [B, F] positions into rows.
    Returns [B, dim] (combined) or [B, F, dim] when combiner is None.
    """
    rows = features[name + ROWS_SUFFIX]
    indices = features[name + INDICES_SUFFIX]
    gathered = rows[indices]  # [B, dim] or [B, F, dim]
    mask = features.get(name + SLOT_MASK_SUFFIX)
    if gathered.ndim == 2 or combiner is None:
        if mask is not None and gathered.ndim == 3:
            # padded slots index row 0 of the pulled buffer; zero them
            gathered = gathered * jnp.asarray(mask, gathered.dtype)[
                ..., None
            ]
        return gathered
    if combiner not in ("sum", "mean", "sqrtn"):
        raise ValueError("unknown combiner %r" % combiner)
    from elasticdl_tpu.preprocessing.feature_column import combine_gathered

    if mask is not None:
        w = jnp.asarray(mask, gathered.dtype)
    else:
        w = jnp.ones(gathered.shape[:2], gathered.dtype)
    return combine_gathered(gathered, w, combiner)




class PullInfo(dict):
    """``{table: (push_ids, n)}`` for the gradient push, plus the
    device-tier step context riding as attributes (slots / push
    positions per table, and the tier epoch the lookups ran under) —
    consumers that treat it as a plain mapping are unaffected."""

    tier_ctx = None
    tier_epoch = None


class SparseBatchPreparer:
    """Host-side: swap raw id features for (rows, indices) pairs.

    With a device tier attached, each table's unique ids are looked up
    in the HBM hot set first; only the misses reach the HotRowCache /
    PS pull path, and ids promoted this step leave the PS push set
    entirely (their gradients apply in-device). Pulls for all tables
    fan out concurrently (DeepFM's second-order and linear tables ride
    one round trip instead of two), and an optional HotRowCache bounds
    how often hot rows are re-pulled.
    """

    def __init__(self, specs, ps_client, cache=None, device_tier=None,
                 read_only=False):
        self._specs = list(specs)
        self._ps = ps_client
        self._registered = False
        # Read-only consumers (the serving tier, ISSUE 8) never write:
        # table infos are not pushed (the tables were created by the
        # training job this serves), and a PS relaunch only invalidates
        # the cache — there is no model to re-register.
        self._read_only = bool(read_only)
        if cache is not None and device_tier is not None:
            # The tier SUPERSEDES the hot-row cache: resident rows are
            # served from device, and the residual misses are
            # tail/cold ids the cache barely helps. More importantly,
            # a cache-stale row must never become a promotion's staged
            # value — the tier makes resident values AUTHORITATIVE
            # (writebacks raw-overwrite the PS), so promoting a row
            # that is missing the staleness window's PS-applied
            # gradients would erase them permanently. Cache-only and
            # tier-only configurations are both sound; the combination
            # is not, so the tier wins.
            logger.warning(
                "HotRowCache disabled: the device embedding tier owns "
                "the hot set, and stale cached rows must not be "
                "promoted as authoritative tier values"
            )
            cache = None
        # the extracted pull/cache stack (ISSUE 8): this preparer and
        # the serving tier ride the same EmbeddingClient — cache
        # consult/fill, fused multi-table pull, per-table fallback all
        # live there, once
        self._embedding = EmbeddingClient(
            ps_client, cache=cache, read_only=self._read_only
        )
        self._tier = device_tier
        # set by _on_ps_restart (possibly from the async-push thread),
        # consumed at the top of prepare() on the pulling thread
        self._cache_dirty = False
        if not self._read_only and hasattr(ps_client, "resync_hook"):
            # PS crash recovery: when the client detects a relaunched
            # shard (version regression on a push response), re-push the
            # embedding-table infos on the next prepare — a PS that
            # restored nothing must not lazily create tables with
            # default dims/initializers — and drop cached rows that no
            # longer reflect the restored store. The hook slot is
            # single-owner (last writer wins), so a READ-ONLY preparer
            # must not take it: it has no tables to re-register and no
            # device tier, and its deferred cache clear is redundant
            # with the serving engine's own thread-safe hook
            # (serve/engine._chain_resync_hook) — installing here would
            # clobber a co-resident trainer's hook on every
            # ServingModel build.
            ps_client.resync_hook = self._on_ps_restart

    @property
    def ps_num(self):
        return getattr(self._ps, "ps_num", 1)

    @property
    def cache(self):
        return self._embedding.cache

    def _on_ps_restart(self, shard):
        if not self._read_only:
            self._registered = False
        # cached rows were pulled from the dead process's store;
        # staleness bounds don't cover a whole relaunch. The clear is
        # DEFERRED to the next prepare(): under async push this hook
        # fires on the push-executor thread, and HotRowCache has no
        # locking — an immediate clear() here races the main thread's
        # in-flight cache.put, which could re-insert pre-crash rows
        # AFTER the invalidation and keep them for `staleness` more
        # prepares. The flag write is atomic; the clear then runs on
        # the one thread that ever mutates the cache.
        self._cache_dirty = True
        if self._tier is not None:
            # device tier: host maps invalidate NOW (thread-safe), the
            # dirty rows' device values flush back to the restored PS
            # from the dispatch thread before the state resets — the
            # flush-then-invalidate order that makes a PS SIGKILL lose
            # no tier-held updates (device_tier.mark_restart)
            self._tier.mark_restart()

    def register_tables(self):
        if self._read_only:
            return
        if not self._registered:
            self._ps.push_embedding_table_infos(
                [(s.name, s.dim, _wire_initializer(s)) for s in self._specs]
            )
            self._registered = True

    def _pull_tables(self, plans):
        """Pull every table's unique rows for this batch; returns
        {name: (capacity, rows [n_unique, dim] float32)}. The pull
        itself — cache consult/fill, fused multi-table RPC, per-table
        fan-out fallback — is the extracted EmbeddingClient's job
        (embedding/client.py); only the capacity bookkeeping is
        training-specific."""
        rows = self._embedding.pull_tables({
            spec.name: unique
            for spec, unique, _ in plans
            if unique.size
        })
        return {
            spec.name: (capacity, rows[spec.name])
            for spec, unique, capacity in plans
            if unique.size
        }

    # edlint: thread=prepare
    def prepare(self, batch):
        """Returns (batch with rows/indices features, pull_info) where
        pull_info = {name: (push_ids, n)} for the grad push (all unique
        ids without a device tier; only the un-promoted misses with
        one)."""
        self.register_tables()
        if self.cache is not None:
            if self._cache_dirty:
                # deferred PS-relaunch invalidation (_on_ps_restart)
                self._cache_dirty = False
                self._embedding.invalidate()
            self._embedding.advance()
        if self._tier is not None:
            self._tier.advance()
        features = dict(batch["features"])
        # Zero-padded batch rows (lockstep padding, SPMD batch-multiple
        # padding — data/pipeline.pad_batch) must be invisible to the
        # PS: their ids (all 0) would otherwise join the unique-id set,
        # creating/pulling a row the real data never asked for. Beyond
        # waste, that breaks run-to-run comparability: the store's lazy
        # row init draws from a sequential per-table RNG stream, so an
        # extra early row creation shifts every later row's init values.
        # The mask path engages UNCONDITIONALLY whenever the batch has a
        # mask (even all-ones): under multi-process lockstep every
        # worker must compile the SAME program, and a dried-up worker's
        # zero-masked batch growing extra __slotmask features while its
        # peer's full batch lacks them would deadlock the mesh on
        # mismatched collectives.
        batch_mask = None
        if MASK_KEY in batch:
            batch_mask = np.asarray(batch[MASK_KEY]) > 0
        pull_info = PullInfo()
        if self._tier is not None:
            pull_info.tier_ctx = {}
            pull_info.tier_epoch = self._tier.epoch
        consumed = set()
        plans = []
        tier_meta = {}  # name -> (unique, slots, miss_pos)
        for spec in self._specs:
            # multiple tables may read the same id feature (e.g. DeepFM's
            # second-order and linear tables), so consume keys at the end
            ids = np.asarray(features[spec.feature_key])
            consumed.add(spec.feature_key)
            capacity = spec.capacity or int(np.prod(ids.shape))
            mask = None
            if (
                spec.mask_feature_key
                and spec.mask_feature_key in features
            ):
                mask = np.asarray(features[spec.mask_feature_key], bool)
            if batch_mask is not None:
                rows_real = np.broadcast_to(
                    batch_mask.reshape(
                        (-1,) + (1,) * (ids.ndim - 1)
                    ),
                    ids.shape,
                )
                mask = rows_real if mask is None else (mask & rows_real)
            if mask is not None:
                unique, inv_real = np.unique(
                    ids[mask], return_inverse=True
                )
                # padded slots index row 0; the slot-mask feature below
                # zeroes their contribution in embedding_lookup (and
                # mask-aware columns do their own masking)
                inverse = np.zeros(ids.shape, dtype=np.int64)
                inverse[mask] = inv_real
                features[spec.name + SLOT_MASK_SUFFIX] = mask
            else:
                unique, inverse = np.unique(ids, return_inverse=True)
            if unique.size > capacity:
                raise ValueError(
                    "Batch has %d unique ids for table %s (capacity %d); "
                    "raise SparseEmbeddingSpec.capacity"
                    % (unique.size, spec.name, capacity)
                )
            features[spec.name + INDICES_SUFFIX] = inverse.reshape(
                ids.shape
            ).astype(np.int32)
            if self._tier is not None and unique.size:
                # hot-set lookup first: only misses reach the PS path
                slots = self._tier.lookup(spec.name, unique)
                miss_pos = np.nonzero(slots < 0)[0]
                if miss_pos.size:
                    # ordering barrier: a miss id with an eviction
                    # writeback still in flight must not be pulled
                    # until the writeback lands (the pull would read
                    # the pre-writeback value, and the late overwrite
                    # would revert gradients pushed in between)
                    self._tier.wait_for_writebacks(
                        spec.name, unique[miss_pos]
                    )
                tier_meta[spec.name] = (unique, slots, miss_pos)
                plans.append((spec, unique[miss_pos], capacity))
            else:
                plans.append((spec, unique, capacity))
        pulled = self._pull_tables(plans)
        for spec, pull_ids, capacity in plans:
            padded = np.zeros((capacity, spec.dim), dtype=np.float32)
            meta = tier_meta.get(spec.name)
            if meta is None:
                if pull_ids.size:
                    padded[: pull_ids.size] = pulled[spec.name][1]
                features[spec.name + ROWS_SUFFIX] = padded
                pull_info[spec.name] = (pull_ids, pull_ids.size)
                continue
            unique, slots, miss_pos = meta
            fetched = (
                np.asarray(pulled[spec.name][1], np.float32)
                if pull_ids.size
                else np.empty((0, spec.dim), np.float32)
            )
            if miss_pos.size:
                # PS rows land at their miss positions; hit positions
                # stay zero — the tier's fused gather fills them on
                # device at combine time
                padded[miss_pos] = fetched
            promoted, new_slots = self._tier.admit(
                spec.name, pull_ids, fetched
            )
            if promoted.size and promoted.any():
                # promoted ids are hits from THIS step on: their
                # gradient applies in-device to the freshly staged
                # slot, and they leave the PS push set (pushing too
                # would double-apply the step)
                slots = slots.copy()
                slots[miss_pos[promoted]] = new_slots
            push_pos = miss_pos[~promoted] if promoted.size else miss_pos
            push_ids = pull_ids[~promoted] if promoted.size else pull_ids
            slots_padded = np.full((capacity,), -1, np.int32)
            slots_padded[: unique.size] = slots
            features[spec.name + ROWS_SUFFIX] = padded
            pull_info[spec.name] = (push_ids, int(push_ids.size))
            pull_info.tier_ctx[spec.name] = {
                "slots": slots_padded,
                "push_pos": push_pos,
            }
        for key in consumed:
            features.pop(key, None)
        out = dict(batch)
        out["features"] = features
        return out, pull_info

    def push_gradients(self, row_grads, pull_info, model_version=0,
                       only_shards=None, force_empty=False,
                       round_scoped=False):
        grads_by_table = {}
        for name, (unique, n) in pull_info.items():
            if n == 0:
                continue
            grads_by_table[name] = (
                np.asarray(row_grads[name])[:n],
                unique,
            )
        kwargs = {"model_version": model_version}
        if only_shards is not None:
            kwargs["only_shards"] = only_shards
        if force_empty:
            # lockstep: EVERY shard must receive this worker's round —
            # a shard whose id-mod slice happens to be empty this round
            # (or a fully-masked batch) still counts toward the sync
            # PS's grads_to_wait, else that shard's apply cadence
            # drifts behind its peers' (see PSClient.push_gradients)
            kwargs["force_empty"] = True
        if round_scoped:
            # lockstep tags are exact global round counters: tell the
            # sync PS to pair by TAG, not arrival order (proto
            # round_scoped field)
            kwargs["round_scoped"] = True
        return _normalize_push_result(
            self._ps.push_gradients(grads_by_table, **kwargs),
            model_version,
        )


def _normalize_push_result(result, model_version):
    """Client push results are (accepted, version[, rejected_shards]);
    None rejected set means 'unknown — treat every shard as retryable'."""
    if result is None:
        return True, model_version, ()
    parts = tuple(result)
    if len(parts) >= 3:
        # idempotent: a re-normalized (accepted, version, None) must
        # keep its unknown-shards None, not crash in tuple(None)
        rejected = parts[2]
        return (
            parts[0], parts[1],
            None if rejected is None else tuple(rejected),
        )
    accepted, version = parts
    return accepted, version, None if not accepted else ()


def _forward_loss(model, loss_fn, compute_dtype, params, model_state,
                  rows, features, labels, mask, rngs):
    """Shared forward+loss used by the train step and the grad-only
    retry path; returns (masked mean loss, new mutable model state)."""
    if compute_dtype is not None:
        params = cast_floating(params, compute_dtype)
        rows = cast_floating(rows, compute_dtype)
        features = cast_floating(features, compute_dtype)
    merged = {**features, **rows}
    variables = {"params": params, **model_state}
    if model_state:
        outputs, new_model_state = model.apply(
            variables,
            merged,
            training=True,
            rngs=rngs,
            mutable=list(model_state.keys()),
        )
        new_model_state = dict(new_model_state)
    else:
        outputs = model.apply(variables, merged, training=True, rngs=rngs)
        new_model_state = model_state
    per_sample = loss_fn(labels, outputs)
    return masked_mean(per_sample.astype(jnp.float32), mask), new_model_state


def _split_batch(batch, row_keys):
    features = dict(batch["features"])
    labels, mask = batch["labels"], batch[MASK_KEY]
    rows = {key: features.pop(key) for key in row_keys}
    return features, labels, mask, rows


@hot_path
def make_sparse_train_step(model, loss_fn, tx, specs, compute_dtype=None,
                           health=False, guard_nonfinite=False):
    """Train step that also returns d(loss)/d(embedding rows).

    ``health=True`` (ISSUE 15) appends a fourth output — the in-graph
    health scalars (global grad norm over dense AND row gradients +
    nonfinite flag); ``guard_nonfinite`` keeps the previous dense
    state on a nonfinite batch (the skip sentinel — the caller drops
    the matching row-grad push, so the batch contributes nothing
    anywhere). ``health=False`` emits the exact pre-health program."""
    row_keys = [spec.name + ROWS_SUFFIX for spec in specs]

    def train_step(state: TrainState, batch):
        features, labels, mask, rows = _split_batch(batch, row_keys)
        rngs = {
            "dropout": jax.random.fold_in(jax.random.PRNGKey(0), state.step)
        }

        def compute_loss(params, rows):
            return _forward_loss(
                model, loss_fn, compute_dtype, params, state.model_state,
                rows, features, labels, mask, rngs,
            )

        (loss, new_model_state), (param_grads, row_grads) = (
            jax.value_and_grad(compute_loss, argnums=(0, 1), has_aux=True)(
                state.params, rows
            )
        )
        param_grads = cast_floating(param_grads, jnp.float32)
        row_grads = cast_floating(row_grads, jnp.float32)
        updates, new_opt_state = tx.update(
            param_grads, state.opt_state, state.params
        )
        new_params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), state.params, updates
        )
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            model_state=new_model_state,
            opt_state=new_opt_state,
        )
        # strip the suffix for the caller: {table_name: grad rows}
        named = {
            key[: -len(ROWS_SUFFIX)]: value
            for key, value in row_grads.items()
        }
        if not health:
            return new_state, loss, named
        from elasticdl_tpu.train.step_fns import (
            global_grad_norm,
            guard_nonfinite_state,
            health_scalars,
        )

        scalars = health_scalars(
            loss, global_grad_norm(param_grads, row_grads)
        )
        if guard_nonfinite:
            new_state = guard_nonfinite_state(
                state, new_state, scalars["nonfinite"]
            )
        return new_state, loss, named, scalars

    return train_step


@hot_path
def make_row_grads_fn(model, loss_fn, specs, compute_dtype=None):
    """d(loss)/d(rows) at FIXED params — the sync-PS retry path: when a
    push is rejected as stale, fresh rows are pulled and only the row
    gradients are recomputed (dense params were already updated locally;
    reference worker.py:597-649 re-ran the whole minibatch because its
    dense params lived on the PS too)."""
    row_keys = [spec.name + ROWS_SUFFIX for spec in specs]

    def row_grads(state: TrainState, batch):
        features, labels, mask, rows = _split_batch(batch, row_keys)
        rngs = {
            "dropout": jax.random.fold_in(jax.random.PRNGKey(0), state.step)
        }

        def compute_loss(rows):
            loss, _ = _forward_loss(
                model, loss_fn, compute_dtype, state.params,
                state.model_state, rows, features, labels, mask, rngs,
            )
            return loss

        grads = jax.grad(compute_loss)(rows)
        grads = cast_floating(grads, jnp.float32)
        return {
            key[: -len(ROWS_SUFFIX)]: value
            for key, value in grads.items()
        }

    return row_grads


class SparseTrainer(Trainer):
    """Trainer surface (create_state/train_step/eval_step) over dense
    on-device params + host-PS sparse tables."""

    sparse = True
    streams = True

    # the reference retried a rejected minibatch up to 64 times against
    # the sync PS (worker/worker.py:49,608)
    MAX_PUSH_RETRIES = 64
    # lockstep trainers set True: fully-masked batches still push (the
    # sync PS counts pushes, not gradients, toward grads_to_wait)
    FORCE_EMPTY_PUSH = False
    # lockstep trainers set True: their version tags are exact global
    # round counters, so the sync PS pairs their pushes BY TAG instead
    # of arrival order (a worker whose pushes lag its rounds under
    # host contention must not have its round-r and round-r+1 pushes
    # paired with each other — the version-skew churn measured in the
    # SIGKILL chaos tests under full-suite load)
    ROUND_SCOPED_PUSH = False
    # False (lockstep trainers): a version-rejected push is RESENT
    # as-is with the corrected version instead of re-pulling rows and
    # recomputing grads. Sound there because every lockstep round pulls
    # fresh rows — a rejection can only mean the version TAG was stale
    # (e.g. a relaunched worker's counter), not the gradients. The
    # recompute would also be a cross-process collective that a
    # single process must not run alone.
    RETRY_RECOMPUTES = True
    # Device-resident embedding tier (ISSUE 6, train/device_tier.py):
    # hit gradients apply in HBM outside the PS's round/version
    # accounting, so the tier composes with the async PS only; the
    # lockstep multi-host trainer turns it off (its rows buffer is
    # dp-sharded, a different layout contract).
    SUPPORTS_DEVICE_TIER = True

    def __init__(
        self,
        model,
        loss_fn,
        optimizer,
        specs,
        ps_client,
        compute_dtype=None,
        seed=0,
        cache_staleness=0,
        cache_capacity=1_000_000,
        async_push=None,
        device_tier=None,
        health=None,
    ):
        self._model = model
        self._tx = optimizer
        self._rng = jax.random.PRNGKey(seed)
        self._specs = list(specs)
        # Training-health sentinels (ISSUE 15): None reads EDL_HEALTH
        # (default on), False disables, or pass a HealthTracker. With
        # a tracker the jitted step returns the in-graph health
        # scalars as one extra small output; EDL_HEALTH=0 compiles the
        # exact pre-health program (test-asserted).
        from elasticdl_tpu.train.health import maybe_tracker

        if health is None:
            self.health = maybe_tracker(role="worker")
        elif health is False:
            self.health = None
        else:
            self.health = health
        self._health_on = self.health is not None
        self._health_guard = (
            self._health_on and self.health.action == "skip"
        )
        cache = (
            HotRowCache(cache_staleness, cache_capacity)
            if cache_staleness > 0
            else None
        )
        # Device-resident embedding tier (ISSUE 6): None reads
        # EDL_DEVICE_TIER*, False disables, True/DeviceTierConfig
        # opt in programmatically. With the tier off this trainer is
        # bit-exact with the PS-only path (test-enforced).
        from elasticdl_tpu.train.device_tier import resolve_tier_config

        tier_config = resolve_tier_config(device_tier)
        self.device_tier = None
        if tier_config is not None and not self.SUPPORTS_DEVICE_TIER:
            logger.warning(
                "%s does not support the device embedding tier "
                "(dp-sharded rows layout); EDL_DEVICE_TIER ignored",
                type(self).__name__,
            )
            tier_config = None
        if tier_config is not None:
            from elasticdl_tpu.train.device_tier import (
                DeviceEmbeddingTier,
            )

            self.device_tier = DeviceEmbeddingTier(
                self._specs, ps_client, tier_config,
                mesh=self._tier_mesh(),
            )
        self.preparer = SparseBatchPreparer(
            self._specs, ps_client, cache=cache,
            device_tier=self.device_tier,
        )
        compute_dtype = resolve_dtype(compute_dtype)
        from elasticdl_tpu.train.step_fns import make_eval_step

        # subclass hook: the SPMD trainers (train/sparse_spmd.py) defer
        # jitting to the first batch so they can attach mesh shardings
        self._jit_steps(
            make_sparse_train_step(
                model, loss_fn, optimizer, self._specs, compute_dtype,
                health=self._health_on,
                guard_nonfinite=self._health_guard,
            ),
            make_row_grads_fn(model, loss_fn, self._specs, compute_dtype),
            make_eval_step(model, compute_dtype),
        )
        self._version = 0
        # observability: total sync-PS version rejections this trainer
        # has retried through (tests assert the race really raced)
        self.push_rejections = 0
        # Brownout (ISSUE 19): consecutive overload-class push failures
        # absorbed so far, and the lifetime count of pushes dropped —
        # EDL_BROWNOUT_SKIP_AFTER=0 (default) keeps this machinery
        # entirely out of the push path
        self._brownout_streak = 0
        self.brownout_skipped_pushes = 0
        # Async double-buffered push (ASYNC_PUSH_ENV): at most ONE push
        # in flight; train_step joins step N-1's push before submitting
        # step N's, so gradients land at most one step late — inside
        # the async PS's staleness envelope, the same bound
        # train_stream already rides.
        if async_push is None:
            from elasticdl_tpu.common.args import bool_flag

            raw = env_str(ASYNC_PUSH_ENV, "").strip()
            # same bool spellings as every other knob (common/args
            # .bool_flag): "false"/"no" must disable, not silently
            # enable; garbage fails loudly at construction
            async_push = bool(bool_flag(raw)) if raw else False
        self._async_push = bool(async_push)
        self._push_future = None
        self._async_pool = None
        if self._async_push:
            self._async_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="sparse-async-push"
            )
        # memo of the last prepared batch, so ensure_state followed by
        # eval_step/train_step on the same batch pulls rows once
        self._prep_memo = None
        # per-phase wall-clock, in a ledger of the trainer's own:
        # sparse_pull/sparse_push are this design's analogues of the
        # reference's get_model / report_gradient phases
        # (common/timing_utils.py, worker.py:298)
        from elasticdl_tpu.common.timing_utils import Timing

        self.timing = Timing()

    def _tier_mesh(self):
        """Mesh the device tier shards its tables over (``ep`` axis);
        resolves to the SPMD subclasses' mesh, None on single device.
        Called before super().__init__ finishes, so it must only read
        attributes the subclass set first."""
        return getattr(self, "mesh", None)

    def _tier_combine(self, batch, prepared, pull_info):
        """Materialize the step's combined row buffers on device
        (staged promotions land, eviction victims read out, hits
        gathered from HBM). If a PS relaunch invalidated the tier
        between this batch's prepare and now (epoch moved), the batch
        is re-prepared — its slot context points into a map that no
        longer exists, and the rows must re-pull from the restored
        PS."""
        tier = self.device_tier
        ctx = getattr(pull_info, "tier_ctx", None)
        if tier is None or not ctx:
            return prepared, pull_info
        if pull_info.tier_epoch != tier.epoch:
            prepared, pull_info = self.preparer.prepare(batch)
            ctx = getattr(pull_info, "tier_ctx", None) or {}
        features = dict(prepared["features"])
        for name, step_ctx in ctx.items():
            features[name + ROWS_SUFFIX] = tier.combine(
                name, step_ctx["slots"], features[name + ROWS_SUFFIX]
            )
        out = dict(prepared)
        out["features"] = features
        return out, pull_info

    def _tier_apply_extract(self, row_grads, pull_info):
        """Dispatch the fused in-device scatter-apply for every
        table's hit gradients, then extract the (host) miss gradients
        aligned with pull_info's push ids. The applies go first so the
        device works while the host fetch blocks."""
        tier = self.device_tier
        ctx = getattr(pull_info, "tier_ctx", None)
        if tier is None or not ctx:
            return row_grads
        for name, grads in row_grads.items():
            step_ctx = ctx.get(name)
            if step_ctx is not None:
                tier.apply(name, step_ctx["slots"], grads)
        # after every table's apply has been dispatched: the periodic
        # writeback's device fetch then reads post-apply values
        tier.maybe_periodic_writeback()
        out = {}
        for name, grads in row_grads.items():
            step_ctx = ctx.get(name)
            if step_ctx is None:
                out[name] = grads
            else:
                with device_obs.transfer_span(
                    "d2h", getattr(grads, "nbytes", 0)
                ):
                    host = np.asarray(grads)
                out[name] = host[step_ctx["push_pos"]]
        return out

    def flush_device_tier(self):
        """Write every tier-held row update back to the PS (worker
        checkpoint/export boundaries); no-op without a tier."""
        if self.device_tier is not None:
            self.device_tier.flush()

    def _jit_steps(self, train_step_fn, row_grads_fn, eval_step_fn):
        """Compile the three step callables; single-device default.
        instrumented_jit (ISSUE 18) counts compiles vs cache hits per
        step fn and is plain jax.jit when EDL_DEVICE_OBS=0."""
        self._train_step = device_obs.instrumented_jit(
            train_step_fn, name="sparse_train_step", donate_argnums=(0,)
        )
        self._row_grads = device_obs.instrumented_jit(
            row_grads_fn, name="sparse_row_grads"
        )
        self._eval_step = device_obs.instrumented_jit(
            eval_step_fn, name="sparse_eval_step"
        )

    @property
    def cost_step_flops(self):
        """Executable-reported FLOPs of one sparse train batch: the
        fused train step plus the row-grads pass (both run per batch).
        0.0 until first compile / where cost analysis is unavailable."""
        return sum(
            float(getattr(fn, "cost_flops", 0.0))
            for fn in (self._train_step, self._row_grads)
        )

    @property
    def cost_step_bytes(self):
        return sum(
            float(getattr(fn, "cost_bytes", 0.0))
            for fn in (self._train_step, self._row_grads)
        )

    def _fetch_row_grads(self, row_grads):
        """Bring the step's row gradients to per-table host-pushable
        arrays. Single-device (and replicated-SPMD) outputs are plain
        fully-addressable arrays — pass through; the multi-host trainer
        overrides this to extract its process's dp shard."""
        return row_grads

    def create_state(self, sample_features):
        init_rng, self._rng = jax.random.split(self._rng)
        return create_train_state(
            self._model, self._tx, init_rng, sample_features
        )

    def _prepare_once(self, batch):
        if self._prep_memo is not None and self._prep_memo[0] is batch:
            return self._prep_memo[1], self._prep_memo[2]
        with self.timing.timeit("sparse_pull"):
            prepared, pull_info = self.preparer.prepare(batch)
        self._prep_memo = (batch, prepared, pull_info)
        return prepared, pull_info

    def ensure_state(self, state, batch):
        if state is None:
            prepared, _ = self._prepare_once(batch)
            return self.create_state(prepared["features"])
        return state

    def prepare_batch(self, batch):
        return self._prepare_once(batch)

    def join_pushes(self):
        """Depth-1 bounded-staleness barrier for the async push path:
        blocks until the in-flight step push (if any) resolves and
        adopts its version. Failures surface HERE, one step after
        dispatch — an RpcError that exhausted the client's retry
        budget propagates, and a sync-PS rejection raises (the
        async path cannot replay the rejected minibatch; see
        PushResult.rejected_shards). Called automatically before the
        next push and before eval; checkpoint/round boundaries
        (worker, executor) call it explicitly. No-op when async push
        is off or nothing is in flight."""
        future, self._push_future = self._push_future, None
        if future is None:
            return
        accepted, version, rejected = _normalize_push_result(
            future.result(), self._version
        )
        if not accepted:
            self.push_rejections += 1
            raise RuntimeError(
                "async-push gradients rejected as stale by a sync-mode "
                "PS (shards %s); %s requires the async PS — use the "
                "synchronous step against --use_async=false"
                % (sorted(rejected) if rejected else "all",
                   ASYNC_PUSH_ENV)
            )
        self._version = version

    def close(self):
        """Release the async-push executor at end of life. Joins the
        in-flight push first (best-effort: teardown must not mask the
        caller's own exception — stream/checkpoint boundaries already
        surfaced push failures loudly via join_pushes). After close the
        trainer degrades to synchronous pushes, so a late train_step
        still works."""
        try:
            self.join_pushes()
        except Exception:
            logger.exception("in-flight async push failed at close")
        pool, self._async_pool = self._async_pool, None
        self._async_push = False
        if pool is not None:
            pool.shutdown(wait=True)
        if self.device_tier is not None:
            # final writeback: tier-held updates reach the PS before
            # the process exits (export/a successor would otherwise
            # read stale spillover rows)
            self.device_tier.close()

    # overload-class failures a brownout may absorb — shared with the
    # pull-side degraded fills (overload.is_overload_failure)
    _BROWNOUT_CODES = overload.BROWNOUT_CODES

    def _push_with_brownout(self, row_grads, pull_info, **kwargs):
        """Gradient push with brownout degradation (ISSUE 19).

        Disabled (EDL_BROWNOUT_SKIP_AFTER=0, the default): a straight
        ``preparer.push_gradients`` — pre-ISSUE-19 semantics exactly.

        Enabled: an overload-class push failure is ABSORBED — the
        batch's push is dropped (counted + journaled), reusing the
        health sentinels' bit-exact skip contract (the PS simply never
        sees this batch; no partial state). Once the failure streak
        reaches the threshold the trainer stops paying the full retry
        budget per batch: each further push runs under a deadline
        budget of one breaker reset window, so a still-down PS costs
        seconds per batch, and the capped attempt doubles as the
        recovery probe — its first success resets the streak and
        restores normal pacing within the breaker's half-open window."""
        skip_after = overload.brownout_skip_after()
        if skip_after <= 0:
            return self.preparer.push_gradients(
                row_grads, pull_info, **kwargs
            )
        degraded = self._brownout_streak >= skip_after
        try:
            if degraded:
                with overload.budget(overload.circuit_reset_secs()):
                    result = self.preparer.push_gradients(
                        row_grads, pull_info, **kwargs
                    )
            else:
                result = self.preparer.push_gradients(
                    row_grads, pull_info, **kwargs
                )
        except grpc.RpcError as e:
            code = e.code() if hasattr(e, "code") else None
            if code not in self._BROWNOUT_CODES:
                raise
            self._brownout_streak += 1
            self.brownout_skipped_pushes += 1
            overload.note_brownout_skip()
            logger.warning(
                "brownout: dropping this batch's push (overload-class "
                "failure %s, streak %d%s)",
                code, self._brownout_streak,
                ", degraded pacing" if degraded else "",
            )
            if events.enabled():
                events.emit(
                    "brownout_skipped_push",
                    streak=self._brownout_streak,
                    degraded=degraded,
                    code=str(code),
                )
            # accepted=True at the trainer's CURRENT version: the push
            # was never sent, so there is nothing to retry and no
            # version to adopt
            return True, self._version, ()
        if self._brownout_streak:
            logger.warning(
                "brownout recovered: push landed after %d dropped "
                "pushes", self._brownout_streak,
            )
            if events.enabled():
                events.emit(
                    "brownout_recovered",
                    skipped=self._brownout_streak,
                )
            self._brownout_streak = 0
        return result

    def _dispatch_train_step(self, state, prepared):
        """Run the jitted step (health-injection hook included);
        returns (state, loss, row_grads, health_scalars|None)."""
        from elasticdl_tpu.testing import faults

        prepared = faults.maybe_poison_batch(prepared)
        outputs = self._train_step(state, prepared)
        if not self._health_on:
            state, loss, row_grads = outputs
            return state, loss, row_grads, None
        return outputs

    def _observe_health(self, loss, scalars):
        """Fetch the step's health scalars (the one small host
        transfer) and fold them into the tracker. Returns True when
        the skip sentinel says this batch contributes nothing (the
        in-graph guard already kept the state; the caller drops the
        push and any device-tier apply). Raises HealthSentinelError
        under halt."""
        if scalars is None:
            return False
        action = self.health.observe(
            float(loss),
            float(scalars["grad_norm"]),
            bool(scalars["nonfinite"]),
        )
        return action == "skip"

    def train_step(self, state, batch):
        """batch: raw (un-prepared) batch with id features."""
        prepared, pull_info = self._prepare_once(batch)
        if state is None:
            state = self.create_state(prepared["features"])
        self._prep_memo = None
        prepared, pull_info = self._tier_combine(
            batch, prepared, pull_info
        )
        t0 = self.timing.start()
        state, loss, row_grads, scalars = self._dispatch_train_step(
            state, prepared
        )
        row_grads = self._fetch_row_grads(row_grads)
        if self._observe_health(loss, scalars):
            # skip sentinel: the state kept its pre-batch value
            # in-graph; dropping the push AND the device-tier apply
            # here means the poisoned batch reaches nothing
            self.timing.end_record_sync("batch_process", t0, loss)
            return state, loss
        row_grads = self._tier_apply_extract(row_grads, pull_info)
        self.timing.end_record_sync("batch_process", t0, loss)
        if self._async_push:
            # join step N-1's push (depth-1 barrier), then hand step
            # N's off to the executor: it overlaps the caller's
            # bookkeeping and step N+1's pull + forward/backward. The
            # rows step N+1 pulls may miss THIS push's contribution —
            # exactly one push of staleness, the async-PS envelope.
            with self.timing.timeit("sparse_push"):
                self.join_pushes()
            # bind_context: the async push runs on the executor thread
            # AFTER this step's root span closed; binding keeps its
            # ps_push / RPC-attempt spans children of the step that
            # produced the gradients, not orphans (ISSUE 9)
            self._push_future = self._async_pool.submit(
                trace.bind_context(self._push_with_brownout),
                row_grads,
                pull_info,
                model_version=self._version,
                force_empty=self.FORCE_EMPTY_PUSH,
                round_scoped=self.ROUND_SCOPED_PUSH,
            )
            return state, loss
        with self.timing.timeit("sparse_push"):
            accepted, version, rejected = self._push_with_brownout(
                row_grads,
                pull_info,
                model_version=self._version,
                force_empty=self.FORCE_EMPTY_PUSH,
                round_scoped=self.ROUND_SCOPED_PUSH,
            )
        if not accepted and self.device_tier is not None:
            # the retry protocol recomputes FULL row grads against
            # fresh pulls — with hit grads already applied in-device
            # that would double-apply; the tier is async-PS only by
            # contract (class attr docstring)
            raise RuntimeError(
                "sync-mode PS rejected a push with the device "
                "embedding tier enabled; EDL_DEVICE_TIER requires the "
                "async PS (--use_async=true)"
            )
        retries = 0
        while not accepted and retries < self.MAX_PUSH_RETRIES:
            # sync PS rejected the push as stale — retry ONLY to the
            # shards that rejected (the others already buffered this
            # minibatch's contribution)
            if rejected is None and self.preparer.ps_num > 1:
                # a multi-shard client MUST report which shards rejected,
                # or a blanket retry would double-apply on the others
                raise RuntimeError(
                    "multi-shard PS client rejected a push without "
                    "reporting rejected_shards; cannot retry safely"
                )
            self._version = version
            if self.RETRY_RECOMPUTES:
                # pull fresh rows and recompute row grads at current
                # params (reference worker.py:597-649 re-ran the whole
                # minibatch; dense params here already updated locally)
                with self.timing.timeit("sparse_pull"):
                    prepared, pull_info = self.preparer.prepare(batch)
                row_grads = self._fetch_row_grads(
                    self._row_grads(state, prepared)
                )
            # else: resend the SAME grads with the corrected version —
            # see RETRY_RECOMPUTES
            with self.timing.timeit("sparse_push"):
                accepted, version, rejected = (
                    self.preparer.push_gradients(
                        row_grads,
                        pull_info,
                        model_version=self._version,
                        only_shards=rejected,
                        round_scoped=self.ROUND_SCOPED_PUSH,
                        force_empty=self.FORCE_EMPTY_PUSH,
                    )
                )
            retries += 1
            self.push_rejections += 1
        if not accepted:
            raise RuntimeError(
                "sync PS rejected gradients %d times in a row; check "
                "that the PS grads_to_wait matches the worker count"
                % self.MAX_PUSH_RETRIES
            )
        self._version = version
        return state, loss

    def eval_step(self, state, batch):
        # eval pulls fresh rows: the in-flight async push must land
        # first or the scored rows would be one update behind the
        # training reality the caller just observed (tier hits are
        # fresher still — gathered straight from HBM)
        self.join_pushes()
        prepared, pull_info = self._prepare_once(batch)
        self._prep_memo = None
        prepared, _ = self._tier_combine(batch, prepared, pull_info)
        outputs = self._eval_step(state, prepared["features"])
        nbytes = sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_leaves(outputs)
        )
        with device_obs.transfer_span("d2h", nbytes):
            return jax.tree_util.tree_map(np.asarray, outputs)

    # ------------------------------------------------------------------
    def train_stream(self, state, batches, on_first_batch=None,
                     push_interval=1):
        """Pipelined training over an iterable of raw batches.

        Overlap structure per step N (async-PS mode):

          dispatch device step N          (returns before completion)
          yield (state, loss, batch_N)    (the consumer's bookkeeping —
                                           record reports, callbacks —
                                           rides under the device step)
          submit pull of batch N+1        (background thread: the PS
                                           RPCs overlap BOTH the device
                                           step and the row-grad fetch
                                           below — at high RTT the pull
                                           used to sit in series with
                                           the fetch, ~1 RTT on the
                                           critical path)
          fetch step N's row grads        (fences the device)
          push step N's grads             (background thread; at most
                                           one push in flight)
          collect the pull                (only its non-overlapped
                                           remainder is critical path)

        The yield MUST precede the lookahead: the consumer's record
        report is what lets the master finish the current task and
        create the next epoch's tasks, and the lookahead blocks on the
        master handing out a task. Yielding after the lookahead
        deadlocks every pure-training epoch boundary (master waits for
        the report, worker waits for the task).

        Rows for batch N+1 are one push stale, and pushed grads land up
        to one step late — both inside the async PS's staleness
        envelope (the reference's async workers trained entire
        minibatches on stale params, servicer.py:120-165). A sync-mode
        PS will version-reject these pushes: use ``train_step`` there
        instead.

        ``push_interval=k`` additionally accumulates row gradients over
        k batches and pushes one merged IndexedSlices — the direct
        analogue of reference ``get_model_steps`` (worker.py:287-295,
        744-806: k local steps between PS syncs, one merged update).

        Yields (state, loss, batch) per input batch, in order. ``loss``
        is an unfetched device scalar (the step has only been
        dispatched when the consumer sees it). ``on_first_batch(batch)``
        runs before the first dispatch (the worker's checkpoint-restore
        hook); if it returns a state, that state is used.
        """
        if push_interval < 1:
            raise ValueError("push_interval must be >= 1")
        # a round boundary for the train_step async-push path: anything
        # still in flight from before this stream joins first (the
        # stream runs its own single-push-in-flight overlap below)
        self.join_pushes()
        it = iter(batches)
        sentinel = object()
        batch = next(it, sentinel)
        if batch is sentinel:
            return
        if on_first_batch is not None:
            restored = on_first_batch(batch)
            if restored is not None:
                state = restored
        # _prepare_once: reuse the rows ensure_state/restore already
        # pulled for this same batch object
        prepared, pull_info = self._prepare_once(batch)
        self._prep_memo = None
        if state is None:
            state = self.create_state(prepared["features"])
        push_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sparse-push"
        )
        push_future = None
        # single lookahead-pull thread: prepare() is called strictly
        # sequentially on it (the HotRowCache clock and table merges
        # assume ordered prepares), RPC legs release the GIL
        pull_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sparse-lookahead"
        )
        next_prep_future = None
        acc = {}  # table -> (values, ids) accumulated since last push
        acc_steps = 0
        push_rpc = self.preparer._ps.push_gradients
        in_flight = None  # (row_grads, pull_info) dispatched, not pushed

        def fold_in_flight():
            """Fetch the in-flight step's row grads (fences the device)
            and fold them into the accumulator. With a device tier the
            hit grads apply in HBM first and only the miss grads come
            to host (flight_info's push ids are miss-only). Health
            scalars are observed HERE — at the fetch, not at dispatch —
            so the sentinel check never breaks the stream's overlap;
            a skip-sentinel batch folds nothing (and never reaches the
            device tier)."""
            nonlocal in_flight, acc_steps
            row_grads, flight_info, loss, scalars = in_flight
            in_flight = None
            fetched_grads = self._fetch_row_grads(row_grads)
            if self._observe_health(loss, scalars):
                acc_steps += 1
                return
            grads = self._tier_apply_extract(fetched_grads, flight_info)
            fetched = {
                name: np.asarray(value)
                for name, value in grads.items()
            }
            for name, (unique, n) in flight_info.items():
                if n == 0:
                    continue
                values, ids = fetched[name][:n], unique
                if name in acc:
                    prev_v, prev_i = acc[name]
                    values = np.concatenate([prev_v, values], axis=0)
                    ids = np.concatenate([prev_i, ids], axis=0)
                    values, ids = deduplicate_indexed_slices(values, ids)
                acc[name] = (values, ids)
            acc_steps += 1

        try:
            while True:
                t0 = self.timing.start()
                # tier combine on the dispatch thread, after the
                # previous step's in-device apply (fold) — staged
                # promotions/evictions land here, hits gather from HBM
                prepared, pull_info = self._tier_combine(
                    batch, prepared, pull_info
                )
                state, loss, row_grads, scalars = (
                    self._dispatch_train_step(state, prepared)
                )
                # Start the device->host copy of the row grads NOW:
                # np.asarray in fold_in_flight would otherwise only
                # begin the transfer after the lookahead pull returns,
                # putting fetch and pull in series. The fetch is a long
                # leg of the step, so overlapping it with the pull
                # matters at non-zero PS RTT.
                for leaf in jax.tree_util.tree_leaves(row_grads):
                    leaf.copy_to_host_async()
                in_flight = (row_grads, pull_info, loss, scalars)
                # ---- overlap window: device is busy with step N ----
                # consumer bookkeeping first (its record report unblocks
                # the master's next task — see docstring), then the
                # lookahead pull
                yield state, loss, batch
                next_batch = next(it, sentinel)
                next_prep_future = None  # collected or abandoned below
                if next_batch is not sentinel:
                    next_prep_future = pull_pool.submit(
                        self.preparer.prepare, next_batch
                    )
                fold_in_flight()  # fences device execution for step N
                self.timing.end_record_sync("batch_process", t0, loss)
                if acc_steps >= push_interval and acc:
                    # snapshot on this thread BEFORE handing to the push
                    # thread — the next interval mutates ``acc``
                    snapshot, acc = acc, {}
                    acc_steps = 0
                    if push_future is not None:
                        with self.timing.timeit("sparse_push"):
                            self._finish_push(push_future.result())
                    push_future = push_pool.submit(
                        push_rpc, snapshot, model_version=self._version
                    )
                if next_batch is sentinel:
                    break
                # only the pull latency NOT hidden under the fetch/push
                # above is critical path; time exactly that remainder
                with self.timing.timeit("sparse_pull"):
                    try:
                        prepared, pull_info = next_prep_future.result()
                    finally:
                        # clear even when result() raises: the future
                        # is consumed either way, and teardown must not
                        # re-drain it (double-logging its error)
                        next_prep_future = None
                batch = next_batch
            if push_future is not None:
                with self.timing.timeit("sparse_push"):
                    self._finish_push(push_future.result())
                push_future = None
            if acc:  # tail accumulation shorter than push_interval
                with self.timing.timeit("sparse_push"):
                    self._finish_push(
                        push_rpc(acc, model_version=self._version)
                    )
                acc = {}
        finally:
            if push_future is not None:
                # only reachable while unwinding (clean exits collect
                # it inside the try block) — surface the push's fate
                # without masking the original exception or aborting
                # the teardown below
                try:
                    push_future.result()
                except Exception:
                    logger.exception(
                        "in-flight gradient push failed during stream "
                        "teardown"
                    )
            # closed mid-stream (stop_training, exception unwinding): a
            # dispatched step's grads and any short accumulation would
            # otherwise be silently dropped — flush best-effort
            try:
                if in_flight is not None:
                    fold_in_flight()
                if acc:
                    self._finish_push(
                        push_rpc(acc, model_version=self._version)
                    )
            except Exception:  # edlint: disable=ft-swallowed-except
                pass  # the original exception matters more
            push_pool.shutdown(wait=True)
            if next_prep_future is not None:
                # exception unwound between submit and collect: cancel
                # if not started; if already running, the shutdown below
                # must drain it (a late prepare mutating the HotRowCache
                # under a successor stream would race) — say so, since
                # a downed PS keeps the pull in its retry budget for up
                # to ~2 min and this wait would otherwise look like a
                # silent hang. Surface the pull's own error too.
                if not next_prep_future.cancel():
                    if not next_prep_future.done():
                        logger.warning(
                            "draining an in-flight lookahead pull before "
                            "stream teardown (PS retry budget bounds this)"
                        )
                    try:
                        next_prep_future.result()
                    except Exception:
                        logger.exception("abandoned lookahead pull failed")
            pull_pool.shutdown(wait=True)

    def _finish_push(self, result):
        accepted, version, _ = _normalize_push_result(
            result, self._version
        )
        if not accepted:
            raise RuntimeError(
                "train_stream pushed gradients to a sync-mode PS which "
                "rejected them as stale; pipelined training requires "
                "the async PS (use train_step with --use_async=false)"
            )
        self._version = version
