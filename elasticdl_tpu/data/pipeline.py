"""Lightweight input pipeline feeding numpy batches to jitted steps.

Fills the role tf.data plays in the reference's ``dataset_fn`` contract
(worker/worker.py:763-768) without a TF dependency. TPU-first choices:

- Batches are numpy arrays (pytrees of them), ready for a single
  host->device transfer into a jit-compiled step.
- Shapes are static: partial batches are padded to ``batch_size`` and
  carry a float mask under the reserved key "_mask", so XLA never sees a
  new shape (a recompile per tail-batch would dwarf the padded FLOPs).
- Prefetching overlaps host-side parsing with device compute via a
  background thread.
"""

import queue
import random
import threading

import numpy as np

MASK_KEY = "_mask"


class _Flush:
    """Stream-control sentinel: "no more records are coming for now —
    emit what you are holding". The elastic training stream WAIT-loops
    on the master instead of ending (task_data_service
    .training_record_stream), so a tail of records smaller than one
    minibatch would otherwise sit in ``batch()``'s buffer forever
    while the master waits for their task to be reported — a mutual
    wait that hangs the job whenever dataset_size % minibatch != 0
    (found by the co-location harness, round 5). The built-in
    combinators pass FLUSH through untouched (map/filter/take), drain
    their buffers on it (shuffle), or consume it by emitting the
    pending partial padded batch (batch)."""

    def __repr__(self):
        return "<FLUSH>"


FLUSH = _Flush()


class _Prefetched:
    """Iterator over what a producer thread has made of a source: the
    thread starts with the first ``next``. ``ready()`` says whether
    ``next`` would return without waiting (an item, the end or the
    producer's error): the worker's loop asks before it waits for a
    batch with a step still unread (``worker/worker.py``)."""

    _END = object()

    def __init__(self, source_fn, depth):
        self._source_fn = source_fn
        self._queue = queue.Queue(maxsize=depth)
        self._error = None
        self._thread = None
        self._ended = False

    def _produce(self):
        try:
            for item in self._source_fn():
                self._queue.put(item)
        # propagated: ``__next__`` re-raises it on the consumer
        except BaseException as e:  # edlint: disable=ft-swallowed-except
            self._error = e
        finally:
            self._queue.put(self._END)

    def __iter__(self):
        return self

    def __next__(self):
        if self._ended:
            raise StopIteration
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._produce, daemon=True)
            self._thread.start()
        item = self._queue.get()
        if item is self._END:
            self._ended = True
            if self._error is not None:
                raise self._error
            raise StopIteration
        return item

    def ready(self):
        return self._ended or not self._queue.empty()


class Dataset:
    """A re-iterable stream of examples with functional combinators."""

    def __init__(self, source_fn):
        # source_fn: () -> iterator of examples
        self._source_fn = source_fn

    def __iter__(self):
        return iter(self._source_fn())

    @staticmethod
    def from_iterable(iterable_fn):
        return Dataset(iterable_fn)

    @staticmethod
    def from_list(items):
        return Dataset(lambda: iter(items))

    def map(self, fn):
        def gen():
            for item in self._source_fn():
                yield item if item is FLUSH else fn(item)

        return Dataset(gen)

    def filter(self, predicate):
        def gen():
            for item in self._source_fn():
                if item is FLUSH or predicate(item):
                    yield item

        return Dataset(gen)

    def shuffle(self, buffer_size, seed=None):
        def gen():
            rng = random.Random(seed)
            buf = []
            for item in self._source_fn():
                if item is FLUSH:
                    rng.shuffle(buf)
                    yield from buf
                    buf = []
                    yield item
                    continue
                buf.append(item)
                if len(buf) >= buffer_size:
                    idx = rng.randrange(len(buf))
                    buf[idx], buf[-1] = buf[-1], buf[idx]
                    yield buf.pop()
            rng.shuffle(buf)
            yield from buf

        return Dataset(gen)

    def batch(self, batch_size, drop_remainder=False, pad_remainder=True):
        """Collate examples into stacked-numpy batches.

        The tail batch is padded (repeating the last example) with a
        ``_mask`` array marking real rows, unless dropped. A FLUSH
        sentinel forces the pending partial batch out the same way
        (and is consumed here — batches flow downstream, not
        sentinels).
        """

        def emit_partial(buf):
            real = len(buf)
            if pad_remainder:
                buf = buf + [buf[-1]] * (batch_size - real)
            return _collate(buf, len(buf), real=real)

        def gen():
            buf = []
            for item in self._source_fn():
                if item is FLUSH:
                    if buf and not drop_remainder:
                        yield emit_partial(buf)
                    # drop_remainder: the pending partial is CLEARED,
                    # not retained — these records would be dropped at
                    # end-of-stream anyway, and holding them past a
                    # FLUSH recreates the worker/master mutual-wait the
                    # sentinel exists to break (their task is never
                    # reported consumed while the master WAIT-loops;
                    # ADVICE round 5 #3)
                    buf = []
                    continue
                buf.append(item)
                if len(buf) == batch_size:
                    yield _collate(buf, batch_size, real=batch_size)
                    buf = []
            if buf and not drop_remainder:
                yield emit_partial(buf)

        return Dataset(gen)

    def prefetch(self, depth=2):
        """Items made ahead on a background thread, ``depth`` of them;
        the iterator's ``ready()`` says whether the next one is
        there."""
        return Dataset(lambda: _Prefetched(self._source_fn, depth))

    def take(self, n):
        def gen():
            taken = 0
            for item in self._source_fn():
                if item is FLUSH:
                    yield item
                    continue
                if taken >= n:
                    return
                taken += 1
                yield item

        return Dataset(gen)


def _collate(examples, padded_size, real):
    """Stack a list of example pytrees into one batch pytree + mask."""
    mask = np.zeros((padded_size,), dtype=np.float32)
    mask[:real] = 1.0
    first = examples[0]
    if isinstance(first, dict):
        batch = {
            key: np.stack([np.asarray(e[key]) for e in examples])
            for key in first
        }
        batch[MASK_KEY] = mask
        return batch
    if isinstance(first, (tuple, list)):
        features = _stack_field([e[0] for e in examples])
        labels = _stack_field([e[1] for e in examples])
        return {"features": features, "labels": labels, MASK_KEY: mask}
    return {"features": np.stack([np.asarray(e) for e in examples]), MASK_KEY: mask}


def _stack_field(values):
    if isinstance(values[0], dict):
        return {
            key: np.stack([np.asarray(v[key]) for v in values])
            for key in values[0]
        }
    return np.stack([np.asarray(v) for v in values])


def batch_real_count(batch):
    mask = batch.get(MASK_KEY)
    if mask is None:
        raise KeyError("batch has no %r entry" % MASK_KEY)
    return int(mask.sum())


def normalize_outputs(outputs, real):
    """Slice model outputs to the real (unpadded) rows of a batch,
    wrapping a bare array as {"output": ...} for multi-output parity."""
    if isinstance(outputs, dict):
        return {k: np.asarray(v)[:real] for k, v in outputs.items()}
    return {"output": np.asarray(outputs)[:real]}


def pad_batch(batch, size):
    """Zero-pad every leaf's leading dim to ``size``; padded rows carry
    mask 0 so the loss/metrics machinery weighs them out. Used by the
    multi-host lockstep loop, where every process must feed
    identically-shaped shards every step."""
    import jax.tree_util

    n = int(np.asarray(batch[MASK_KEY]).shape[0])
    if n == size:
        return batch
    if n > size:
        raise ValueError("batch of %d rows exceeds pad size %d" % (n, size))

    def pad(leaf):
        leaf = np.asarray(leaf)
        fill = np.zeros((size - n,) + leaf.shape[1:], leaf.dtype)
        return np.concatenate([leaf, fill], axis=0)

    return jax.tree_util.tree_map(pad, batch)


def zero_batch_like(batch):
    """An all-padding batch (mask 0 everywhere): a lockstep process
    whose task stream ran dry feeds these until the global consensus
    says every process is done."""
    import jax.tree_util

    return jax.tree_util.tree_map(
        lambda leaf: np.zeros_like(np.asarray(leaf)), batch
    )
