"""Run the model-zoo contract locally, no master/cluster.

Reference parity: elasticdl/python/elasticdl/local_executor.py:36-208 —
the "try the model on my laptop" path over the same module contract the
distributed job uses.
"""

import numpy as np

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.common.platform import configure_compile_cache
from elasticdl_tpu.data.pipeline import (
    Dataset,
    batch_real_count,
    normalize_outputs,
)
from elasticdl_tpu.data.readers import create_data_reader
from elasticdl_tpu.models.registry import get_model_spec
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.train.metrics import EvaluationMetrics
from elasticdl_tpu.worker.trainer import JaxTrainer

logger = _logger_factory("elasticdl_tpu.train.local_executor")


class LocalExecutor:
    def __init__(
        self,
        model_zoo_module,
        training_data=None,
        validation_data=None,
        minibatch_size=32,
        num_epochs=1,
        data_reader_params=None,
        compute_dtype=None,
        seed=0,
        model_def="",
        model_params="",
        symbol_overrides=None,
    ):
        self.spec = get_model_spec(
            model_zoo_module, model_def=model_def,
            model_params=model_params,
            symbol_overrides=symbol_overrides,
        )
        self._minibatch_size = minibatch_size
        self._num_epochs = num_epochs
        # this process compiles the steps itself (no worker entry ran)
        configure_compile_cache()
        reader_params = data_reader_params or {}
        self._train_reader = (
            create_data_reader(training_data, **reader_params)
            if training_data
            else None
        )
        self._valid_reader = (
            create_data_reader(validation_data, **reader_params)
            if validation_data
            else None
        )
        if self.spec.sparse_embedding_specs:
            # Sparse model locally: in-process embedding store, no gRPC.
            from elasticdl_tpu.ps.local_client import LocalPSClient
            from elasticdl_tpu.train.sparse import SparseTrainer

            self.trainer = SparseTrainer(
                model=self.spec.custom_model(),
                loss_fn=self.spec.loss,
                optimizer=self.spec.optimizer(),
                specs=self.spec.sparse_embedding_specs(
                    batch_size=minibatch_size
                ),
                ps_client=LocalPSClient(seed=seed),
                compute_dtype=compute_dtype,
                seed=seed,
            )
        else:
            self.trainer = JaxTrainer(
                model=self.spec.custom_model(),
                loss_fn=self.spec.loss,
                optimizer=self.spec.optimizer(),
                compute_dtype=compute_dtype,
                seed=seed,
            )
        self.state = None
        # observability: opt-in via EDL_METRICS_PORT, same knob as the
        # distributed roles — the "try it on my laptop" path is also
        # the CI smoke that asserts /metrics serves the core series
        from elasticdl_tpu.common.timing_utils import Timing
        from elasticdl_tpu.observability import (
            events,
            http_server,
            profiler,
            trace,
        )

        self._timing = Timing()
        trace.configure("local")
        events.configure("local")
        # continuous profiler (ISSUE 14): the local executor plays the
        # worker role, so EDL_PROF_HZ profiles it the same way — and
        # /profilez rides the same opt-in metrics port
        profiler.maybe_start("local")
        self.observability = http_server.maybe_start("local")
        if self.observability is not None:
            # a local run is ready as soon as the trainer exists
            self.observability.add_readiness_check(
                "trainer_constructed", lambda: self.trainer is not None
            )

    # ------------------------------------------------------------------
    def _records(self, reader):
        def gen():
            for shard_name, (start, count) in reader.create_shards().items():
                task = pb.Task(
                    task_id=0,
                    shard_name=shard_name,
                    start=start,
                    end=start + count,
                )
                yield from reader.read_records(task)

        return Dataset(gen)

    def _batches(self, reader, mode):
        dataset = self.spec.dataset_fn(
            self._records(reader), mode, reader.metadata
        )
        return dataset.batch(self._minibatch_size).prefetch(2)

    # ------------------------------------------------------------------
    def train(self):
        from elasticdl_tpu.common import timing_utils

        ledger = self._timing
        previous_ledger = timing_utils.bind(ledger)
        try:
            return self._train(ledger)
        finally:
            timing_utils.bind(previous_ledger)

    def _train(self, ledger):
        losses = []
        step = 0
        for epoch in range(self._num_epochs):
            batches = iter(self._batches(self._train_reader, "training"))
            while True:
                # the local run traces like the distributed one
                # (ISSUE 9): each iteration is a ledger step and so a
                # ``train_batch`` root span, and the in-process
                # LocalPSClient's apply/pull spans (tagged role="ps")
                # chain under it through the thread-local context — so
                # merge_trace + critical_path report the same
                # worker/PS attribution a real topology yields
                with ledger.step(step + 1, step=step) as iteration:
                    with ledger.phase("input_wait"):
                        batch = next(batches, None)
                    if batch is None:
                        iteration.cancel()
                        break
                    iteration.has_batch()
                    self.state, loss = self.trainer.train_step(
                        self.state, batch
                    )
                    # a local run reads every step at once (the
                    # trainer's one read: ``device_wait``, ``health``)
                    value, _ = self.trainer.read_step(
                        self.trainer.pending_step(loss)
                    )
                    losses.append(value)
                step += 1
            logger.info(
                "Epoch %d done; last-batch loss %.4f", epoch, losses[-1]
            )
            if self._valid_reader is not None:
                summary = self.evaluate()
                logger.info("Epoch %d eval: %s", epoch, summary)
        return losses

    def evaluate(self):
        books = EvaluationMetrics(self.spec.eval_metrics_fn())
        for batch in self._batches(self._valid_reader, "evaluation"):
            self.state = self.trainer.ensure_state(self.state, batch)
            outputs = self.trainer.eval_step(self.state, batch)
            real = batch_real_count(batch)
            books.update_evaluation_metrics(
                normalize_outputs(outputs, real),
                np.asarray(batch["labels"])[:real],
            )
        return books.get_evaluation_summary()

    def predict(self, data=None):
        reader = (
            create_data_reader(data) if data is not None else self._valid_reader
        )
        results = []
        for batch in self._batches(reader, "prediction"):
            self.state = self.trainer.ensure_state(self.state, batch)
            outputs = self.trainer.eval_step(self.state, batch)
            real = batch_real_count(batch)
            results.append(normalize_outputs(outputs, real)["output"])
        return results
