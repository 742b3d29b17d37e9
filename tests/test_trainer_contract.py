"""The seam between the worker's loop and what it drives (ISSUE 40):
the contract every trainer class states (``worker/trainer.py:Trainer``),
the one place that picks a class (``trainer_class``, ``build_trainer``)
and the one channel a model's facts take to the journal
(``train/step_fns.py:FACTS``)."""

import json
import os
import re

import numpy as np
import pytest

from elasticdl_tpu.data.readers import RecordIODataReader
from elasticdl_tpu.models.registry import get_model_spec
from elasticdl_tpu.observability import events
from elasticdl_tpu.parallel.multihost_trainer import MultiHostSpmdTrainer
from elasticdl_tpu.parallel.spmd_trainer import SpmdTrainer
from elasticdl_tpu.train import step_fns
from elasticdl_tpu.train.sparse import SparseTrainer
from elasticdl_tpu.train.sparse_spmd import (
    MultiHostSparseSpmdTrainer,
    SparseSpmdTrainer,
)
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.trainer import (
    JaxTrainer,
    Trainer,
    build_trainer,
    trainer_class,
)
from elasticdl_tpu.worker.worker import Worker
from tests.test_utils import create_mnist_recordio
from tests.test_worker_distributed import start_master
from tests.test_worker_ledger import worker_journal  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_DIR = os.path.join(REPO, "elasticdl_tpu", "worker")
CLASSES = [
    JaxTrainer, SpmdTrainer, MultiHostSpmdTrainer,
    SparseTrainer, SparseSpmdTrainer, MultiHostSparseSpmdTrainer,
]
# what only a class with the capability has to have
LOCKSTEP_ONLY = {"consensus", "process_count", "checkpoint_state"}
STREAMS_ONLY = {"train_stream"}
TAKEN = {"mesh", "sharding_rules", "batch_spec", "grad_accum_steps"}


def _worker_reads():
    """Every ``self.trainer.<name>`` in ``worker/worker.py``."""
    with open(os.path.join(WORKER_DIR, "worker.py")) as f:
        return set(re.findall(r"self\.trainer\.(\w+)", f.read()))


# ------------------------------------------------------- (a) the contract

@pytest.mark.parametrize("cls", CLASSES, ids=lambda cls: cls.__name__)
def test_a_trainer_class_states_what_the_worker_reads(cls):
    """Every member the loop reads is on the class, with the type the
    contract gives it (a property may stand for a plain value); what
    belongs to a capability is there where the class says it has it."""
    assert issubclass(cls, Trainer)
    reads = _worker_reads()
    assert LOCKSTEP_ONLY | STREAMS_ONLY <= reads
    for name in sorted(reads - LOCKSTEP_ONLY - STREAMS_ONLY):
        stated, has = getattr(Trainer, name), getattr(cls, name)
        if callable(stated):
            assert callable(has), name
        else:
            assert isinstance(has, (type(stated), property)), name
    for flag, members in (("lockstep", LOCKSTEP_ONLY),
                          ("streams", STREAMS_ONLY)):
        assert isinstance(getattr(cls, flag), bool)
        assert all(hasattr(cls, m) for m in members) == getattr(cls, flag)
    assert isinstance(cls.sparse, bool)
    assert isinstance(cls.takes, frozenset) and cls.takes <= TAKEN


def test_the_capabilities_by_class():
    assert [c.__name__ for c in CLASSES if c.lockstep] == [
        "MultiHostSpmdTrainer", "MultiHostSparseSpmdTrainer"]
    assert [c.__name__ for c in CLASSES if c.streams] == [
        "SparseTrainer", "SparseSpmdTrainer", "MultiHostSparseSpmdTrainer"]
    assert [c for c in CLASSES if c.sparse] == CLASSES[3:]
    assert [sorted(c.takes) for c in CLASSES] == [
        ["grad_accum_steps"],
        ["batch_spec", "grad_accum_steps", "mesh", "sharding_rules"],
        ["batch_spec", "grad_accum_steps", "mesh", "sharding_rules"],
        [],
        ["mesh", "sharding_rules"],
        ["mesh", "sharding_rules"],
    ]


@pytest.mark.parametrize("pattern", [
    "getattr(self.trainer", "hasattr(self.trainer",
    "inspect.signature(factory", "sparse_trainer_for", "emit_moe_routing",
])
def test_the_worker_asks_no_trainer_by_reflection(pattern):
    for name in sorted(os.listdir(WORKER_DIR)):
        if name.endswith(".py"):
            with open(os.path.join(WORKER_DIR, name)) as f:
                assert pattern not in f.read(), name
    with open(os.path.join(
            REPO, "elasticdl_tpu", "train", "sparse_spmd.py")) as f:
        text = f.read()
    assert pattern not in text and "inspect" not in text


@pytest.mark.parametrize("key", ["routing", "noise", "mhc", "loss_terms"])
def test_an_output_key_is_a_literal_in_the_table_s_module_alone(key):
    found = []
    for package in ("train", "worker", "parallel"):
        directory = os.path.join(REPO, "elasticdl_tpu", package)
        for name in sorted(os.listdir(directory)):
            if name.endswith(".py"):
                with open(os.path.join(directory, name)) as f:
                    if '"%s"' % key in f.read():
                        found.append("%s/%s" % (package, name))
    assert found == ["train/step_fns.py"]


# ------------------------------------------------------ (d) the one choice

@pytest.mark.parametrize("processes,devices,sparse,expected", [
    (1, 1, False, JaxTrainer),
    (1, 4, False, SpmdTrainer),
    (2, 1, False, MultiHostSpmdTrainer),
    (2, 4, False, MultiHostSpmdTrainer),
    (1, 1, True, SparseTrainer),
    (1, 4, True, SparseSpmdTrainer),
    (2, 1, True, MultiHostSparseSpmdTrainer),
    (2, 4, True, MultiHostSparseSpmdTrainer),
])
def test_the_class_by_processes_devices_and_model(
        monkeypatch, processes, devices, sparse, expected):
    """What the three choosers this replaced returned together
    (``worker/main.py`` by the counts, ``Worker.__init__`` and
    ``sparse_trainer_for`` by the model): ``worker/main.py`` hands the
    dense class of ``jax.process_count()`` and ``jax.device_count()``
    to the ``Worker``, whose ``build_trainer`` composes it with the
    model's tables."""
    import jax

    monkeypatch.setattr(jax, "process_count", lambda: processes)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    dense = trainer_class(jax.process_count(), jax.device_count())
    assert not dense.sparse
    assert trainer_class(sparse=sparse, factory=dense) is expected
    assert trainer_class(processes, devices, sparse) is expected


@pytest.mark.parametrize("sparse", [False, True])
def test_a_worker_built_by_hand_trains_on_one_device(sparse):
    assert trainer_class(sparse=sparse) is (
        SparseTrainer if sparse else JaxTrainer)


@pytest.mark.parametrize("factory,accum,warned", [
    (None, 2, False), (SpmdTrainer, 2, False), (SpmdTrainer, 1, False),
    ("refuses", 2, True),
], ids=["jax", "spmd", "spmd-no-accum", "a-class-that-takes-none"])
def test_build_trainer_hands_a_class_what_it_takes(
        caplog, factory, accum, warned):
    seen = {}

    class Refuses(JaxTrainer):
        takes = frozenset()

        def __init__(self, **kwargs):
            seen.update(kwargs)
            super().__init__(**kwargs)

    class Sees(SpmdTrainer):
        def __init__(self, **kwargs):
            seen.update(kwargs)
            super().__init__(**kwargs)

    factory = {None: None, SpmdTrainer: Sees, "refuses": Refuses}[factory]
    spec = get_model_spec("elasticdl_tpu.models.transformer")
    with caplog.at_level("WARNING"):
        trainer = build_trainer(
            spec, factory, minibatch_size=8, grad_accum_steps=accum)
    assert isinstance(trainer, factory or JaxTrainer)
    assert ("--grad_accum_steps ignored: trainer Refuses" in caplog.text
            ) == warned
    if factory is Sees:
        # the zoo's rules, and the mesh even without a mesh flag
        assert seen["mesh"] is trainer.mesh and trainer.mesh.size == 8
        assert seen["sharding_rules"] is not None
        assert seen.get("grad_accum_steps", 1) == accum
    elif factory is Refuses:
        assert set(seen) == {
            "model", "loss_fn", "optimizer", "compute_dtype", "seed"}


# ------------------------------------- (b), (c) the facts' one channel

def _run_worker(tmp_path, zoo, log_every=4, **worker_kwargs):
    """A real Worker against a real in-process master over 256 mnist
    records in batches of 32, two epochs: 16 steps."""
    train_dir = tmp_path / "train"
    train_dir.mkdir(parents=True)
    create_mnist_recordio(str(train_dir / "f0.rec"), num_records=256, seed=0)
    server, dispatcher, _evals, port = start_master(
        str(train_dir), str(train_dir), str(tmp_path / "export"),
        eval_steps=0,
    )
    try:
        Worker(
            MasterClient("localhost:%d" % port, worker_id=0), zoo,
            RecordIODataReader(data_dir=str(train_dir)),
            minibatch_size=32, wait_sleep_secs=0.1,
            log_loss_steps=log_every, **worker_kwargs,
        ).run()
        assert dispatcher.finished() and not dispatcher.job_failed()
    finally:
        server.stop(None)


def test_a_new_fact_costs_a_row_and_an_event_s_name(
        tmp_path, monkeypatch, worker_journal):  # noqa: F811
    """A model hands out a fact under a key the table does not have:
    with one row more in ``FACTS`` and its name in ``EVENT_TYPES`` (and
    nothing of ``step_fns.py``, ``trainer.py`` or ``worker.py`` touched)
    it is journaled on the steps that log and on no other, arrays as
    lists; without the row it never leaves the step."""
    from tests.models import mnist_with_fact

    zoo = "tests.models.mnist_with_fact"
    _run_worker(tmp_path / "without", zoo)
    assert worker_journal("probe_fact") == []
    monkeypatch.setattr(step_fns, "FACTS", step_fns.FACTS + (
        step_fns.Fact(mnist_with_fact.FACT_KEY, "probe_fact"),))
    monkeypatch.setattr(
        events, "EVENT_TYPES", events.EVENT_TYPES | {"probe_fact"})
    _run_worker(tmp_path / "with", zoo)
    seen = worker_journal("probe_fact")
    assert [e["step"] for e in seen] == [4, 8, 12, 16]
    for event in seen:
        assert event["rows"] == 32.0
        assert len(event["class_mean"]) == 10
        assert all(isinstance(x, float) for x in event["class_mean"])
    assert len({tuple(e["class_mean"]) for e in seen}) == 4


_THIRD = float(np.float32(1 / 3))
# a step's facts as a trainer holds them, and the events the parent of
# ISSUE 40 made of them (``emit_moe_routing`` and the three ``emit``s
# of ``Worker._after_train_batch``), fields in its order
RECORDED = {
    "routing": {
        "load_max": np.float32(9.0), "load_mean": np.float32(2.5),
        "entropy": np.float32(1 / 3), "dropped": np.float32(0.0)},
    "noise": {
        "masked_share": np.float32(0.5), "mean_t": np.float32(1 / 3),
        "weight_mean": np.float32(1.0)},
    "mhc": {
        "diag_mean": np.asarray([0.25, 1 / 3, 0.5], np.float32),
        "row_err": np.asarray([0.0, 1e-6, 2.0], np.float32)},
    "loss_terms": {"mtp_loss": np.float32(6.25)},
}
HELD = {"bias_abs_max": np.float32(0.125), "held": np.float32(300.0),
        "rows_run": np.float32(1024.0), "rows_buffer": np.float32(4096.0)}
EXPECTED = {
    "moe_routing": {
        "tokens_per_expert_max": 9.0, "tokens_per_expert_mean": 2.5,
        "router_entropy": _THIRD, "dropped_pairs": 0.0},
    "bd_noise": {
        "masked_share": 0.5, "mean_t": _THIRD, "weight_mean": 1.0},
    "mhc": {
        "diag_mean": [0.25, _THIRD, 0.5],
        "row_err": [0.0, float(np.float32(1e-6)), 2.0]},
    "loss_terms": {"loss": None, "mtp_loss": 6.25},
}
EXPECTED_HELD = dict(
    EXPECTED["moe_routing"], bias_abs_max=0.125, held_pairs=300.0,
    held_rows_run=1024.0, held_rows_buffer=4096.0)
ENVELOPE = {"ts", "role", "pid", "event", "seq", "job"}


@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory):
    """One worker whose trainer holds ``RECORDED`` after every step (the
    held share's four on the steps past 8): its journal and its log."""
    import jax.numpy as jnp
    import logging

    class Recorded(JaxTrainer):
        def train_step(self, state, batch):
            state, loss = super().train_step(state, batch)
            facts = {key: {k: jnp.asarray(v) for k, v in value.items()}
                     for key, value in RECORDED.items()}
            if int(state.step) > 8:
                facts["routing"].update(
                    (k, jnp.asarray(v)) for k, v in HELD.items())
            self.facts = facts
            return state, loss

    tmp_path = tmp_path_factory.mktemp("recorded")
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger = logging.getLogger("elasticdl_tpu.worker.worker")
    logger.addHandler(handler)
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("EDL_EVENTS_DIR", str(tmp_path / "events"))
        events.configure("worker-0")
        try:
            _run_worker(tmp_path, "elasticdl_tpu.models.mnist",
                        trainer_factory=Recorded)
        finally:
            events._reset_for_tests()
            logger.removeHandler(handler)
    records = []
    for path in sorted((tmp_path / "events").glob("*.events.ndjson")):
        records += [json.loads(line)
                    for line in path.read_text().splitlines()]
    return records, lines


@pytest.mark.parametrize("event", sorted(EXPECTED))
def test_today_s_four_events_field_for_field(recorded_run, event):
    records, lines = recorded_run
    seen = [r for r in records if r["event"] == event]
    assert [r["step"] for r in seen] == [4, 8, 12, 16]
    logged = {
        int(m.group(1)): (float(m.group(2)), m.group(3))
        for m in (re.match(r"step (\d+) loss (\S+)(.*)$", line)
                  for line in lines) if m}
    assert sorted(logged) == [4, 8, 12, 16]
    for record in seen:
        want = dict(EXPECTED[event])
        if event == "moe_routing" and record["step"] > 8:
            want = dict(EXPECTED_HELD)
        fields = [k for k in record if k not in ENVELOPE]
        assert fields == ["step"] + list(want)
        if "loss" in want:
            # the logged loss, and the terms after it on the line
            value, rest = logged[record["step"]]
            assert record["loss"] == pytest.approx(value, abs=1e-6)
            assert rest == " mtp_loss 6.250000"
            want.pop("loss")
        assert {k: record[k] for k in want} == want


def test_the_facts_are_fetched_with_the_logged_loss_and_on_no_other_step(
        tmp_path):
    """A fact reaches the host inside ``device_wait`` on the steps that
    log; between them nothing of it is read."""
    from elasticdl_tpu.common import timing_utils

    fetches = []

    class Leaf:
        def __init__(self, step):
            self.step = step

        def __array__(self, dtype=None, copy=None):
            fetches.append(
                (self.step, timing_utils.current()._open_phase()))
            return np.asarray(1.0, dtype)

    class Watched(JaxTrainer):
        def train_step(self, state, batch):
            state, loss = super().train_step(state, batch)
            step = int(state.step)
            self.facts = {"noise": {"mean_t": Leaf(step), "w": Leaf(step)}}
            return state, loss

    _run_worker(tmp_path, "elasticdl_tpu.models.mnist",
                trainer_factory=Watched)
    assert fetches == [
        (step, "device_wait") for step in (4, 8, 12, 16) for _ in "tw"]
