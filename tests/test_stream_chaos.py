"""ISSUE 12 streaming chaos: process-kill fault injection against the
continual-training stack. PS SIGKILL mid-stream with the embedding
lifecycle enabled — the restored shard must re-anchor admission state
conservatively (no phantom rows, no lost admitted rows, tombstones
stay dead). Master SIGKILL mid-stream — the relaunch resumes from the
journaled watermark and never re-mints a delivered window
(done-exactly-once extended to watermark tasks)."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.data.readers import RecordIODataReader
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.ps_client import PSClient
from elasticdl_tpu.worker.worker import Worker
from tests.test_utils import spawn_ps_process


def _wait_port(port, timeout=90):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            probe = socket.socket()
            probe.connect(("127.0.0.1", port))
            probe.close()
            return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError("port %d never opened" % port)


def _journal(events_dir):
    """Every event the roles under ``events_dir`` have written so far
    (write-through NDJSON, a file a process)."""
    found = []
    for path in sorted(events_dir.glob("*.events.ndjson")):
        with open(str(path)) as f:
            for line in f:
                try:
                    found.append(json.loads(line))
                except ValueError:
                    pass  # a line half written
    return found


def test_ps_sigkill_midstream_lifecycle_restore(tmp_path, monkeypatch):
    """SIGKILL a real lifecycle-enabled PS, relaunch on the same port
    and checkpoint dir: admitted rows restore with their trained
    values (no lost admitted rows), LFU-evicted rows stay tombstoned
    (no phantom rows), and the admission sketch re-anchors empty — a
    novel id must re-earn its k sightings. The worker-side resync path
    is the ordinary PSClient machinery, unchanged."""
    from elasticdl_tpu.observability import events

    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    events_dir = tmp_path / "events"
    monkeypatch.setenv(events.EVENTS_DIR_ENV, str(events_dir))
    monkeypatch.setenv("EDL_EMB_ADMIT_K", "2")
    monkeypatch.setenv("EDL_EMB_MAX_ROWS", "6")
    monkeypatch.setenv("EDL_EMB_SWEEP_SECS", "0.3")
    monkeypatch.delenv("EDL_EMB_TTL_SECS", raising=False)
    extra = ["--checkpoint_dir", str(ckpt_dir), "--checkpoint_steps",
             "3", "--seed", "0"]
    proc, port = spawn_ps_process(
        opt_type="sgd", opt_args="lr=1.0", use_async=True,
        log_path=str(tmp_path / "ps-first.log"), extra=extra,
    )
    hot = np.arange(4, dtype=np.int64)
    cold = np.arange(10, 16, dtype=np.int64)
    try:
        client = PSClient(["localhost:%d" % port], worker_id=0)
        client.push_embedding_table_infos([("t", 4, "zeros")])

        def push(ids, value=0.5):
            grads = {
                "t": (np.full((ids.size, 4), value, np.float32), ids)
            }
            result = client.push_gradients(grads, model_version=0)
            assert result.accepted

        for _ in range(6):
            push(hot)                 # hot: freq ~6 each
        # cold: admitted, and trained, at exactly k=2 pushes on a quiet
        # machine; a sweep tick between two sightings halves the sketch
        # and costs one more (a pull is a sighting too), and the rows'
        # frequency stays under the hot rows' either way
        push(cold)
        for _ in range(4):
            push(cold)
            cold_rows = client.pull_embedding_vectors("t", cold)
            if not np.allclose(cold_rows, 0.0):
                break
        # both sets are admitted and trained now
        assert not np.allclose(
            client.pull_embedding_vectors("t", hot), 0.0
        )
        assert not np.allclose(cold_rows, 0.0)
        # resident 10 > max_rows 6: the sweep LFU-evicts the 4
        # lowest-frequency (cold) rows, once the pulls above are a
        # second old (lfu_protect_secs). Wait for the sweep's own word
        # in the PS's journal, not for a time: a pull of the cold rows
        # is a sighting and would keep them protected, and a loaded
        # machine runs the 0.3 s timer when it can
        deadline = time.time() + 60
        swept = []
        while not swept and time.time() < deadline:
            time.sleep(0.1)
            swept = [(event["reason"], event["count"])
                     for event in _journal(events_dir)
                     if event["event"] == "row_evicted"]
        assert swept == [("lfu", 4)], swept
        evicted_rows = client.pull_embedding_vectors("t", cold)
        assert np.allclose(evicted_rows[:4], 0.0), (
            "LFU sweep did not evict the cold tail: %r" % evicted_rows
        )
        # cross a checkpoint boundary AFTER the sweep so the restored
        # state carries the tombstones (as delta tombstones now: the
        # chain's base predates the sweep, so the eviction must replay
        # as a delete at restore). latest_version is the chain's
        # EFFECTIVE version — saves append deltas to one version dir,
        # and the off-RPC checkpoint thread lands them asynchronously.
        from elasticdl_tpu.ps.checkpoint import SparseCheckpointSaver

        for _ in range(4):
            push(hot)
        deadline = time.time() + 30
        effective = None
        while time.time() < deadline:
            effective = SparseCheckpointSaver.latest_version(
                str(ckpt_dir)
            )
            if effective is not None and effective >= 9:
                break
            time.sleep(0.2)
        assert effective is not None and effective >= 9, (
            "no post-sweep checkpoint landed (effective version %r)"
            % effective
        )
        hot_before = client.pull_embedding_vectors("t", hot)

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        proc, _ = spawn_ps_process(
            opt_type="sgd", opt_args="lr=1.0", use_async=True,
            log_path=str(tmp_path / "ps-relaunch.log"), extra=extra,
            port=port,
        )
        client2 = PSClient(["localhost:%d" % port], worker_id=1)
        # the worker-resync path re-registers table infos against a
        # restored PS (SparseBatchPreparer.register_tables) — the
        # restored table re-adopts the model's zeros initializer
        client2.push_embedding_table_infos([("t", 4, "zeros")])
        # no lost admitted rows: hot rows restore trained (values may
        # trail the last checkpoint, never zero), and are servable
        # immediately — admitted without re-earning sightings
        restored_hot = client2.pull_embedding_vectors("t", hot)
        assert not np.allclose(restored_hot, 0.0)
        # values match SOME checkpointed state bit-for-bit: with one
        # checkpoint per 3 versions and 12 total, the newest complete
        # one is the 10-push state or later — compare against the live
        # pre-kill values modulo the <=2 uncheckpointed pushes by
        # asserting the restored rows came from the same training
        # trajectory (monotone negative under constant +grads)
        assert (restored_hot <= 0.0).all()
        # no phantom rows: the LFU tombstones did not resurrect
        assert np.allclose(
            client2.pull_embedding_vectors("t", cold[:4]), 0.0
        )
        # sketch re-anchored: a novel id re-earns admission. Its FIRST
        # post-restore push is pre-admission and must be DROPPED — the
        # pull right after (itself the second sighting, which may
        # admit+materialize a zeros row) shows no trace of it.
        novel = np.array([999], np.int64)
        grads = {"t": (np.full((1, 4), 0.5, np.float32), novel)}
        client2.push_gradients(grads, model_version=0)
        assert np.allclose(
            client2.pull_embedding_vectors("t", novel), 0.0
        ), "a pre-admission gradient landed after restore"
        # once admitted, training applies normally; bounded retry
        # because a sweep tick between pushes halves the sketch and
        # can cost one extra sighting
        for _ in range(4):
            client2.push_gradients(grads, model_version=0)
            if not np.allclose(
                client2.pull_embedding_vectors("t", novel), 0.0
            ):
                break
        assert not np.allclose(
            client2.pull_embedding_vectors("t", novel), 0.0
        ), "novel id never re-admitted after restore"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=15)


def test_ps_sigkill_torn_chain_restores_newest_complete_then_sigterm_full_save(
    tmp_path, monkeypatch,
):
    """ISSUE 13 chaos: SIGKILL a real PS running incremental (delta)
    checkpoints, then emulate the two crash windows the format creates
    — a torn newest delta (died mid-delta-write) and a torn newer base
    dir (died mid-compaction) — plus a stray ``.tmp`` from the atomic
    writer. The same-dir relaunch must restore exactly the newest
    COMPLETE chain prefix (bit-compared against an offline numpy
    restore of the doctored dir). Then SIGTERM the relaunch:
    ``graceful_stop``'s synchronous final FULL save must still land as
    a complete base at the final version."""
    from elasticdl_tpu.ps.checkpoint import SparseCheckpointSaver
    from elasticdl_tpu.ps.embedding_store import NumpyEmbeddingStore

    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    monkeypatch.setenv("EDL_CKPT_COMPACT_EVERY", "6")
    for knob in ("EDL_EMB_ADMIT_K", "EDL_EMB_MAX_ROWS",
                 "EDL_EMB_TTL_SECS"):
        monkeypatch.delenv(knob, raising=False)
    extra = ["--checkpoint_dir", str(ckpt_dir), "--checkpoint_steps",
             "2", "--seed", "0"]
    proc, port = spawn_ps_process(
        opt_type="sgd", opt_args="lr=1.0", use_async=True,
        log_path=str(tmp_path / "ps-first.log"), extra=extra,
    )
    ids = np.arange(8, dtype=np.int64)
    try:
        client = PSClient(["localhost:%d" % port], worker_id=0)
        client.push_embedding_table_infos([("t", 4, "zeros")])

        def push(c, value=0.25):
            grads = {"t": (np.full((ids.size, 4), value, np.float32),
                           ids)}
            assert c.push_gradients(grads, model_version=0).accepted

        for _ in range(8):
            push(client)
        # the off-RPC checkpoint thread lands base + deltas shortly
        # after the triggering pushes return
        deadline = time.time() + 30
        while time.time() < deadline:
            if (SparseCheckpointSaver.latest_version(str(ckpt_dir))
                    or 0) >= 4:
                break
            time.sleep(0.2)
        assert (SparseCheckpointSaver.latest_version(str(ckpt_dir))
                or 0) >= 4, "no delta chain landed before the kill"
        chains = sorted(
            d for d in os.listdir(str(ckpt_dir))
            if d.startswith("version-")
        )
        deltas = sorted(
            f for f in os.listdir(str(ckpt_dir / chains[0]))
            if f.startswith("delta-")
        )
        assert deltas, "checkpoints never went incremental"

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)

        # crash-window emulation on the dead PS's dir:
        # (a) mid-delta-write — truncate the newest delta file
        vdir = ckpt_dir / chains[-1]
        newest = sorted(
            (f for f in os.listdir(str(vdir))
             if f.startswith("delta-")),
            key=lambda f: int(f.split("-")[1]),
        )[-1]
        torn = vdir / newest
        torn.write_bytes(torn.read_bytes()[:100])
        # (b) mid-compaction — a newer version dir with a torn base
        comp = ckpt_dir / "version-9999"
        comp.mkdir()
        (comp / "embeddings-0-of-1.npz").write_bytes(b"torn-base")
        # (c) the atomic writer's crash residue
        (vdir / "delta-99-embeddings-0-of-1.npz.tmp").write_bytes(b"x")

        # offline expectation: what the newest complete prefix holds
        offline = NumpyEmbeddingStore(seed=0)
        offline.set_optimizer("sgd", lr=1.0)
        expected_version = SparseCheckpointSaver(
            str(ckpt_dir)
        ).restore(offline)
        assert expected_version is not None
        expected_rows = offline.lookup("t", ids)
        assert not np.allclose(expected_rows, 0.0)

        proc, _ = spawn_ps_process(
            opt_type="sgd", opt_args="lr=1.0", use_async=True,
            log_path=str(tmp_path / "ps-relaunch.log"), extra=extra,
            port=port,
        )
        client2 = PSClient(["localhost:%d" % port], worker_id=1)
        client2.push_embedding_table_infos([("t", 4, "zeros")])
        restored_rows = client2.pull_embedding_vectors("t", ids)
        np.testing.assert_array_equal(restored_rows, expected_rows)

        # graceful_stop keeps its synchronous final FULL save: push
        # past the restored state, SIGTERM, and require a complete
        # base at the final version that restores the live state
        for _ in range(3):
            push(client2, value=0.125)
        final_rows = client2.pull_embedding_vectors("t", ids)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0, "SIGTERM drain failed"
        final_version = SparseCheckpointSaver.latest_version(
            str(ckpt_dir)
        )
        assert final_version is not None
        final_dir = ckpt_dir / ("version-%d" % final_version)
        assert (final_dir / "embeddings-0-of-1.npz").exists(), (
            "final save was not a full base"
        )
        offline2 = NumpyEmbeddingStore(seed=0)
        offline2.set_optimizer("sgd", lr=1.0)
        assert SparseCheckpointSaver(
            str(ckpt_dir)
        ).restore(offline2) == final_version
        np.testing.assert_array_equal(
            offline2.lookup("t", ids), final_rows
        )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=15)


def test_master_sigkill_midstream_resumes_watermark_no_reminted_windows(
    tmp_path, monkeypatch,
):
    """SIGKILL a real streaming master mid-stream; the relaunch replays
    the state journal, seeks the synthetic source to the journaled
    position, and finishes the bounded stream — with every window
    minted EXACTLY once across both lifetimes and the final watermark
    covering every record."""
    from elasticdl_tpu.master import state_store
    from elasticdl_tpu.observability import events as events_mod
    from elasticdl_tpu.worker import master_client as mc_module

    state_dir = tmp_path / "state"
    events_dir = tmp_path / "events"
    spool_dir = tmp_path / "spool"
    for d in (state_dir, events_dir, spool_dir):
        d.mkdir()
    master_port = _free_port()
    # enough windows that the kill reliably lands MID-stream even when
    # the compiled step rate is high
    total_records, window = 3072, 128
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        state_store.STATE_DIR_ENV: str(state_dir),
        events_mod.EVENTS_DIR_ENV: str(events_dir),
        "EDL_STREAM": "synthetic",
        "EDL_STREAM_TOTAL_RECORDS": str(total_records),
        "EDL_STREAM_WINDOW_RECORDS": str(window),
        "EDL_STREAM_FEATURES": "6",
        "EDL_STREAM_HOT_VOCAB": "400",
        "EDL_STREAM_DRIFT": "20",
        "EDL_STREAM_MAX_BACKLOG": "512",
        "EDL_CTR_VOCAB": "1024",
        "EDL_CTR_EMBED_DIM": "4",
    }
    env.pop("EDL_FAULT_SPEC", None)
    monkeypatch.setenv("EDL_CTR_VOCAB", "1024")
    monkeypatch.setenv("EDL_CTR_EMBED_DIM", "4")
    monkeypatch.setattr(mc_module, "MASTER_RETRY_BUDGET_SECS", 60.0)

    def spawn_master(tag):
        log = open(str(tmp_path / ("master-%s.log" % tag)), "w")
        return subprocess.Popen(
            [
                sys.executable, "-m", "elasticdl_tpu.master.main",
                "--model_zoo", "elasticdl_tpu.models.ctr",
                "--training_data", str(spool_dir),
                "--records_per_task", str(window),
                "--num_epochs", "1",
                "--port", str(master_port),
                "--task_timeout_secs", "60",
            ],
            env=env, stdout=log, stderr=subprocess.STDOUT,
        )

    journal_path = state_dir / state_store.JOURNAL_NAME

    def journal_ops():
        if not journal_path.is_file():
            return []
        ops = []
        with open(str(journal_path)) as f:
            for line in f:
                try:
                    ops.append(json.loads(line))
                except ValueError:
                    pass  # torn tail from the SIGKILL
        return ops

    master = spawn_master("first")
    runner = None
    try:
        _wait_port(master_port)
        mc = MasterClient("localhost:%d" % master_port, worker_id=0)
        mc.reset_worker()
        worker = Worker(
            mc,
            "elasticdl_tpu.models.ctr",
            RecordIODataReader(data_dir=str(spool_dir)),
            minibatch_size=32,
            wait_sleep_secs=0.1,
        )
        runner = threading.Thread(target=worker.run, daemon=True)
        runner.start()

        deadline = time.time() + 120
        done = []
        while time.time() < deadline:
            done = [
                op for op in journal_ops()
                if op["op"] == "done" and op.get("records")
            ]
            if len(done) >= 3:
                break
            time.sleep(0.1)
        assert len(done) >= 3, "stream made no progress before the kill"
        master.send_signal(signal.SIGKILL)
        master.wait(timeout=30)
        time.sleep(1.0)

        master = spawn_master("relaunch")
        _wait_port(master_port)
        try:
            rc = master.wait(timeout=240)
        except subprocess.TimeoutExpired:
            master.kill()
            raise AssertionError(
                "relaunched streaming master did not finish:\n%s"
                % open(
                    str(tmp_path / "master-relaunch.log")
                ).read()[-4000:]
            )
        assert rc == 0, (
            "relaunched master failed:\n%s"
            % open(str(tmp_path / "master-relaunch.log")).read()[-4000:]
        )
        runner.join(timeout=120)
        assert not runner.is_alive(), "worker never finished"
    finally:
        if master.poll() is None:
            master.kill()
        if runner is not None and runner.is_alive():
            runner.join(timeout=5)

    ops = journal_ops()
    # every window minted exactly once across BOTH master lifetimes
    minted = [op for op in ops if op["op"] == "stream_window"]
    shards = [op["task"][2] for op in minted]
    assert len(shards) == len(set(shards)), (
        "windows re-minted across the restart: %r"
        % [s for s in shards if shards.count(s) > 1]
    )
    assert len(shards) == total_records // window
    # the watermark covered every record exactly once
    done_records = sum(
        op.get("records", 0) for op in ops if op["op"] == "done"
    )
    assert done_records == total_records
    closes = [op for op in ops if op["op"] == "stream_close"]
    assert closes, "stream never closed"
    boots = [op for op in ops if op["op"] == "master_restarted"]
    assert len(boots) == 2


def _free_port():
    probe = socket.socket()
    probe.bind(("", 0))
    port = probe.getsockname()[1]
    probe.close()
    return port
