"""MultiHostRuntime: mesh-epoch-driven jax.distributed lifecycle
(reference allreduce_trainer.py:94-118 re-init semantics), driven
against the real MeshRendezvous."""

import json
import os
import socket
import subprocess
import sys

import pytest

from elasticdl_tpu.master.rendezvous import MeshRendezvous
from elasticdl_tpu.parallel.multihost import MultiHostRuntime
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb


class FakeDistributed:
    def __init__(self):
        self.calls = []

    def initialize(self, coordinator_address, num_processes, process_id,
                   initialization_timeout=None):
        self.calls.append(
            ("init", coordinator_address, num_processes, process_id)
        )

    def shutdown(self):
        self.calls.append(("shutdown",))


class Client:
    """MasterClient stand-in wired straight to a MeshRendezvous."""

    def __init__(self, rendezvous, host):
        self._r = rendezvous
        self._host = host

    def get_comm_info(self):
        rank, size, epoch, coord = self._r.get_comm_info(self._host)
        return pb.CommInfo(
            rank=rank, world_size=size, mesh_epoch=epoch,
            coordinator_addr=coord,
        )


def test_initialize_once_then_noop():
    rendezvous = MeshRendezvous()
    rendezvous.set_worker_hosts(["hostA:3333", "hostB:3333"])
    fake = FakeDistributed()
    runtime = MultiHostRuntime(
        Client(rendezvous, "hostB:3333"), distributed=fake,
        coordinator_port=5000,
    )
    assert runtime.ensure_runtime() is True
    assert fake.calls == [("init", "hostA:5000", 2, 1)]
    assert runtime.rank == 1 and runtime.world_size == 2
    # same epoch: no-op
    assert runtime.ensure_runtime() is False
    assert len(fake.calls) == 1
    assert not runtime.check_epoch()


def test_membership_change_reinitializes():
    rendezvous = MeshRendezvous()
    rendezvous.set_worker_hosts(["hostA:3333", "hostB:3333"])
    fake = FakeDistributed()
    runtime = MultiHostRuntime(
        Client(rendezvous, "hostA:3333"), distributed=fake,
        coordinator_port=5000,
    )
    runtime.ensure_runtime()
    rendezvous.add_worker_host("hostC:3333")  # epoch bump
    assert runtime.check_epoch()
    assert runtime.ensure_runtime() is True
    assert fake.calls == [
        ("init", "hostA:5000", 2, 0),
        ("shutdown",),
        ("init", "hostA:5000", 3, 0),
    ]


def test_rpc_failure_marker_is_not_an_epoch_change():
    """mesh_epoch=-1 (MasterClient RPC-failure marker) must not trigger
    a restart — a network blip would discard un-checkpointed work."""
    rendezvous = MeshRendezvous()
    rendezvous.set_worker_hosts(["hostA:3333"])
    fake = FakeDistributed()
    runtime = MultiHostRuntime(
        Client(rendezvous, "hostA:3333"), distributed=fake,
        coordinator_port=5000,
    )
    runtime.ensure_runtime()
    assert not runtime.epoch_moved(-1)
    assert not runtime.epoch_moved(None)
    assert runtime.epoch_moved(rendezvous.mesh_epoch + 1)


def test_unadmitted_host_blocks_then_joins():
    rendezvous = MeshRendezvous()
    rendezvous.set_worker_hosts(["hostA:3333"])
    fake = FakeDistributed()
    client = Client(rendezvous, "hostB:3333")
    runtime = MultiHostRuntime(
        client, distributed=fake, coordinator_port=5000
    )
    with pytest.raises(TimeoutError):
        runtime.ensure_runtime(wait_sleep_secs=0.01, max_wait_secs=0.05)
    rendezvous.add_worker_host("hostB:3333")
    assert runtime.ensure_runtime() is True
    assert runtime.rank == 1


def test_coordinator_loss_promotes_next_rank():
    """When the coordinator host dies, the surviving worker re-inits
    with itself as rank 0 / coordinator."""
    rendezvous = MeshRendezvous()
    rendezvous.set_worker_hosts(["hostA:3333", "hostB:3333"])
    fake = FakeDistributed()
    runtime = MultiHostRuntime(
        Client(rendezvous, "hostB:3333"), distributed=fake,
        coordinator_port=5000,
    )
    runtime.ensure_runtime()
    assert runtime.rank == 1
    rendezvous.remove_worker_host("hostA:3333")
    assert runtime.ensure_runtime() is True
    assert runtime.rank == 0
    assert fake.calls[-1] == ("init", "hostB:5000", 1, 0)


def test_failed_init_retries_with_fresh_membership():
    """A join attempt that fails (e.g. the coordinator host died
    between fetching comm info and connecting, or the per-attempt
    initialization_timeout expired) must refresh membership and retry
    inside ensure_runtime — not block for jax's 300 s default or give
    up (the mid-join coordinator-death hang found by the chaos e2e)."""

    class FlakyDistributed(FakeDistributed):
        def __init__(self):
            super().__init__()
            self.fail_next_init = False

        def initialize(self, coordinator_address, num_processes,
                       process_id, initialization_timeout=None):
            if self.fail_next_init:
                self.fail_next_init = False
                self.calls.append(("init-failed",))
                raise RuntimeError("coordinator unreachable")
            super().initialize(
                coordinator_address, num_processes, process_id
            )

    rendezvous = MeshRendezvous()
    rendezvous.set_worker_hosts(["hostA:3333", "hostB:3333"])
    fake = FlakyDistributed()
    runtime = MultiHostRuntime(
        Client(rendezvous, "hostB:3333"), distributed=fake,
        coordinator_port=5000,
    )
    runtime.ensure_runtime()
    rendezvous.add_worker_host("hostC:3333")  # epoch bump
    fake.fail_next_init = True
    # the failed attempt is retried internally against refreshed
    # membership — simulate the coordinator dying mid-join
    rendezvous.remove_worker_host("hostA:3333")
    assert runtime.ensure_runtime() is True
    assert runtime.initialized
    # final successful init targets the POST-change membership
    assert fake.calls[-1] == ("init", "hostB:5000", 2, 0)
    assert ("init-failed",) in fake.calls


def test_create_state_on_a_mesh_spanning_processes():
    """Two real jax.distributed CPU processes, dp over both: state
    creation (whose placement log may only ask this process's devices
    for memory_stats — a device another process owns raises) and one
    lockstep step agree on the loss."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tests.drivers.multihost_state_driver",
             "--coordinator", "127.0.0.1:%d" % port, "--rank", str(rank)],
            env=dict(os.environ, PYTHONPATH=repo),
            cwd=repo,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for rank in (0, 1)
    ]
    try:
        results = []
        for proc in procs:
            out, err = proc.communicate(timeout=180)
            assert proc.returncode == 0, err[-3000:]
            assert "this process's 2 of 4 devices" in err
            assert "features (8, 8, 8)" in err  # the GLOBAL batch
            results.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["devices"] == 4 and r["local"] == 2 for r in results)
    assert results[0]["loss"] == results[1]["loss"]
