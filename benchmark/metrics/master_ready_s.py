"""master_ready_s: seconds from the master process's start to its port
listening (``master_startup.wall_ns`` of the master's journal): its
imports, the model zoo, the shards. The worker's launch waits for it,
so it is serial in ``setup_s``."""

from benchmark.lib import setup_ledger


def read(run):
    startup = setup_ledger.first(
        setup_ledger.master_events(run), "master_startup")
    return None if startup is None else startup["wall_ns"] / 1e9
