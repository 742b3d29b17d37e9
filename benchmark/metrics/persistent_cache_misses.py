"""persistent_cache_misses: programs of the worker, wrapped or eager,
that the persistent compilation cache was asked for and did not hold,
before the window (its ``xla_cache_miss`` journal events with ``ts``
before the window's start). 0 in a warm run of a sound tree: a warm
start that compiles is the finding, the event's ``module`` its lead.
Left out for a program whose start-up record has no ``compiles``."""

from benchmark.lib import loop_ledger, setup_ledger


def read(run):
    if setup_ledger.startup_compiles(run) is None:
        return None
    return float(sum(
        1 for e in loop_ledger.worker_events(run)
        if e.get("event") == "xla_cache_miss"
        and e.get("ts", 0) < run["window"][0]
    ))
