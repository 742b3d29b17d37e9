"""Readers of what the program says about its own start and end
(ISSUE 33): where ``setup_s`` goes, as far as the program can tell.
Part of the yardstick (``tests/benchmark_harness/test_setup_ledger.py``
checks it on a recorded journal).

Everything is read from the two journals of a run, ``events/worker-*``
and ``events/master-*``, on one clock: an event's ``ts`` and the
``start_ts`` / ``signal_ts`` / ``spans`` inside it are epoch seconds,
the clock of the harness's ``window`` and ``spawn_time``; durations are
nanoseconds of ``perf_counter_ns``.

- ``xla_compile`` carries ``stages``: the call that compiled as jax
  split it (``trace_s``, ``lower_s``, ``backend_s``: an XLA compile
  when the persistent cache missed, its read, deserialise and load
  when it hit; ``first_run_s``: the rest of the call, which is
  dispatch, since the result is not awaited).
- ``xla_cache_miss``: one for every program the cache was asked for
  and did not hold.
- ``worker_startup`` carries ``start_ts`` and ``compiles``: the same
  split summed over ALL the programs of each start-up phase, eager
  ones included.
- ``drain_requested`` carries ``signal_ts``, when SIGTERM arrived;
  ``worker_teardown`` ends the worker (``start_ts`` + ``wall_ns``).
- ``master_startup`` / ``master_teardown``: the master's own two.

A program without these records (the parent of PR 33) leaves none of
this: every reader then returns None and raises nothing.
"""

import os

from benchmark.lib import logs, loop_ledger

STAGES = ("trace_s", "lower_s", "backend_s")


def master_events(run):
    """The master's journal (the harness has read it; a bare run
    directory is read here)."""
    if "journal" not in run:
        run["journal"] = logs.read_journal(
            os.path.join(run["out"], "events"))
    return run["journal"]


def first(events, kind):
    for event in events:
        if event.get("event") == kind:
            return event
    return None


def step_stages(run):
    """``stages`` of the train step's first compile; None for a
    program that journals none."""
    for event in loop_ledger.worker_events(run):
        if (event.get("event") == "xla_compile"
                and event.get("compiles") == 1
                and str(event.get("fn", "")).endswith("train_step")):
            return event.get("stages")
    return None


def startup_compiles(run):
    """``worker_startup.compiles``, {phase: split}; None for a program
    whose start-up record has none."""
    startup = first(loop_ledger.worker_events(run), "worker_startup")
    return None if startup is None else startup.get("compiles")


def record_interval(event):
    """(start, end) on the epoch clock of a record that carries
    ``start_ts`` and ``wall_ns``; None otherwise."""
    if event is None or event.get("start_ts") is None:
        return None
    return event["start_ts"], event["start_ts"] + event["wall_ns"] / 1e9


def worker_exit_interval(run):
    """SIGTERM's arrival to the worker's last exit hook."""
    events = loop_ledger.worker_events(run)
    requested = first(events, "drain_requested")
    teardown = record_interval(first(events, "worker_teardown"))
    if requested is None or teardown is None:
        return None
    return requested["signal_ts"], teardown[1]


def program_intervals(run):
    """The stretches of the run that lie inside one of the program's
    own records, by name; None where the worker's start-up record
    carries no ``start_ts`` (a program from before PR 33)."""
    workers = loop_ledger.worker_events(run)
    startup = record_interval(first(workers, "worker_startup"))
    if startup is None:
        return None
    masters = master_events(run)
    return {
        "master_startup": record_interval(first(masters, "master_startup")),
        "worker_startup": startup,
        # the warm-up steps: the first step's return to the window
        "warm_up": (startup[1], run["window"][0]),
        "worker_exit": worker_exit_interval(run),
        "master_teardown": record_interval(
            first(masters, "master_teardown")),
    }


def outside_window(intervals, window):
    """Seconds of the union of ``intervals`` that lie outside
    ``window``; a second two records share counts once."""
    t0, t1 = window
    pieces = []
    for start, end in intervals:
        # what lies before the window, and what lies after it
        pieces += [(start, min(end, t0)), (max(start, t1), end)]
    total, reached = 0.0, float("-inf")
    for start, end in sorted(p for p in pieces if p[1] > p[0]):
        if end > reached:
            total += end - max(start, reached)
            reached = end
    return total
