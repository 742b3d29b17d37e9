"""imports_s: seconds from the worker process's start to the first
statement of its ``main`` (``worker_startup.phases.imports`` of the
worker's journal): the interpreter, jax, flax, orbax, grpc."""

from benchmark.lib import loop_ledger


def read(run):
    return loop_ledger.startup_seconds(run, "imports")
