"""step_backend_s: seconds of the train step's first call inside jax's
backend stage (``stages.backend_s`` of its first ``xla_compile``
journal event): the XLA compile when the persistent cache missed, the
cache's read, deserialise and load onto the device when it hit."""

from benchmark.lib import setup_ledger


def read(run):
    stages = setup_ledger.step_stages(run)
    return stages["backend_s"] if stages else None
