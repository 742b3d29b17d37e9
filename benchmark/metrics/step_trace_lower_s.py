"""step_trace_lower_s: seconds of the train step's first call that jax
spent tracing the Python function and lowering the jaxpr to StableHLO
(``stages.trace_s + lower_s`` of its first ``xla_compile`` journal
event): paid cold and warm alike, the persistent cache holds neither."""

from benchmark.lib import setup_ledger


def read(run):
    stages = setup_ledger.step_stages(run)
    if not stages:
        return None
    return stages["trace_s"] + stages["lower_s"]
