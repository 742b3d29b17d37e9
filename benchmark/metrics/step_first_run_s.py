"""step_first_run_s: what is left of the train step's first call once
tracing, lowering and the backend stage are taken off
(``stages.first_run_s`` of its first ``xla_compile`` journal event):
the rest of the call, which is dispatch and whatever the runtime does
before it returns. The call does not await its result, so this is not
the first execution's time."""

from benchmark.lib import setup_ledger


def read(run):
    stages = setup_ledger.step_stages(run)
    return stages["first_run_s"] if stages else None
