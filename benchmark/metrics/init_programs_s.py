"""init_programs_s: seconds jax spent tracing, lowering and compiling
or loading the worker's programs OTHER than the train step before the
first step (``trace_s + lower_s + backend_s`` of
``worker_startup.compiles`` over every phase but ``first_step``): the
eager ops of ``state_init``, the restore, the zoo's own."""

from benchmark.lib import setup_ledger


def read(run):
    compiles = setup_ledger.startup_compiles(run)
    if compiles is None:
        return None
    return sum(
        split[stage] for phase, split in compiles.items()
        if phase != "first_step" for stage in setup_ledger.STAGES
    )
