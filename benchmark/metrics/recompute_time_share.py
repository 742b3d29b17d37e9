"""recompute_time_share: device time of the forward run AGAIN inside
the backward (operations whose ``op_name`` lies under jax's
``rematted_computation``: ``observability/scopes.py:time_direction``)
over device busy time, the busiest device, in percent; 0.0 where
nothing is rematerialised. ``step_account.json`` has it by scope
(lib/step_account.py). Left out for a program without the registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.share(
        step_account.reduced(run),
        lambda row: row["direction"] == "recompute")
