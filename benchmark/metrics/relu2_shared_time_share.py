"""relu2_shared_time_share: device time under ``moe/shared`` -- the
shared expert ``relu(x U_up)^2 U_down`` that every token passes, two
matrices of d x 3712, forward, backward and recompute -- over device
busy time, in percent, from the rows of ``step_account.json``
(lib/step_account.py). Left out for a program without the scope
registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.share(
        step_account.reduced(run), lambda row: row["scope"] == "moe/shared")
