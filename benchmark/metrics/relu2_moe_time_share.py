"""relu2_moe_time_share: device time of the expert layers of a stack
whose experts are ``relu(x W_up)^2 W_down`` -- every operation under a
``moe/`` scope of ``MoeMlp`` (``router``, ``dispatch``, ``experts``,
``combine``, ``shared``; forward, backward and recompute; the grouped
matmuls' kernels by their names) -- over device busy time, in percent,
summed from the rows of ``step_account.json`` (lib/step_account.py: the
device with the most busy time speaks). Left out for a program without
the scope registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.share(
        step_account.reduced(run),
        lambda row: row["scope"].startswith("moe/"))
