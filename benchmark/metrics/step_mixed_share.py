"""step_mixed_share: device time of the operations that the train
step's ``scope_mix`` lists (fusions whose interior instructions fall in
more than one family: ``observability/device.py:scope_mix``, the
``xla_compile`` event), joined to the trace by instruction name, over
device busy time, the busiest device, in percent: how much of the step
a ``*_time_share`` charges to one family while it holds another's work
too. ``step_account.json`` has the twelve longest (lib/step_account.py).
Left out without the registry or without ``scope_mix`` in the
journal."""

from benchmark.lib import step_account


def read(run):
    return step_account.mixed_share(step_account.reduced(run))
