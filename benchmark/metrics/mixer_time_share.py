"""mixer_time_share: device time of the family ``mixer`` of
``observability/scopes.py`` -- the mixers that stand in attention's
place (the scopes ``gdn/``, ``kda/``, ``mamba/``, ``short_conv/`` and
their kernels) -- forward, recompute and backward, over device busy
time, the busiest device, in percent. The deepest registered scope on an
operation's ``op_name`` decides its family. ``step_account.json`` has
the family's rows by scope and direction (lib/step_account.py). 0.0 in
a cell whose blocks have no such mixer. Left out for a program without
the registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.family_share(
        step_account.reduced(run), "mixer")
