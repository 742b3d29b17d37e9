"""mlp_time_share: device time of the family ``mlp`` of
``observability/scopes.py`` -- a block's second sublayer, dense or
experts (the scopes ``dense_mlp`` and ``moe/``, the grouped matmuls'
kernels) -- forward, recompute and backward, over device busy time, the
busiest device, in percent. The deepest registered scope on an
operation's ``op_name`` decides its family. ``step_account.json`` has
the family's rows by scope and direction (lib/step_account.py). Left out
for a program without the registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.family_share(
        step_account.reduced(run), "mlp")
