"""attention_time_share: device time of the family ``attention`` of
``observability/scopes.py`` -- softmax attention of every kind (the
scopes ``attn_full/``, ``attn_window/``, ``mla/``, ``dsa/`` and the
flash, rotary and indexer kernels) -- forward, recompute and backward,
over device busy time, the busiest device, in percent. The deepest
registered scope on an operation's ``op_name`` decides its family.
``step_account.json`` has the family's rows by scope and direction
(lib/step_account.py). Left out for a program without the registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.family_share(
        step_account.reduced(run), "attention")
