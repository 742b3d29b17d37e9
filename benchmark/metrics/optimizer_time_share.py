"""optimizer_time_share: device time of the operations whose
``op_name`` lies under the ``optimizer`` scope of ``make_train_step``
over device busy time, worst device, in percent. A fusion is charged
to its root instruction's scope; ``scopes.json`` beside the report has
forward, backward, loss, optimizer and unscoped (lib/loop_ledger.py)."""

from benchmark.lib import loop_ledger


def read(run):
    return loop_ledger.optimizer_time_share(loop_ledger.reduced(run))
