"""step_named_share: of the traced step's device busy time, the
percentage in operations that ``observability/scopes.py:family`` names:
100 less the family ``unnamed`` (no registered scope on the
``op_name``'s path, no registered kernel name; an operation without an
``op_name`` takes its operand's). The device with the most busy time
speaks. ``step_account.json`` beside ``trace_reduced.json`` has the rows
and the twelve longest unnamed operations (lib/step_account.py). Left
out for a program without the registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.named_share(step_account.reduced(run))
