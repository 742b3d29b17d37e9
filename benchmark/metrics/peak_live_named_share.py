"""peak_live_named_share: of what the train step holds in HBM at its
fullest point by the program's own walk over the compiled schedule
(``peak_live.walk_peak`` of the step's ``xla_compile`` event), the
percentage in groups that the program's scopes name, i.e. other than
``other`` and ``unnamed``. None where ``walk_over_compiler`` lies
outside 0.85-1.15: an uncalibrated walk reports nothing. Leaves
``step_memory.json`` beside ``loop_gaps.json`` (the event's ``memory``
and ``peak_live`` and the ``device_memory`` readings)."""

from benchmark.lib import step_memory


def read(run):
    event = step_memory.write_step_memory(run)
    return None if event is None else step_memory.named_share(
        event.get("peak_live"))
