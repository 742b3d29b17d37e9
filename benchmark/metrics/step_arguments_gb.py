"""step_arguments_gb: gigabytes of arguments a device that the compiled
train step takes (``memory.arguments`` of its ``xla_compile`` event,
lib/step_memory.py): the state and the batch as they lie on one
device, which is what sharding moves."""

from benchmark.lib import step_memory


def read(run):
    return step_memory.step_gb(run, "arguments")
