"""step_temp_gb: gigabytes of temporaries a device that the compiled
train step reserves (``memory.temporaries`` of its ``xla_compile``
event, lib/step_memory.py): what a remat policy or a kernel's
residuals move. The compiler counts a loop's buffers generously, so
this can exceed what the step holds at any one moment
(``step_peak_gb``)."""

from benchmark.lib import step_memory


def read(run):
    return step_memory.step_gb(run, "temporaries")
