"""step_peak_gb: gigabytes a device that the compiled train step needs
at its peak, by the compiler's own count: ``memory.peak`` of the worker
journal's ``xla_compile`` event for the train step (the executable's
``memory_analysis()``: its ``peak_memory_in_bytes`` where the runtime
gives one, else arguments + outputs - aliased + temporaries;
``memory.peak_from`` says which). One program's count: what else the
process keeps on the device is ``worker_hbm_peak_gb``'s. A program
without the event (before PR 47) reports nothing."""

from benchmark.lib import step_memory


def read(run):
    return step_memory.step_gb(run, "peak")
