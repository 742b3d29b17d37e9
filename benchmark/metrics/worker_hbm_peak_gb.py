"""worker_hbm_peak_gb: the fullest local device's peak in GB as the
worker itself journaled it (``device_memory`` at ``teardown``, else the
last one journaled: the allocator's ``peak_bytes_in_use +
peak_bytes_reserved``, lib/step_memory.py). The inside twin of
``peak_hbm_gb``, which the benchmark's own callback reads every
``log_every`` steps: the same counters, read once at the end."""

from benchmark.lib import step_memory


def read(run):
    peak = step_memory.worker_peak_bytes(run)
    return None if peak is None else peak / 1e9
