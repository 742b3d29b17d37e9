"""peak_hbm_gb: the allocator's peak of buffers in use plus the peak it
reserved for program temporaries (lib/window.py), largest over the
worker's local devices (written by the zoo's callback), in GB."""

from benchmark.lib import window


def read(run):
    peaks = window.memory_peaks(run)
    return max(peaks) / 1e9 if peaks else None
