"""required_gflops_per_s: FLOPs the configuration's own count
(``flops/<name>.py``) requires per sample x samples_per_s, in GFLOP/s.
Added by the preset as a file, to show a second family's count in use
where no published peak exists (a CPU rehearsal has no ``mfu``)."""

from benchmark.lib import window


def read(run):
    per_sample = window.flops_per_sample(run)
    if per_sample is None:
        return None
    return per_sample * window.samples_per_second(run) / 1e9
