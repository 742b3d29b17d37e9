"""mfu: FLOPs the forward and backward passes require per sample
(the configuration's ``flops/<name>.py``: no recompute, no gather) x
samples_per_s over chips x the published bf16 peak, in percent. Left
out for a configuration that names no FLOPs count."""

from benchmark.lib import window


def read(run):
    per_sample = window.flops_per_sample(run)
    if per_sample is None:
        return None
    peak = window.peaks(run)["bf16_flops_per_s"] * run["chips"]
    return 100.0 * per_sample * window.samples_per_second(run) / peak
