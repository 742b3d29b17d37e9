"""flash_roofline: the least time the chip could take for the flash
kernels' work in the traced steps -- the larger of FLOPs over the bf16
peak and bytes over the HBM peak, both from the configuration's count
(``flops/<name>.py:kernels``, entry ``flash``) -- over the kernels'
measured device time, in percent. At head 256 and the cells' lengths
FLOPs bound it (PERF.md says which for each cell). Left out for a
configuration whose count names no ``flash`` kernel."""

from benchmark.lib import window
from benchmark.metrics.flash_time_share import flash_seconds


def read(run):
    trace = run["reduced_trace"]
    needs = getattr(run.get("flops"), "kernels", None)
    if not trace or needs is None:
        return None
    need = needs(run["config"], run["traffic"]).get("flash")
    if not need:
        return None
    need_flops, need_bytes = need
    peaks = window.peaks(run)
    shares = []
    for device in trace["devices"]:
        measured = flash_seconds(device)
        if not measured or not device["steps"]:
            continue
        # this device's samples in the traced steps
        samples = (
            device["steps"] * run["traffic"]["minibatch"] / run["chips"]
        )
        least = samples * max(
            need_flops / peaks["bf16_flops_per_s"],
            need_bytes / peaks["hbm_bytes_per_s"],
        )
        shares.append(least / measured)
    return 100.0 * min(shares) if shares else None
