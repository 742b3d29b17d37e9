"""kda_scan_roofline: the least time the chip could take for the chunked
vector-decay delta rule's needed work in the traced steps -- the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, both from the
configuration's count (``flops/<name>.py:kernels``, entry ``kda_scan``)
-- over the device time under ``kda/scan`` (lib/kda_trace.py; a Mosaic
kernel named ``kda...`` counts there), in percent: the share of the
roofline of what runs the rule, kernel or not. Left out for a
configuration whose count names no ``kda_scan`` and for a program
without the scopes."""

from benchmark.lib import kda_trace, window


def read(run):
    needs = getattr(run.get("flops"), "kernels", None)
    devices = kda_trace.scoped_devices(kda_trace.reduced(run))
    if needs is None or not devices:
        return None
    need = needs(run["config"], run["traffic"]).get("kda_scan")
    if not need:
        return None
    peaks = window.peaks(run)
    least_a_sample = max(
        need[0] / peaks["bf16_flops_per_s"],
        need[1] / peaks["hbm_bytes_per_s"])
    shares = []
    for device in devices:
        measured = device["seconds"]["kda/scan"]
        if not measured or not device["steps"]:
            continue
        # this device's samples in the traced steps
        samples = (
            device["steps"] * run["traffic"]["minibatch"] / run["chips"])
        shares.append(samples * least_a_sample / measured)
    return 100.0 * min(shares) if shares else None
