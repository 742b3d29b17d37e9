"""ssd_scan_roofline: the least time the chip could take for the chunked
selective scan's needed work in the traced steps -- the larger of FLOPs
over the bf16 peak and bytes over the HBM peak, both from the
configuration's count (``flops/<name>.py:kernels``, entry ``ssd_scan``)
-- over the device time under ``mamba/scan`` (lib/ssm_trace.py; a Mosaic
kernel named ``ssd...`` counts there), in percent: the share of the
roofline of what runs the scan, kernel or not. Left out for a
configuration whose count names no ``ssd_scan`` and for a program
without the scopes."""

from benchmark.lib import ssm_trace, window


def read(run):
    needs = getattr(run.get("flops"), "kernels", None)
    devices = ssm_trace.scoped_devices(ssm_trace.reduced(run))
    if needs is None or not devices:
        return None
    need = needs(run["config"], run["traffic"]).get("ssd_scan")
    if not need:
        return None
    peaks = window.peaks(run)
    least_a_sample = max(
        need[0] / peaks["bf16_flops_per_s"],
        need[1] / peaks["hbm_bytes_per_s"])
    shares = []
    for device in devices:
        measured = device["seconds"]["mamba/scan"]
        if not measured or not device["steps"]:
            continue
        # this device's samples in the traced steps
        samples = (
            device["steps"] * run["traffic"]["minibatch"] / run["chips"])
        shares.append(samples * least_a_sample / measured)
    return 100.0 * min(shares) if shares else None
