"""mhc_mix_roofline: the least time the chip could take for the
hyper-connections' two mixes in the traced steps -- the larger of FLOPs
over the bf16 peak and bytes over the HBM peak, both from the
configuration's count (``flops/<name>.py:kernels``, entry ``mhc_mix``:
a sublayer's forward reads X and writes X', its backward reads X and
dX' and writes dX, nothing else, so no fusion can beat the count; bytes
bound it) -- over the device time under ``mhc/pre`` and ``mhc/post``
(lib/mhc_trace.py; a Mosaic kernel named ``mhc...`` counts there), in
percent. A forward recomputed by remat is time and no needed work: it
lowers the share, as it should. Left out for a configuration whose
count names no ``mhc_mix`` and for a program without the scopes."""

from benchmark.lib import mhc_trace, window

KINDS = ("mhc/pre", "mhc/post")


def read(run):
    needs = getattr(run.get("flops"), "kernels", None)
    devices = mhc_trace.scoped_devices(mhc_trace.reduced(run))
    if needs is None or not devices:
        return None
    need = needs(run["config"], run["traffic"]).get("mhc_mix")
    if not need:
        return None
    peaks = window.peaks(run)
    least_a_sample = max(
        need[0] / peaks["bf16_flops_per_s"],
        need[1] / peaks["hbm_bytes_per_s"])
    shares = []
    for device in devices:
        measured = sum(device["seconds"][k] for k in KINDS)
        if not measured or not device["steps"]:
            continue
        # this device's samples in the traced steps
        samples = (
            device["steps"] * run["traffic"]["minibatch"] / run["chips"])
        shares.append(samples * least_a_sample / measured)
    return 100.0 * min(shares) if shares else None
