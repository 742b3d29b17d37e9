"""relu2_gmm_roofline: the least time the chip could take for the held
experts' grouped matmuls in a traced step -- the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, both from the configuration's
count (``flops/<name>.py:kernels``, entry ``relu2_gmm``: the NEEDED
operations at the experts' width, 1856, not the 1920 the tiles cover,
over the rows the held experts get on average) -- over the device time
of the backend's ``gmm`` / ``tgmm`` kernels a step, read from the rows
of ``step_account.json`` (lib/step_account.py: ``kernels`` by name), in
percent. Null without those kernels: a ``ragged_dot`` fallback is not
read. Left out for a configuration whose count names no ``relu2_gmm``
and for a program without the scope registry."""

from benchmark.lib import step_account, window

KERNELS = ("gmm", "tgmm")


def share_of_roofline(run, kernel, measured_ms):
    """The least seconds the chip could take for a step's ``kernel``
    (the configuration's count: the larger of FLOPs over the bf16 peak
    and bytes over the HBM peak) over ``measured_ms`` a step, in
    percent; None where the count names no such kernel or nothing was
    measured. ``ssd_g8_scan_roofline`` reads through it too."""
    needs = getattr(run.get("flops"), "kernels", None)
    if needs is None or not measured_ms:
        return None
    need = needs(run["config"], run["traffic"]).get(kernel)
    if not need:
        return None
    peaks = window.peaks(run)
    # this device's samples a step
    samples = run["traffic"]["minibatch"] / run["chips"]
    least_s = samples * max(
        need[0] / peaks["bf16_flops_per_s"],
        need[1] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (measured_ms / 1e3)


def read(run):
    device = step_account.speaker(step_account.reduced(run))
    if device is None:
        return None
    return share_of_roofline(run, "relu2_gmm", sum(
        ms for row in device["rows"]
        for name, ms in row["kernels"].items()
        if name.lower().startswith(KERNELS)))
