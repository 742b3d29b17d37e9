"""window_flash_roofline: the least time the chip could take for the
band's flash kernels' work in the traced steps -- the larger of FLOPs
over the bf16 peak and bytes over the HBM peak, both from the
configuration's count (``flops/<name>.py:kernels``, entry
``flash_window``: the seven score-sized matmuls over the entries the
band KEEPS, at the window layers' heads; at 32,768 x 512 and 64 heads
of 128 the FLOPs bound it, 29 ms a sample against the bytes' 13) --
over the measured device time of the kernels named ``flash_band...``
(lib/window_trace.py), in percent. Grid steps that
compute nothing and tiles that compute entries the band drops are time
and no needed work: they lower the share, as they should, and a
forward recomputed by remat does too. Left out for a configuration
whose count names no ``flash_window`` and for a program without the
band's kernels."""

from benchmark.lib import window, window_trace


def read(run):
    needs = getattr(run.get("flops"), "kernels", None)
    devices = window_trace.scoped_devices(window_trace.reduced(run))
    if needs is None or not devices:
        return None
    need = needs(run["config"], run["traffic"]).get("flash_window")
    if not need:
        return None
    peaks = window.peaks(run)
    least_a_sample = max(
        need[0] / peaks["bf16_flops_per_s"],
        need[1] / peaks["hbm_bytes_per_s"])
    shares = []
    for device in devices:
        measured = device["band_kernels_s"]
        if not measured or not device["steps"]:
            continue
        # this device's samples in the traced steps
        samples = (
            device["steps"] * run["traffic"]["minibatch"] / run["chips"])
        shares.append(samples * least_a_sample / measured)
    return 100.0 * min(shares) if shares else None
