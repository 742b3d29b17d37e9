"""short_conv_gate_roofline: the least time the chip could take for the
gate-convolve-gate passes in the traced steps -- their bytes over the
HBM's peak, from the configuration's count (``flops/<name>.py:kernels``,
entry ``short_conv_gate``: a forward reads B, C, X and writes y, a
backward reads B, C, X, dy and writes dB, dC, dX, nothing else, so no
fusion can beat the count; their operations are the vector unit's and
bound nothing) -- over the device time under ``short_conv/gate``
(lib/conv_trace.py; a Mosaic kernel named ``short_conv...`` counts
there), in percent. A forward recomputed by remat is time and no needed
work: it lowers the share, as it should. Left out for a configuration
whose count names no ``short_conv_gate`` and for a program with
nothing under the scope."""

from benchmark.lib import conv_trace, window


def read(run):
    needs = getattr(run.get("flops"), "kernels", None)
    devices = conv_trace.busy_devices(conv_trace.reduced(run))
    if needs is None or not devices:
        return None
    need = needs(run["config"], run["traffic"]).get("short_conv_gate")
    if not need:
        return None
    least_a_sample = need[1] / window.peaks(run)["hbm_bytes_per_s"]
    shares = []
    for device in devices:
        measured = device["seconds"][conv_trace.GATE]
        if not measured or not device["steps"]:
            continue
        # this device's samples in the traced steps
        samples = (
            device["steps"] * run["traffic"]["minibatch"] / run["chips"])
        shares.append(samples * least_a_sample / measured)
    return 100.0 * min(shares) if shares else None
