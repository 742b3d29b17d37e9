"""expert_matmul_roofline: the least time the chip could take for the
experts' grouped matmuls in the traced steps -- the larger of FLOPs
over the bf16 peak and bytes over the HBM peak, both from the
configuration's count (``flops/<name>.py:kernels``, entry
``moe_experts``) -- over their measured device time
(lib/moe_trace.py: the ragged-dot kernels, or a kernel of the repo's
own under ``moe/experts``), worst device, in percent. At 4096 rows an
expert and widths 2048 x 1024 FLOPs bound it. Left out for a
configuration whose count names no ``moe_experts``."""

from benchmark.lib import moe_trace, window


def read(run):
    needs = getattr(run.get("flops"), "kernels", None)
    devices = moe_trace.scoped_devices(moe_trace.reduced(run))
    if needs is None or not devices:
        return None
    need = needs(run["config"], run["traffic"]).get("moe_experts")
    if not need:
        return None
    peaks = window.peaks(run)
    least_a_sample = max(
        need[0] / peaks["bf16_flops_per_s"],
        need[1] / peaks["hbm_bytes_per_s"],
    )
    shares = [
        # this device's samples in the traced steps
        d["steps"] * run["traffic"]["minibatch"] / run["chips"]
        * least_a_sample / d["expert_matmul_s"]
        for d in devices if d["expert_matmul_s"]
    ]
    return 100.0 * min(shares) if shares else None
