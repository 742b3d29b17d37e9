"""indexer_score_roofline: the least time the chip could take for the
indexer's scores in the traced steps -- the larger of FLOPs over the
bf16 peak and bytes over the HBM peak of the products over the causal
half of the (query, key) pairs, forward and backward, from the
configuration's count (``flops/<name>.py:kernels``, entry
``indexer_scores``) -- over the device time under ``dsa/scores``
(lib/dsa_trace.py: whatever forms the scores there; today the kernel
``dsa_mask`` by its name), in percent. FLOPs bound it (a 64-wide
contraction fills half an MXU pass, so 50 is the products' own
ceiling). Scores formed again elsewhere (a recompute under remat,
passes inside ``dsa/select`` or ``dsa/indexer_loss``) are time or
another scope's time and no needed work. Left out for a configuration
whose count names no ``indexer_scores`` and for a program with nothing
under the scope."""

from benchmark.lib import dsa_trace, window


def read(run):
    needs = getattr(run.get("flops"), "kernels", None)
    devices = dsa_trace.busy_devices(dsa_trace.reduced(run))
    if needs is None or not devices:
        return None
    need = needs(run["config"], run["traffic"]).get("indexer_scores")
    if not need:
        return None
    peaks = window.peaks(run)
    least_a_sample = max(
        need[0] / peaks["bf16_flops_per_s"],
        need[1] / peaks["hbm_bytes_per_s"])
    shares = []
    for device in devices:
        measured = device["seconds"][dsa_trace.SCORES]
        if not measured or not device["steps"]:
            continue
        # this device's samples in the traced steps
        samples = (
            device["steps"] * run["traffic"]["minibatch"] / run["chips"])
        shares.append(samples * least_a_sample / measured)
    return 100.0 * min(shares) if shares else None
