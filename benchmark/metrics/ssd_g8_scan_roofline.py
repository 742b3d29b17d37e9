"""ssd_g8_scan_roofline: the least time the chip could take for the
chunked selective scan's needed work in a traced step at 8 groups and a
chunk of 128 -- the larger of FLOPs over the bf16 peak and bytes over
the HBM peak, both from the configuration's count
(``flops/<name>.py:kernels``, entry ``ssd_scan``: bytes bound it) --
over the device time under ``mamba/scan`` a step (a Mosaic kernel named
``ssd...`` counts there), read from the rows of ``step_account.json``
(lib/step_account.py), in percent: the share of the roofline of what
runs the scan, kernel or not. Left out for a configuration whose count
names no ``ssd_scan`` and for a program without the scope registry."""

from benchmark.lib import step_account
from benchmark.metrics.relu2_gmm_roofline import share_of_roofline


def read(run):
    device = step_account.speaker(step_account.reduced(run))
    if device is None:
        return None
    return share_of_roofline(run, "ssd_scan", sum(
        row["ms"] for row in device["rows"] if row["scope"] == "mamba/scan"))
