"""gap_attributed_share: of the device's idle time between step
programs, the percentage lying under a named ``edl/<phase>`` annotation
of the loop thread (device trace and the program's annotations on its
clock; the device with the most such idle time). Only the thread that
holds ``edl/step`` counts. The split by phase is ``loop_gaps.json``
beside the report (lib/loop_ledger.py)."""

from benchmark.lib import loop_ledger


def read(run):
    return loop_ledger.gap_attributed_share(loop_ledger.reduced(run))
