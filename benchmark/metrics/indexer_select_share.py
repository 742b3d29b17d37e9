"""indexer_select_share: device time under ``dsa/select`` alone -- the
k-th largest of each query's scores (the kernel ``dsa_select`` by its
name: it forms a block of queries' scores in VMEM and bisects on their
bits) -- over device busy time, worst device, in percent. A selection
needs no FLOPs, so all of it is overhead the required work does not
count (lib/dsa_trace.py). Left out for a program without the scope."""

from benchmark.lib import dsa_trace


def read(run):
    return dsa_trace.time_share(
        dsa_trace.reduced(run), [dsa_trace.SELECT])
