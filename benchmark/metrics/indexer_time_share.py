"""indexer_time_share: device time of a learned sparse-attention
indexer -- the operations under ``dsa/indexer_proj``, ``dsa/scores``,
``dsa/select`` and ``dsa/indexer_loss`` (its projections, the pairs'
scores, the selection, its own KL term and that term's gradient;
forward, recompute and backward; the kernels ``dsa_select``,
``dsa_mask`` and ``dsa_indexer_loss`` by their names) -- over device
busy time, worst device, in percent: what choosing the keys costs
beside the attention over them (``dsa/attend``, which
``flash_time_share`` reads). ``dsa_reduced.json`` beside the report has
the parts apart (lib/dsa_trace.py). Left out for a program without the
scopes."""

from benchmark.lib import dsa_trace


def read(run):
    return dsa_trace.time_share(
        dsa_trace.reduced(run), dsa_trace.INDEXER_KINDS)
