"""mamba_g8_time_share: device time of the Mamba-2 mixers of a stack of
one-sublayer layers (8 groups, chunk 128) -- every operation under a
``mamba/`` scope of ``Mamba2Mixer`` (``in_proj``, ``conv``, ``gates``,
``scan``, ``out_norm``, ``out_proj``; forward, backward and recompute)
-- over device busy time, in percent, summed from the rows of
``step_account.json`` (lib/step_account.py). Left out for a program
without the scope registry."""

from benchmark.lib import step_account


def read(run):
    return step_account.share(
        step_account.reduced(run),
        lambda row: row["scope"].startswith("mamba/"))
