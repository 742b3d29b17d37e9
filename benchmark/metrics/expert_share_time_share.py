"""expert_share_time_share: device time under the ``moe/`` scopes
(``router``, ``dispatch``, ``experts``, ``combine``, ``shared``; forward
and backward) of an expert layer that holds a SHARE of its experts, over
device busy time, worst device, in percent (lib/gdn_trace.py). The
router, the top-k and the sort run over every (token, choice) pair
(327,680 in ``qwen3next80b-s32k``) though only the held experts' pairs
(20,480 on average) are gathered and multiplied: the share says what
that costs. Left out for a program without the ``gdn/`` scopes."""

from benchmark.lib import gdn_trace


def read(run):
    return gdn_trace.time_share(gdn_trace.reduced(run), gdn_trace.MOE_KINDS)
