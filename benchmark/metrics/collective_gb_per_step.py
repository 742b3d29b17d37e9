"""collective_gb_per_step: gigabytes of collective results a device
that the compiled train step holds: the ``collectives.bytes`` of the
worker journal's ``xla_compile`` event for the train step (the compile
ledger reads the executable's HLO text once a compile: all-gather,
all-reduce, reduce-scatter, all-to-all and collective-permute, an
asynchronous pair or a repeated ``channel_id`` counted once; a static
count, which is the traffic of a step as long as no collective sits in
a loop). The event's ``by_kind`` and ``largest`` say what they are. A
program without the event (before PR 24) reports nothing."""

from benchmark.lib import loop_ledger


def read(run):
    for event in loop_ledger.worker_events(run):
        if (event.get("event") == "xla_compile"
                and str(event.get("fn", "")).endswith("train_step")
                and event.get("collectives")):
            return event["collectives"]["bytes"] / 1e9
    return None
