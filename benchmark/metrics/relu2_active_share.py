"""relu2_active_share: of the held experts' hidden units ``x W_up``
(the rows that carry a pair x the experts' width), the percentage above
zero after the ReLU, the expert layers' mean; median over the
``moe_routing`` journal events of the steps logged inside the window
(the event's ``relu2_active_share``, ``models/moe_transformer.py:
MoeMlp``). What a ReLU-squared body's sparsity is at seeded weights,
and what a kernel that skips the zeros would be sized by: a description
of the traffic the experts' second matmul sees, not a cost. Left out
for a program that journals no such field."""

import statistics

from benchmark.lib import loop_ledger, window


def read(run):
    inside = {step for step, _, _ in window.steps_inside(run)}
    shares = [
        e["relu2_active_share"]
        for e in loop_ledger.worker_events(run)
        if e.get("event") == "moe_routing" and "relu2_active_share" in e
        and e.get("step") in inside
    ]
    return 100.0 * statistics.median(shares) if shares else None
