"""held_pairs_over_share: the (token, choice) pairs that fell on the
experts this chip holds, over their expectation under a uniform router
(tokens x k x held / all experts), in the layer where they were most;
median over the ``moe_routing`` journal events of a FIXED range of
steps, the ``EVENTS`` logged steps after the cell's warm-up, not the
window's wall time (as ``expert_load_max_over_mean`` reads its own). 1
is a router that spreads its load evenly over the chips of a layer; the
row buffer (``expert_rows.held_rows``) has to hold this ratio's largest
value. Left out for a program that journals no ``held_pairs``."""

import statistics

from benchmark.lib import loop_ledger

EVENTS = 9


def read(run):
    config, traffic = run["config"], run["traffic"]
    published = config.get("published", {}).get("num_experts")
    if not published:
        return None
    expected = (
        traffic["seq_len"] * traffic["minibatch"]
        * config["num_experts_per_tok"] * config["num_experts"] / published)
    first = run["cell"]["warmup_steps"]
    last = first + EVENTS * run["cell"]["log_every"]
    ratios = [
        e["held_pairs"] / expected
        for e in loop_ledger.worker_events(run)
        if e.get("event") == "moe_routing" and "held_pairs" in e
        and first < e.get("step", 0) <= last
    ]
    return statistics.median(ratios) if ratios else None
