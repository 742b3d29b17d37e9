"""launch_to_first_step_s: worker spawn to the return of the train
step's first call (its compile-ledger line): imports, backend start,
state init, compile or cache load. Layer: entry points."""


def read(run):
    firsts = [
        c["at"] for c in run["worker"]["compiles"]
        if c["n"] == 1 and c["fn"].endswith("train_step")
    ]
    if not firsts:
        return None
    return min(firsts) - run["spawn_time"]
