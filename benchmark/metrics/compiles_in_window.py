"""compiles_in_window: compile-ledger lines (compiles and recompiles of
any instrumented function) timestamped inside the window. Should be 0;
``correct`` is false otherwise."""


def read(run):
    t0, t1 = run["window"]
    return float(sum(
        1 for c in run["worker"]["compiles"] if t0 <= c["at"] <= t1
    ))
