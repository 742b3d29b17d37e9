"""step_compile_s: seconds the train step's first call took (compile
ledger ``call``): a compile when cold, a cache load when warm."""


def read(run):
    calls = [
        c["call_s"] for c in run["worker"]["compiles"]
        if c["n"] == 1 and c["fn"].endswith("train_step")
    ]
    return calls[0] if calls else None
