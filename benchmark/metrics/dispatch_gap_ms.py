"""dispatch_gap_ms: median gap on the device between one step
program's last operation and the next one's first (device trace,
worst device). What the host's loop costs the chip each step."""


def read(run):
    trace = run["reduced_trace"]
    if not trace:
        return None
    medians = [
        d["step_gap_median_ms"] for d in trace["devices"]
        if d.get("step_gap_median_ms") is not None
    ]
    return max(medians) if medians else None
