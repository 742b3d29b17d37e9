"""collective_time_share: the time all-gather, reduce-scatter,
all-reduce, all-to-all and collective-permute operations are in flight
on a device (on its core, or asynchronous from start to done) over the
traced window, worst device, in percent. Read only from the devices
whose trace shows the asynchronous ones when any does
(lib/trace_reduce.py:collective_devices), so one trace never mixes two
definitions. Absent on one chip."""

from benchmark.lib.trace_reduce import collective_devices


def read(run):
    trace = run["reduced_trace"]
    if not trace or run["chips"] < 2:
        return None
    shares = [
        d["collective_s"] / d["window_s"]
        for d in collective_devices(trace["devices"]) if d["window_s"]
    ]
    return 100.0 * max(shares) if shares else None
