"""collective_exposed_share: the time a collective holds a device's
core (a synchronous one, or the wait for an asynchronous one's done)
while no compute operation runs there, over the traced window, worst
device, in percent. Every device's trace shows it. Absent on one
chip."""


def read(run):
    trace = run["reduced_trace"]
    if not trace or run["chips"] < 2:
        return None
    shares = [
        d["collective_exposed_s"] / d["window_s"]
        for d in trace["devices"] if d["window_s"]
    ]
    return 100.0 * max(shares) if shares else None
