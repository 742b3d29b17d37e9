"""device_idle_share: 1 - (union of the device's operation intervals)
/ traced window, averaged over the chips used, in percent."""


def read(run):
    trace = run["reduced_trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
