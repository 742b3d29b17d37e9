"""The measured window's arithmetic, shared by the metric readers."""

from benchmark.lib.procs import HarnessFailure


def steps_inside(run, after_step=None):
    """The worker's ``step N loss`` lines (number, seconds, loss) whose
    timestamps fall inside the window; with ``after_step``, only those
    of later steps."""
    t0, t1 = run["window"]
    return [
        s for s in run["worker"]["steps"]
        if t0 <= s[1] <= t1 and (after_step is None or s[0] > after_step)
    ]


def _two_lines(run, after_step=None):
    inside = steps_inside(run, after_step)
    if len(inside) < 2 or inside[-1][1] <= inside[0][1]:
        raise HarnessFailure(
            "fewer than two logged steps inside the window (%d): the "
            "window is too short for this cell's log_every" % len(inside)
        )
    return inside


def samples_per_second(run, after_step=None):
    """Records trained per second, all chips together, as ISSUE 22
    fixes it: the steps between the first and the last logged line
    inside the window, times the minibatch, over the seconds between
    those lines. Each line is written after the worker fetched that
    step's loss, so both ends are moments at which the device had
    finished the step. Every second the window holds counts: a stall
    of the input path, a save or a dispatch hiccup lowers this, as it
    lowers what a user gets for the chip."""
    inside = _two_lines(run, after_step)
    steps = inside[-1][0] - inside[0][0]
    return (
        steps * run["traffic"]["minibatch"] / (inside[-1][1] - inside[0][1])
    )


def interval_rates(run, after_step=None):
    """Samples per second over each interval between consecutive
    logged lines inside the window. Not judged: the commentary line and
    ``report.json`` carry the median and the slowest, which say whether
    a low ``samples_per_s`` was one stall or a slower step."""
    inside = _two_lines(run, after_step)
    return [
        (b[0] - a[0]) * run["traffic"]["minibatch"] / (b[1] - a[1])
        for a, b in zip(inside, inside[1:]) if b[1] > a[1]
    ]


def peaks(run):
    """This device's row of the peaks table; a device that is not in
    the table is an error, never a default."""
    kind = run["worker"]["device_kind"]
    try:
        return run["peaks_table"][kind]
    except KeyError:
        raise HarnessFailure(
            "no published peak for device_kind %r in benchmark/lib/"
            "peaks.json; add it with its source" % kind
        ) from None


def memory_peaks(run):
    """Each local device's peak in bytes, as the zoo's callback last
    wrote the allocator's statistics; empty where the backend reports
    none. On the TPU runtime ``peak_bytes_in_use`` counts buffers
    (state, batch) and a loaded program's temporaries are counted apart
    as ``peak_bytes_reserved`` (PR 22, on the chip: a program with 1 GiB
    of temporaries reserved exactly 1 GiB and left ``in_use`` at its
    arguments), so a device's peak is the sum of the two."""
    return [
        d["peak_bytes_in_use"] + (d.get("peak_bytes_reserved") or 0)
        for d in (run["memory"] or {}).get("devices", [])
        if d.get("peak_bytes_in_use") is not None
    ]


def flops_per_sample(run):
    """FLOPs one sample requires, by the configuration's own count
    (``flops/<name>.py``, loaded by the harness); None for a
    configuration that names none."""
    module = run.get("flops")
    if module is None:
        return None
    return module.per_sample(run["config"], run["traffic"])
