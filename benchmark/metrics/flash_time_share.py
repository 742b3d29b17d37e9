"""flash_time_share: device time of the flash attention kernels
(forward, dq, dkv) over device busy time, worst device, in percent.
The kernels are found by name among the trace's Mosaic kernels: the
program names none yet, so today that is the flax scope they are called
in (``attn``, lib/trace_reduce.py); ``flash`` is what a named kernel
would carry, and on a mesh the kernel runs inside the ``shard_map`` of
``ops/attention.py:_shard_over_mesh`` and takes that name. No other
Mosaic kernel runs in the dense cells."""

KERNEL_WORDS = ("flash", "attn", "shard_map")


def flash_seconds(device):
    return sum(
        secs for name, secs in device["kernels"].items()
        if any(word in name.lower() for word in KERNEL_WORDS)
    )


def read(run):
    trace = run["reduced_trace"]
    if not trace:
        return None
    shares = [
        flash_seconds(d) / d["busy_s"]
        for d in trace["devices"] if d["busy_s"] and d["kernels"]
    ]
    return 100.0 * max(shares) if shares else None
