"""The benchmark's only hook inside the worker process.

A zoo module exports ``callbacks`` from here (the model-zoo contract's
optional ``callbacks()``), and the worker calls ``on_batch_end`` after
every step. Without ``EDLBENCH_OUT`` in the environment there is no
callback at all, so a user's job never runs this.
"""

import json
import os

import jax

from elasticdl_tpu.train.callbacks import Callback

OUT_ENV = "EDLBENCH_OUT"


class BenchProbe(Callback):
    """Does nothing unless the harness set ``EDLBENCH_OUT``. Then, on
    every ``EDLBENCH_EVERY``-th step, it asks each local device's
    allocator for its peaks (a host call, no sync) and rewrites
    ``memory.json`` there when they have grown: after the warm-up they
    do not, so the measured window sees no file written by the probe
    (``on_train_end`` is not reached when a signal stops the job). Once
    the harness drops ``trace.flag`` (a traced run, inside the window)
    it starts the profiler on the next step and stops it
    ``trace_steps`` steps later, after waiting for that step's loss so
    the trace holds whole steps."""

    def __init__(self, out_dir, every, trace_steps):
        super().__init__()
        self._out = out_dir
        self._every = max(1, every)
        self._trace_steps = trace_steps
        self._flag = os.path.join(out_dir, "trace.flag")
        self._trace_stop_at = None
        self._written = None
        # an untraced run never looks for the flag
        self._trace_done = os.environ.get("EDLBENCH_TRACE") != "1"

    def on_batch_end(self, step, loss):
        if step % self._every == 0:
            self._write_memory(step)
        if self._trace_done:
            return
        if self._trace_stop_at is None:
            if os.path.exists(self._flag):
                jax.block_until_ready(loss)
                jax.profiler.start_trace(os.path.join(self._out, "trace"))
                self._trace_stop_at = step + self._trace_steps
        elif step >= self._trace_stop_at:
            jax.block_until_ready(loss)
            jax.profiler.stop_trace()
            self._trace_done = True
            with open(os.path.join(self._out, "trace.done"), "w") as f:
                f.write("%d\n" % step)

    def _write_memory(self, step):
        stats = []
        for device in jax.local_devices():
            s = device.memory_stats() or {}
            stats.append({
                "id": device.id,
                "peak_bytes_in_use": s.get("peak_bytes_in_use"),
                "peak_bytes_reserved": s.get("peak_bytes_reserved"),
                "bytes_limit": s.get("bytes_limit"),
            })
        if stats == self._written:
            return
        self._written = stats
        tmp = os.path.join(self._out, "memory.json.tmp")
        with open(tmp, "w") as f:
            json.dump({"step": step, "devices": stats}, f)
        os.replace(tmp, os.path.join(self._out, "memory.json"))


def callbacks():
    out_dir = os.environ.get(OUT_ENV)
    if not out_dir:
        return []
    return [BenchProbe(
        out_dir, int(os.environ.get("EDLBENCH_EVERY", "8")),
        int(os.environ.get("EDLBENCH_TRACE_STEPS", "6")),
    )]
