"""Trace summaries: per-HLO-category (jax.profiler) and per-trace (EDL).

Two halves:

- the original per-HLO-category breakdown of a ``jax.profiler``
  capture (``summarize_trace``; the benchmark's own reduction is
  benchmark/lib/trace_reduce.py);
- ISSUE 9: a summary of an ``EDL_TRACE_DIR`` capture grouped by the
  propagated ``trace_id`` — per-span-name stats (count / p50 / p99)
  plus a per-trace duration table with the slowest-N traces, each
  with its span count and participating roles. Runnable directly:

      python scripts/trace_summary.py TRACE_DIR [--slowest N]
"""

import argparse
import collections
import glob
import gzip
import json
import os
import sys


def latest_trace_path(trace_dir):
    return sorted(
        glob.glob(trace_dir + "/plugins/profile/*/*.trace.json.gz")
    )[-1]


def capture_trace(run_once, trace_dir, steps):
    """Profile one invocation of ``run_once`` (which must fence device
    execution itself, e.g. by fetching a scalar loss) and print the
    per-HLO-category summary. The single capture protocol shared by the
    bench scripts."""
    import jax

    jax.profiler.start_trace(trace_dir)
    run_once()
    jax.profiler.stop_trace()
    return summarize_trace(trace_dir, steps)


def summarize_trace(trace_dir, steps, top=14):
    """Print device time / bytes / bandwidth / flops by HLO category for
    the newest trace under ``trace_dir``; returns the trace path."""
    path = latest_trace_path(trace_dir)
    with gzip.open(path) as f:
        data = json.load(f)
    tpu_pid = None
    for e in data["traceEvents"]:
        if e.get("ph") == "M" and e.get("name") == "process_name" \
                and "TPU" in str(e.get("args", {}).get("name", "")):
            tpu_pid = e["pid"]
    ops = [
        e for e in data["traceEvents"]
        if e.get("ph") == "X" and e.get("pid") == tpu_pid
        and "hlo_category" in e.get("args", {})
        and not e["name"].startswith("while")
    ]
    total = sum(e["dur"] for e in ops)
    cat = collections.Counter()
    catb = collections.Counter()
    catf = collections.Counter()
    for e in ops:
        c = e["args"]["hlo_category"]
        cat[c] += e["dur"]
        catb[c] += int(e["args"].get("bytes_accessed", 0))
        catf[c] += int(float(e["args"].get("flops", 0)))
    print(
        "device time: %.1f ms / %d steps; bytes %.1f GB/step"
        % (total / 1e3, steps, sum(catb.values()) / steps / 1e9)
    )
    for c, dur in cat.most_common(top):
        bw = catb[c] / (dur / 1e6) / 1e9 if dur else 0
        tf = catf[c] / (dur / 1e6) / 1e12 if dur else 0
        print(
            "%5.1f%%  %8.1fms  bw=%6.0f GB/s  %6.1f TFLOP/s  %s"
            % (dur / total * 100, dur / 1e3, bw, tf, c)
        )
    print("trace at:", path)
    return path


# ---------------------------------------------------------------------------
# EDL distributed-trace summary (ISSUE 9)


def _merge_trace():
    """The sibling merge_trace module, importable whether this module
    was loaded as ``scripts.trace_summary`` or bare ``trace_summary``;
    it owns the shared capture helpers (load_events/percentile/...)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import merge_trace
    finally:
        sys.path.pop(0)
    return merge_trace


def summarize_edl_traces(trace_path, slowest=10):
    """Summary dict for an EDL trace dir (or merged file): per-name
    span stats over EVERY complete span, plus per-trace records for
    spans carrying the propagated trace context, slowest first."""
    mt = _merge_trace()
    events = mt.load_events(str(trace_path))
    roles_of_pids = mt.role_by_pid(events)
    spans = [e for e in events if e.get("ph") == "X"]
    by_name = collections.defaultdict(list)
    by_trace = collections.defaultdict(list)
    for event in spans:
        by_name[event["name"]].append(event.get("dur", 0.0) / 1e3)
        trace_id = (event.get("args") or {}).get("trace_id")
        if trace_id:
            by_trace[trace_id].append(event)
    names = {
        name: {
            "count": len(durs),
            "p50_ms": round(mt.percentile(durs, 0.50), 3),
            "p99_ms": round(mt.percentile(durs, 0.99), 3),
            "total_ms": round(sum(durs), 3),
        }
        for name, durs in by_name.items()
    }
    traces = []
    for trace_id, trace_spans in by_trace.items():
        trace_spans.sort(key=lambda e: e["ts"])
        root = next(
            (e for e in trace_spans if "parent_id" not in e["args"]),
            trace_spans[0],
        )
        roles = set()
        for event in trace_spans:
            role = event["args"].get("role") or roles_of_pids.get(
                event.get("pid"), ""
            )
            if role:
                roles.add(mt.normalize_role(role))
        traces.append({
            "trace_id": trace_id,
            "root": root["name"],
            "duration_ms": round(root.get("dur", 0.0) / 1e3, 3),
            "spans": len(trace_spans),
            "roles": sorted(roles),
        })
    traces.sort(key=lambda t: -t["duration_ms"])
    return {
        "spans": len(spans),
        "names": names,
        "traces": len(traces),
        "slowest": traces[:slowest],
    }


def print_edl_summary(summary):
    print("%d span(s), %d trace(s)" % (summary["spans"],
                                       summary["traces"]))
    print("per-name stats:")
    for name, stats in sorted(
        summary["names"].items(), key=lambda kv: -kv[1]["total_ms"]
    ):
        print(
            "  %-28s n=%-6d p50=%8.3fms  p99=%8.3fms  total=%10.3fms"
            % (name, stats["count"], stats["p50_ms"], stats["p99_ms"],
               stats["total_ms"])
        )
    if summary["slowest"]:
        print("slowest traces:")
        for t in summary["slowest"]:
            print(
                "  %s  %-14s %10.3fms  %2d span(s)  %s"
                % (t["trace_id"][:16], t["root"], t["duration_ms"],
                   t["spans"], ",".join(t["roles"]))
            )


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Summarize an EDL_TRACE_DIR capture by span name "
        "and by propagated trace_id",
    )
    parser.add_argument(
        "trace_path", help="EDL_TRACE_DIR or a merged.trace.json"
    )
    parser.add_argument("--slowest", type=int, default=10)
    args = parser.parse_args(argv)
    print_edl_summary(
        summarize_edl_traces(args.trace_path, slowest=args.slowest)
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
