#!/usr/bin/env python
"""Critical-path attribution over a merged EDL distributed trace.

Consumes an ``EDL_TRACE_DIR`` (or an already-merged
``merged.trace.json``) whose spans carry the ISSUE-9 trace context
(``trace_id``/``span_id``/``parent_id`` args) and answers the question
the ROADMAP items keep asking: *which segment* of the step / predict
path is hot. For every trace it walks the span tree and attributes
each span's SELF time (duration minus the union of its children's
intervals — the time that span was the deepest thing running) to a
segment:

==================  =====================================================
segment             spans
==================  =====================================================
queue_wait          master ``dispatch`` / ``Master/*`` handler spans;
                    the ``serve_predict`` root's self time (admission
                    queue + batch formation wait)
pull                ``ps_pull`` / ``ps_pull_batch`` client spans and
                    ``Pserver/pull_*`` handler spans
push                ``ps_push`` / ``ps_push_rows`` client spans
apply               ``ps_apply_push`` and ``Pserver/push_*`` handler
                    spans (server-side deserialize + optimizer apply)
compute             the ``train_batch`` root's self time (forward /
                    backward / device step) and ``serve_batch_run``
                    (the batched forward); the worker ledger's
                    ``edl/dispatch``, ``edl/device_wait``,
                    ``edl/health``, ``edl/state_init`` and
                    ``edl/restore`` children are left unmapped and
                    inherit it, so ``compute`` is what it was before
                    the root had phase children
input_wait          ``edl/input_wait``: the loop waiting for its batch
bookkeeping         ``edl/checkpoint``, ``edl/report``,
                    ``edl/mesh_check``, ``edl/log``, ``edl/callbacks``:
                    the loop's work after the step (the root covers
                    the whole iteration since ISSUE 23; before, this
                    time lay outside every trace)
compile             ``compile`` spans from the ISSUE-18 recompile
                    sentinel — XLA compiles caught on the step path;
                    a steady-state trace showing this segment IS the
                    recompile storm, attributed to the step it stalled
transfer            ``transfer`` spans (ISSUE 18): explicit host<->
                    device movement — output fetches, device-tier
                    gradient extraction — and the ledger's ``edl/h2d``
shed                the full duration of a predict trace whose root
                    failed with RESOURCE_EXHAUSTED / DEADLINE_EXCEEDED
other               anything unrecognized (kept visible, never dropped)
==================  =====================================================

Unmapped spans (``rpc_attempt``, future names) inherit the nearest
mapped ancestor's segment, so retry wire time lands in pull/push where
it belongs. The report gives per-trace-kind (train step / predict)
p50/p99 per segment plus the critical-path breakdown (each segment's
share of total attributed time), and the per-trace role census CI
gates on (a step trace must span worker AND ps).

Report-only by design: CI journals the JSON (tier 1d, like the tier 1f
benches) and asserts only the structural invariants.

**Frame attribution (ISSUE 14).** With ``--frames`` pointing at
``/profilez`` captures (files or a dir of ``*.profile.json``) from the
same run, the report adds a ``frames`` section: the continuous
profiler tags each sample landing inside an open sampled span with
that span's critical-path segment, so every segment above breaks down
into the top-K Python frame stacks that actually burned it —
"``apply`` is 40% of the step" becomes "``apply`` is 40%, and it's
``embedding_store.push_gradients`` → ``np.add.reduceat``".

Usage:
    python scripts/critical_path.py TRACE_DIR [--slowest N] [-o out.json]
        [--frames PROFILES] [--frames-top K]

stdout is the JSON report; the human-readable table goes to stderr.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import merge_trace  # noqa: E402
from merge_trace import (  # noqa: E402 - shared capture helpers
    load_events,
    normalize_role,
    percentile as _percentile,
    role_by_pid,
)

SHED_CODES = ("RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED")

# exact span name -> segment
_SEGMENT_BY_NAME = {
    "dispatch": "queue_wait",
    "ps_pull": "pull",
    "ps_pull_batch": "pull",
    "ps_push": "push",
    "ps_push_rows": "push",
    "ps_apply_push": "apply",
    "serve_batch_run": "compute",
    # device runtime (ISSUE 18): the recompile sentinel's compile
    # spans and explicit host<->device transfer spans
    "compile": "compile",
    "transfer": "transfer",
    # the worker's phase ledger (ISSUE 23): children of train_batch
    # that are not the step itself; the step's own phases stay
    # unmapped and inherit the root's compute
    "edl/input_wait": "input_wait",
    "edl/h2d": "transfer",
    "edl/checkpoint": "bookkeeping",
    "edl/report": "bookkeeping",
    "edl/mesh_check": "bookkeeping",
    "edl/log": "bookkeeping",
    "edl/callbacks": "bookkeeping",
}

# root-span name -> segment its SELF time belongs to
_ROOT_SELF_SEGMENT = {
    "train_batch": "compute",
    "serve_predict": "queue_wait",
}

_ROOT_KIND = {
    "train_batch": "step",
    "serve_predict": "predict",
}


def segment_of(name):
    """Segment for a span name, or None (= inherit the ancestor's)."""
    seg = _SEGMENT_BY_NAME.get(name)
    if seg is not None:
        return seg
    if name.startswith("Pserver/pull"):
        return "pull"
    if name.startswith("Pserver/push"):
        return "apply"
    if name.startswith("Master/"):
        return "queue_wait"
    return None


def _union_secs(intervals):
    """Total length covered by a list of (start, end) intervals."""
    total = 0.0
    last_end = None
    for start, end in sorted(intervals):
        if last_end is None or start > last_end:
            total += end - start
            last_end = end
        elif end > last_end:
            total += end - last_end
            last_end = end
    return total


def analyze_trace(spans, roles_of_pids):
    """Attribution for ONE trace's spans: (record dict) or None when
    the trace has no identifiable root."""
    by_id = {}
    for event in spans:
        span_id = event["args"].get("span_id")
        if span_id:
            by_id[span_id] = event
    children = {}
    roots = []
    for event in spans:
        parent = event["args"].get("parent_id")
        if parent and parent in by_id:
            children.setdefault(parent, []).append(event)
        else:
            roots.append(event)
    if not roots:
        return None
    roots.sort(key=lambda e: e["ts"])
    root = roots[0]
    root_name = root["name"]
    duration_ms = root.get("dur", 0.0) / 1e3
    roles = set()
    for event in spans:
        role = event["args"].get("role") or roles_of_pids.get(
            event.get("pid"), ""
        )
        if role:
            roles.add(normalize_role(role))

    segments = {}

    code = root["args"].get("code")
    if root_name == "serve_predict" and code in SHED_CODES:
        segments["shed"] = duration_ms
        return {
            "trace_id": root["args"].get("trace_id", ""),
            "kind": _ROOT_KIND.get(root_name, "other"),
            "root": root_name,
            "duration_ms": duration_ms,
            "roles": sorted(roles),
            "segments": segments,
            "shed": True,
        }

    def attribute(event, inherited):
        name = event["name"]
        seg = segment_of(name)
        if seg is None:
            seg = (
                _ROOT_SELF_SEGMENT.get(name)
                if event is root
                else inherited
            ) or "other"
        start = event["ts"]
        end = start + event.get("dur", 0.0)
        kids = children.get(event["args"].get("span_id"), [])
        intervals = []
        for kid in kids:
            kid_start = max(start, kid["ts"])
            kid_end = min(end, kid["ts"] + kid.get("dur", 0.0))
            if kid_end > kid_start:
                intervals.append((kid_start, kid_end))
        # ts/dur are microseconds; self time = span minus the union of
        # its children's (clipped) intervals
        self_ms = max(
            0.0, (end - start) - _union_secs(intervals)
        ) / 1e3
        segments[seg] = segments.get(seg, 0.0) + self_ms
        for kid in kids:
            attribute(kid, seg)

    # attribute every top-level span (the root plus any span whose
    # parent lived in a process that never flushed — clock-aligned
    # orphans still count rather than vanish)
    for top in roots:
        attribute(top, None)
    return {
        "trace_id": root["args"].get("trace_id", ""),
        "kind": _ROOT_KIND.get(root_name, "other"),
        "root": root_name,
        "duration_ms": duration_ms,
        "roles": sorted(roles),
        "segments": segments,
        "shed": False,
    }


def _summarize(records):
    durations = [r["duration_ms"] for r in records]
    segment_values = {}
    for record in records:
        for seg, ms in record["segments"].items():
            segment_values.setdefault(seg, []).append(ms)
    total_attributed = sum(sum(v) for v in segment_values.values())
    segments = {}
    for seg, values in sorted(segment_values.items()):
        seg_total = sum(values)
        # traces where the segment never appeared count as 0 for the
        # percentiles: "pull was 0 in half the steps" is signal
        padded = values + [0.0] * (len(records) - len(values))
        segments[seg] = {
            "p50_ms": round(_percentile(padded, 0.50), 3),
            "p99_ms": round(_percentile(padded, 0.99), 3),
            "mean_ms": round(seg_total / len(records), 3),
            "share": round(
                seg_total / total_attributed if total_attributed else 0.0,
                4,
            ),
        }
    multi_role = sum(1 for r in records if len(r["roles"]) >= 2)
    all_roles = sorted({role for r in records for role in r["roles"]})
    return {
        "count": len(records),
        "p50_ms": round(_percentile(durations, 0.50), 3),
        "p99_ms": round(_percentile(durations, 0.99), 3),
        "roles": all_roles,
        "multi_role_traces": multi_role,
        "segments": segments,
    }


def build_report(events, slowest=10):
    roles_of_pids = role_by_pid(events)
    by_trace = {}
    for event in events:
        if event.get("ph") != "X":
            continue
        trace_id = (event.get("args") or {}).get("trace_id")
        if not trace_id:
            continue
        by_trace.setdefault(trace_id, []).append(event)
    records = []
    for spans in by_trace.values():
        record = analyze_trace(spans, roles_of_pids)
        if record is not None:
            records.append(record)
    report = {
        "traces": len(records),
        "slowest": sorted(
            records, key=lambda r: -r["duration_ms"]
        )[:slowest],
    }
    for kind in ("step", "predict"):
        of_kind = [r for r in records if r["kind"] == kind]
        if of_kind:
            report[kind] = _summarize(of_kind)
    other = [r for r in records if r["kind"] == "other"]
    if other:
        report["other"] = _summarize(other)
    return report


def load_profiles(path_spec):
    """/profilez capture dicts from a comma-separated list of files
    and/or directories (discovery + tolerant load shared with
    scripts/profile_report.py)."""
    import profile_report

    paths = [p.strip() for p in path_spec.split(",") if p.strip()]
    return [
        capture
        for _path, capture in profile_report.load_captures(
            profile_report.discover(paths)
        )
    ]


def frames_by_segment(profiles, top=3):
    """{segment: [{stack, count, roles}]}: the top-K span-tagged frame
    stacks per critical-path segment, merged across roles. Untagged
    samples (no open span at sample time) are excluded — they have no
    segment to attribute to."""
    tally = {}  # segment -> stack tuple -> [count, roles set]
    for profile in profiles:
        role = profile.get("role", "?")
        for entry in profile.get("stacks", ()):
            segment = entry.get("segment")
            if not segment:
                continue
            stack = tuple(entry.get("stack", ()))
            if not stack:
                continue
            bucket = tally.setdefault(segment, {})
            slot = bucket.get(stack)
            if slot is None:
                bucket[stack] = [int(entry.get("count", 0)), {role}]
            else:
                slot[0] += int(entry.get("count", 0))
                slot[1].add(role)
    return {
        segment: [
            {
                "stack": list(stack),
                "count": count,
                "roles": sorted(roles),
            }
            for stack, (count, roles) in sorted(
                bucket.items(), key=lambda kv: (-kv[1][0], kv[0])
            )[:top]
        ]
        for segment, bucket in sorted(tally.items())
    }


def render_text(report, out=sys.stderr):
    print("critical-path attribution: %d trace(s)" % report["traces"],
          file=out)
    for kind in ("step", "predict", "other"):
        summary = report.get(kind)
        if not summary:
            continue
        print(
            "%s: n=%d p50=%.2fms p99=%.2fms roles=%s (%d multi-role)"
            % (kind, summary["count"], summary["p50_ms"],
               summary["p99_ms"], ",".join(summary["roles"]),
               summary["multi_role_traces"]),
            file=out,
        )
        for seg, stats in sorted(
            summary["segments"].items(), key=lambda kv: -kv[1]["share"]
        ):
            print(
                "  %-12s %5.1f%%  p50=%8.3fms  p99=%8.3fms"
                % (seg, stats["share"] * 100, stats["p50_ms"],
                   stats["p99_ms"]),
                file=out,
            )
    for record in report["slowest"][:5]:
        print(
            "  slow %s %s %.2fms %s"
            % (record["root"], record["trace_id"][:16],
               record["duration_ms"], record["roles"]),
            file=out,
        )
    frames = report.get("frames")
    if frames:
        print("segment frame stacks (continuous profiler):", file=out)
        for segment, stacks in frames.items():
            print("  %s:" % segment, file=out)
            for entry in stacks:
                # leaf-most frames carry the signal; elide long roots
                stack = entry["stack"]
                shown = ";".join(stack[-4:])
                if len(stack) > 4:
                    shown = "...;" + shown
                print(
                    "    %6d  %s" % (entry["count"], shown), file=out
                )


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "trace_path",
        help="EDL_TRACE_DIR of the run, or a merged.trace.json",
    )
    parser.add_argument("--slowest", type=int, default=10,
                        help="slowest-N traces to include (default 10)")
    parser.add_argument("-o", "--output", default="",
                        help="also write the JSON report here")
    parser.add_argument(
        "--frames", default="",
        help="comma-separated /profilez capture files or dirs of "
             "*.profile.json from the same run: break each segment "
             "down into its top span-tagged frame stacks (ISSUE 14)",
    )
    parser.add_argument("--frames-top", type=int, default=3,
                        help="frame stacks per segment (default 3)")
    args = parser.parse_args(argv)
    events = load_events(args.trace_path)
    report = build_report(events, slowest=args.slowest)
    if args.frames:
        report["frames"] = frames_by_segment(
            load_profiles(args.frames), top=args.frames_top
        )
    render_text(report)
    text = json.dumps(report)
    if args.output:
        with open(args.output, "w", encoding="utf-8") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
