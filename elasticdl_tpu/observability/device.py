"""Device-runtime observability (ISSUE 18): the XLA side of the job.

Every observability layer before this one watches the HOST — Python
stacks, RPCs, locks, loss scalars. This module watches the device
runtime through three instruments:

1. **Recompile sentinels** — ``instrumented_jit`` wraps ``jax.jit``
   and detects, per wrapped step function, whether each call hit the
   compiled-executable cache or compiled: the jit object's cache size
   moves exactly when a new argument signature compiled. A compile
   records a compile-time histogram sample, a ``compile`` span into
   the PR 9 tracer, and the *shape/dtype provenance* of the new
   signature; a RE-compile (any compile after the wrapper's first)
   additionally journals an ``xla_recompile`` event carrying which
   leaves changed — the flight-recorder answer to "why did step 4127
   take 40 s".
2. **Device-memory accounting** — ``memory_snapshot`` reads the
   runtime allocator (``device.memory_stats()``) of every local
   device where it exists, keeps the devices apart and sums nothing:
   its summary is the fullest device's buffers PLUS what its loaded
   programs reserve for their temporaries, which is what fills a chip
   (ISSUE 47). It falls back to walking ``jax.live_arrays()`` on
   backends without an HBM allocator (CPU CI), keeping a
   process-lifetime peak watermark there. ``EDL_HBM_LIMIT_BYTES``
   supplies a synthetic limit where the backend reports none, so the
   ``hbm_pressure`` fleet alert is drillable on any box. A worker
   journals the snapshot three times a process (``journal_memory``:
   ``device_memory``), never on the step path.
3. **Cost-model step attribution** — on a compile the wrapper
   AOT-relowers the function (``jitted.lower(*args).compile()``) and
   keeps the executable's ``cost_analysis()`` FLOPs/bytes. jax serves
   that relower from the compilation the call just did — 0.04 s next
   to a 36 s first call for the zoo transformer on a v5e (PERF.md,
   PR 21) — so it is not a second compile; the compile log line
   carries both figures so a jax that stops doing so is noticed.
   The worker's MFU
   bridge consumes these instead of the hand-coded per-model table,
   and host↔device ``transfer`` counters/spans let
   ``scripts/critical_path.py`` attribute a ``transfer`` segment.
   The same executable's HLO text is read once for the collectives
   the partitioner put into the program (``collective_stats``): count
   and result bytes by kind and the largest single one, on the compile
   log line and the ``xla_compile`` journal event, so that what the
   dense plane's cost model expects (``parallel/dense_plane.py``) can
   be read against what the program does; and for the Pallas kernels
   it holds, by name (``pallas_kernels``), in the same two places: a
   step whose flash backward fell back from ``flash_bwd`` to
   ``flash_dq`` + ``flash_dkv`` says so there. And (ISSUE 47) for the
   program's memory: the compiler's own count of the same executable
   (``compiled_memory``) and, from the same text, which is the
   SCHEDULED module, what is live at the program's fullest point by
   the program's scopes (``peak_live``); both in ``xla_compile`` and
   on a log line of their own after the compile line.
4. **The compile split** (ISSUE 33) -- jax times its own tracing,
   lowering and backend compile, and says whether the persistent
   compilation cache was asked, hit or missed (``jax.monitoring``).
   ``install_listeners`` registers for those once a process; they
   are called only when jax traces, lowers, compiles or reads its
   cache, never by a call that hits the jit cache. A wrapped
   function's compile carries its ``stages`` (``_call_stages``), the
   process keeps totals over ALL programs, the eager ones included
   (``compile_totals``), and a program the cache did not hold is one
   ``xla_cache_miss`` journal event.

Disabled path (``EDL_DEVICE_OBS=0``): ``instrumented_jit`` returns the
**raw ``jax.jit`` product, unchanged** — no wrapper frame, no per-call
bookkeeping, no module state, no extra metric series or events. The
factory-default program is byte-identical to the pre-ISSUE-18 one
(test-asserted in tests/test_device_obs.py).

Knobs (all via common/env_utils, documented in docs/OBSERVABILITY.md):

- ``EDL_DEVICE_OBS``            (default 1) master gate
- ``EDL_DEVICE_COST_ANALYSIS``  (default 1) AOT cost/memory fetch per
  compile, capped at ``_COST_FETCH_CAP`` per wrapper
- ``EDL_HBM_LIMIT_BYTES``       (default 0) synthetic allocator limit
  for backends whose ``memory_stats()`` reports none
"""

import collections
import contextlib
import functools
import math
import re
import threading
import time
import weakref

from elasticdl_tpu.common.env_utils import env_bool, env_int
from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import events
from elasticdl_tpu.observability import metrics as obs_metrics
from elasticdl_tpu.observability import scopes
from elasticdl_tpu.observability import trace
# (``op_scope`` lives with the registry since ISSUE 62; its callers and
# tests find it here as before)
from elasticdl_tpu.observability.scopes import op_scope  # noqa: F401

logger = _logger_factory("elasticdl_tpu.observability.device")

DEVICE_OBS_ENV = "EDL_DEVICE_OBS"
COST_ANALYSIS_ENV = "EDL_DEVICE_COST_ANALYSIS"
HBM_LIMIT_ENV = "EDL_HBM_LIMIT_BYTES"

# AOT cost-analysis relowers per wrapper: each fetch re-traces the
# function, so a shape-churning wrapper must not turn the sentinel
# into a tracing amplifier
_COST_FETCH_CAP = 8
# provenance payload bounds: journal lines are read by humans and the
# postmortem, not parsed exhaustively
_PROVENANCE_CHANGED_MAX = 8
_PROVENANCE_SIG_MAX = 16

_lock = threading.Lock()
# live wrappers (weak: the device tier rebuilds its jit cache on PS
# restart and the dead wrappers must not pin memory or double-count)
_wrappers = []
# process-lifetime cumulative totals — monotonic even across wrapper
# rebuilds, which is what the fleet recompile_storm detector needs
_totals = {
    "compiles": 0,
    "recompiles": 0,
    "compile_secs": 0.0,
    "h2d_bytes": 0,
    "d2h_bytes": 0,
}
# host-side watermark across memory_snapshot() polls, where the backend
# has no allocator to keep a peak of its own
_hbm_peak = 0
# a device's limit as the last memory_snapshot() read it: the memory
# log line's ``of 16.90``, without asking the allocator again
_hbm_limit = 0

# jax's own account of a compile (jax.monitoring, jax 0.9.0): each of
# the three is reported as a scalar when it starts (its epoch start
# time) and as a duration and a time span, epoch start and end, when
# it ends
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# inside the backend stage, when the program has a cache key: asked,
# then on a hit the seconds of the read and what the compile had cost
_CACHE_ANSWER = {
    # asked: a miss until the cache says otherwise
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
    "/jax/compilation_cache/compile_time_saved_sec": "saved",
}
# what ``_call_stages`` looks back over: one wrapped call leaves three
# outermost spans, an eager op between two calls three more
_SPANS_KEPT = 64

# process totals over ALL programs, wrapped or not. ``requests`` are
# programs handed to the backend, ``hits`` / ``misses`` those of them
# with a cache key (the rest had none: no cache directory, or a
# program jax does not cache); seconds count outermost stages only (a
# jit traced inside a trace is its parent's time); ``listener_calls``
# is what this instrument itself was called
_stage_totals = {
    "requests": 0, "hits": 0, "misses": 0,
    "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
    "retrieval_s": 0.0, "listener_calls": 0,
}
_listeners_installed = False
# open stages, the cache's answer and the outermost spans, by thread:
# jax calls a listener on the thread that compiles
_stage_tls = threading.local()
# the start-up record's open phase, while there is one (timing_utils)
_phase_source = None

# instruments hoisted to module scope (obs-hot-path discipline): the
# registry returns NOOPs when metrics collection is off. LAZY: the
# trainers import this module before a role's main() publishes
# EDL_METRICS_PORT; an eager counter() here would freeze the process
# registry disabled and blank /metrics for the whole role.
_m_compiles = obs_metrics.lazy_counter(
    "edl_xla_compiles_total",
    "XLA compiles (new argument signatures) per wrapped step fn",
    ("fn",),
)
_m_recompiles = obs_metrics.lazy_counter(
    "edl_xla_recompiles_total",
    "XLA compiles beyond each wrapped step fn's first",
    ("fn",),
)
_m_persistent_hits = obs_metrics.lazy_counter(
    "edl_xla_persistent_cache_hits_total",
    "Programs loaded from the persistent compilation cache",
)
_m_persistent_misses = obs_metrics.lazy_counter(
    "edl_xla_persistent_cache_misses_total",
    "Programs the persistent compilation cache was asked for and did "
    "not hold (compiled)",
)
_m_compile_secs = obs_metrics.lazy_histogram(
    "edl_xla_compile_seconds",
    "Wall seconds of calls that compiled (trace+compile+run)",
    buckets=(0.05, 0.25, 1.0, 5.0, 20.0, 60.0, 180.0),
)
_m_transfer_bytes = obs_metrics.lazy_counter(
    "edl_device_transfer_bytes_total",
    "Host<->device transfer bytes attributed by direction",
    ("direction",),
)
_m_hbm_in_use = obs_metrics.lazy_gauge(
    "edl_device_hbm_bytes_in_use",
    "Device-memory bytes in use on the fullest local device: its "
    "buffers plus what its loaded programs reserve (allocator stats; "
    "the live-buffer sum where the backend has no allocator)",
)
_m_hbm_peak = obs_metrics.lazy_gauge(
    "edl_device_hbm_peak_bytes",
    "Peak device-memory bytes of the fullest local device (the "
    "allocator's peak in use plus its peak reserved, or the "
    "process-lifetime watermark of the fallback)",
)
_m_live_buffers = obs_metrics.lazy_gauge(
    "edl_device_live_buffers",
    "Live device arrays held by this process",
)


def device_obs_enabled():
    """The master gate: EDL_DEVICE_OBS=0 switches every path in this
    module off and makes ``instrumented_jit`` a pure ``jax.jit``."""
    return env_bool(DEVICE_OBS_ENV, True)


COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# one HLO instruction: ``%name = <shape> <opcode>(<rest of line>``. An
# asynchronous pair counts once, at its ``-done`` (whose result is the
# collective's own; a ``-start`` returns a tuple that repeats the
# operand), and a collective wrapped in ``async-start`` /
# ``async-done`` counts at the instruction inside the wrapped
# computation.
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?\S+ = (\(.*?\)|\S+) (%s)(-done)?\((.*)$"
    % "|".join(COLLECTIVE_KINDS),
    re.MULTILINE,
)
_RESULT_ARRAYS_MAX = 3
# a Pallas kernel in a TPU program: a ``tpu_custom_call`` whose op_name
# ends in the kernel's ``name=`` (inside ``jvp(`` / ``transpose(`` where
# autodiff put it) and ``/pallas_call``
_PALLAS_KERNEL_RE = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r'op_name="[^"]*?(\w+)\)*/pallas_call"'
)
_CHANNEL_RE = re.compile(r"\bchannel_id=(\d+)")
_HLO_ARRAY_RE = re.compile(r"\b([a-z]+\d*\w*)\[([\d,]*)\]")


def _hlo_array_bytes(dtype, dims):
    bits = re.search(r"\d+", dtype)
    # pred is a byte; sub-byte types round up per array
    return -(-math.prod(dims) * (int(bits.group()) if bits else 8) // 8)


def hlo_collectives(hlo_text):
    """The collectives of a compiled program's HLO text: one ``(kind,
    arrays, bytes)`` each, ``arrays`` the ``(dtype, dims)`` of its
    result (several for a tuple) and ``bytes`` their size on one
    device. Instructions that share a ``channel_id`` are one
    collective: the TPU compiler repeats an asynchronous all-gather in
    every fused computation it continues through. A static count: a
    collective inside a loop body counts once."""
    found = []
    channels = set()
    for match in _COLLECTIVE_RE.finditer(hlo_text):
        shape, kind, done, rest = match.groups()
        channel = None if done else _CHANNEL_RE.search(rest)
        if channel:
            if (kind, channel.group(1)) in channels:
                continue
            channels.add((kind, channel.group(1)))
        arrays = [
            (dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in _HLO_ARRAY_RE.findall(shape)
        ]
        found.append((
            kind, arrays,
            sum(_hlo_array_bytes(dtype, dims) for dtype, dims in arrays),
        ))
    return found


def _result_text(arrays):
    """``bf16[2048,8192]``; of a tuple the first arrays and how many
    follow (a combined all-reduce returns every small gradient)."""
    shown = [
        "%s[%s]" % (dtype, ",".join(map(str, dims)))
        for dtype, dims in arrays[:_RESULT_ARRAYS_MAX]
    ]
    if len(arrays) > _RESULT_ARRAYS_MAX:
        shown.append("+%d more" % (len(arrays) - _RESULT_ARRAYS_MAX))
    return ", ".join(shown)


def collective_stats(hlo_text):
    """``hlo_collectives`` folded for the journal: ``{kind: {"count",
    "bytes"}}`` for every kind (zeros included, so a one-device program
    says so), ``bytes`` over all of them and the ``largest`` single one
    (``{"kind", "result", "bytes"}``, None without any)."""
    by_kind = {kind: {"count": 0, "bytes": 0} for kind in COLLECTIVE_KINDS}
    largest = None
    for kind, arrays, nbytes in hlo_collectives(hlo_text):
        by_kind[kind]["count"] += 1
        by_kind[kind]["bytes"] += nbytes
        if largest is None or nbytes > largest["bytes"]:
            largest = {
                "kind": kind, "result": _result_text(arrays),
                "bytes": nbytes,
            }
    return {
        "by_kind": by_kind,
        "bytes": sum(entry["bytes"] for entry in by_kind.values()),
        "largest": largest,
    }


def pallas_kernels(hlo_text):
    """``{name: count}`` of the Pallas kernels in a compiled TPU
    program's HLO text, in the program's order. A static count, like
    the collectives': a kernel inside a loop body counts once."""
    return dict(collections.Counter(_PALLAS_KERNEL_RE.findall(hlo_text)))


# ---------------------------------------------------------------------------
# the compiler's count of a program's memory, and what is live at its peak

def compiled_memory(compiled):
    """The compiler's count of one executable's memory in bytes a
    device: ``arguments``, ``outputs``, ``aliased`` (outputs that share
    a donated argument's buffer), ``temporaries``, ``code`` and
    ``peak``, with ``peak_from``: ``"compiler"`` where the runtime
    gives a peak of its own (the TPU's), else ``"sum"``: arguments +
    outputs - aliased + temporaries. None where the backend has no
    memory analysis."""
    analysis = compiled.memory_analysis()
    if analysis is None:
        return None
    memory = {
        key: int(getattr(analysis, field, 0) or 0)
        for key, field in (
            ("arguments", "argument_size_in_bytes"),
            ("outputs", "output_size_in_bytes"),
            ("aliased", "alias_size_in_bytes"),
            ("temporaries", "temp_size_in_bytes"),
            ("code", "generated_code_size_in_bytes"),
        )
    }
    peak = int(getattr(analysis, "peak_memory_in_bytes", 0) or 0)
    memory["peak_from"] = "compiler" if peak > 0 else "sum"
    memory["peak"] = peak if peak > 0 else (
        memory["arguments"] + memory["outputs"] - memory["aliased"]
        + memory["temporaries"])
    return memory


def memory_text(memory, limit=0):
    """``compiled_memory`` on one line: ``arguments 6.21 GB (aliased
    6.21), temporaries 8.93 GB, outputs 6.21 GB, code 0.04 GB, peak
    15.18 GB of 16.90`` (the last where a device's limit is known)."""
    return (
        "arguments %.2f GB (aliased %.2f), temporaries %.2f GB, outputs "
        "%.2f GB, code %.2f GB, peak %.2f GB%s" % (
            memory["arguments"] / 1e9, memory["aliased"] / 1e9,
            memory["temporaries"] / 1e9, memory["outputs"] / 1e9,
            memory["code"] / 1e9, memory["peak"] / 1e9,
            " of %.2f" % (limit / 1e9) if limit else "")
    )


PEAK_GROUPS_MAX = 12
# how far back a buffer without an ``op_name`` looks for its operand's
_NAME_HOPS = 4
# opcodes whose result is their operand's buffer: no bytes of their
# own, and a use of the result is a use of the operand
_VIEW_OPCODES = frozenset({
    "bitcast", "get-tuple-element", "tuple", "optimization-barrier",
})
# opcodes that run a computation of their own on the schedule
_BODY_OPCODES = frozenset({"while", "conditional", "call"})
_ENTRY_RE = re.compile(r"^ENTRY [^\n]*\{\n", re.MULTILINE)
_INSTRUCTION_RE = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = ")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_SHAPE_RE = re.compile(r"\b([a-z]+\d*\w*)\[([\d,]*)\](\{[^}]*\})?")
_LAYOUT_RE = re.compile(r"\{([\d,]*)(?::T\(([\d,]+)\))?[^}]*?(?:S\((\d+)\))?\}")
_PARAMETER_ALIAS_RE = re.compile(r"\{(\d*)\}: \((\d+), ")
_OPERAND_ALIAS_RE = re.compile(r'\{"indices":\[([^\]]*)\]\}')
_BODY_RE = re.compile(
    r"\b(?:body|to_apply|true_computation|false_computation)=%([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")


@functools.lru_cache(maxsize=4096)
def _array_bytes(dtype, dims, layout):
    """Bytes one array of a scheduled program's text holds in HBM: its
    dimensions padded to the layout's first tile; 0 for an array whose
    layout names another memory space (``S(n)``, n > 0) and for a
    token."""
    if dtype in ("token", "opaque"):
        return 0
    dims = [int(d) for d in dims.split(",") if d]
    if layout:
        found = _LAYOUT_RE.match(layout)
        if found is not None:
            order, tile, space = found.groups()
            if space and int(space) > 0:
                return 0
            if tile and order:
                # the tile's last number pads the minor-most dimension
                order = [int(d) for d in order.split(",")]
                tile = [int(t) for t in tile.split(",")]
                for dim, size in zip(order, reversed(tile)):
                    dims[dim] = -(-dims[dim] // size) * size
    return _hlo_array_bytes(dtype, dims)


def _shape_bytes(text):
    return sum(_array_bytes(*array) for array in _SHAPE_RE.findall(text))


def _split_result(rest):
    """(result shape, what follows it) of an instruction's text after
    ``= ``; a tuple's shape is its balanced parentheses."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, tail
    depth = 0
    for at, char in enumerate(rest):
        if char == "(":
            depth += 1
        elif char == ")":
            depth -= 1
            if depth == 0:
                return rest[:at + 1], rest[at + 2:]
    return rest, ""


def _result_elements(shape):
    """HBM bytes of a result's elements: one entry an array, a tuple's
    top-level elements one each (a nested tuple summed)."""
    if not shape.startswith("("):
        return [_shape_bytes(shape)]
    elements, depth, start = [], 0, 1
    for at, char in enumerate(shape):
        if char in "({[":
            depth += 1
        elif char in ")}]":
            depth -= 1
        if (char == "," and depth == 1) or (char == ")" and depth == 0):
            elements.append(_shape_bytes(shape[start:at]))
            start = at + 1
    return elements


def _argument_scope(op_name):
    """The root of an argument's path: ``state.params`` of
    ``state.params['wte']['embedding']``, ``batch`` of
    ``batch['features']``."""
    return re.split(r"[\[\\]", op_name, maxsplit=1)[0] or "unscoped"


def _op_name(record, by_name):
    """A record's ``op_name``; a copy the compiler put in (out of fast
    memory, into another layout) has none and takes its operand's, up
    to ``_NAME_HOPS`` copies back. None without one."""
    for _ in range(_NAME_HOPS):
        name = _OP_NAME_RE.search(record["attributes"])
        if name is not None:
            return name.group(1)
        if not record["operands"] or not (
                record["opcode"] == "copy"
                or record["opcode"].endswith("-start")):
            return None
        record = by_name.get(record["operands"][0])
        if record is None:
            return None
    return None


def _parse_computation(text, start):
    """The instructions of the computation whose first line ends at
    ``start``: dicts in schedule order."""
    end = text.find("\n}", start)
    records = []
    for line in text[start:end if end >= 0 else len(text)].split("\n"):
        head = _INSTRUCTION_RE.match(line)
        if head is None:
            continue
        shape, tail = _split_result(line[head.end():])
        opcode, _, tail = tail.partition("(")
        operands, _, attributes = tail.partition(")")
        records.append({
            "name": head.group(2), "root": bool(head.group(1)),
            "shape": shape, "opcode": opcode,
            "operands": _OPERAND_RE.findall(operands),
            "attributes": attributes,
        })
    return records


def _walk(records, aliased_outputs=()):
    """Buffer lifetimes over one computation's schedule. Returns
    ``(peak bytes, position, live)``: ``live`` the buffers alive at the
    peak as ``(record index, bytes)``. ``aliased_outputs``: the root's
    operand indices whose buffer is a donated parameter's."""
    # a value is a list of elements, an element a tuple of buffer ids;
    # a buffer is [bytes, defining record, last use]
    buffers = []
    values = {}
    done_of = {
        r["operands"][0]: r for r in records
        if r["opcode"].endswith("-done") and r["operands"]
    }

    def new(nbytes, index):
        buffers.append([nbytes, index, index])
        return (len(buffers) - 1,)

    def flat(name):
        return tuple(b for element in values.get(name, ()) for b in element)

    for index, record in enumerate(records):
        opcode, operands = record["opcode"], record["operands"]
        for name in operands:
            for b in flat(name):
                buffers[b][2] = index
        if opcode == "get-tuple-element":
            found = re.search(r"index=(\d+)", record["attributes"])
            source = values.get(operands[0], ()) if operands else ()
            at = int(found.group(1)) if found else 0
            value = [source[at]] if at < len(source) else [()]
        elif opcode == "tuple":
            value = [flat(name) for name in operands]
        elif (opcode in _VIEW_OPCODES or opcode == "while"
              or opcode.endswith("-update")):
            # a loop's result is its operand's buffers, updated in place
            value = list(values.get(operands[0], ())) if operands else []
        elif opcode.endswith("-done"):
            start = values.get(operands[0], [()]) if operands else [()]
            value = [(b,) for b in start[0]] or [()]
        elif opcode.endswith("-start"):
            # born here: what the ``-done`` will return. The rest of a
            # start's tuple repeats its operands or is context, and the
            # operands live until the ``-done``
            done = done_of.get(record["name"])
            born = tuple(
                b for nbytes in _result_elements(
                    (done or record)["shape"])
                for b in new(nbytes, index))
            value = [born, tuple(b for n in operands for b in flat(n))]
        else:
            sizes = _result_elements(record["shape"])
            value = [None] * len(sizes)
            if "aliasing_operands" in record["attributes"]:
                # a fusion that writes into an operand's buffer: the
                # indices count the operands, then the results
                for group in _OPERAND_ALIAS_RE.findall(
                        record["attributes"]):
                    indices = [int(i) for i in re.findall(r"\d+", group)]
                    held = tuple(
                        b for i in indices if i < len(operands)
                        for b in flat(operands[i]))
                    for i in indices:
                        out = i - len(operands)
                        if 0 <= out < len(sizes) and held:
                            value[out] = held
            value = [
                new(nbytes, index) if element is None else element
                for element, nbytes in zip(value, sizes)
            ]
        values[record["name"]] = value
        if record["root"]:
            # what the computation returns lives to the end; an output
            # in a donated argument's buffer is that argument, counted
            # as a parameter already
            kept = (
                [flat(name) for name in operands]
                if opcode == "tuple" else [flat(record["name"])])
            for out, element in enumerate(kept):
                for b in element:
                    buffers[b][2] = len(records)
                    if (out in aliased_outputs and records[
                            buffers[b][1]]["opcode"] != "parameter"):
                        buffers[b][0] = 0
    for buffer in buffers:
        if records[buffer[1]]["opcode"] == "parameter":
            buffer[2] = len(records)
    # sweep: bytes born at a position less bytes that died before it
    delta = [0] * (len(records) + 2)
    for nbytes, born, last in buffers:
        delta[born] += nbytes
        delta[last + 1] -= nbytes
    peak, position, live_now = 0, 0, 0
    for index in range(len(records)):
        live_now += delta[index]
        if live_now > peak:
            peak, position = live_now, index
    live = [
        (born, nbytes) for nbytes, born, last in buffers
        if nbytes and born <= position <= last
    ]
    return peak, position, live


def peak_live(hlo_text, compiler_peak=0):
    """What a compiled program holds in HBM at its fullest point, by
    the program's own scopes: one pass over the ENTRY computation of a
    scheduled module's text, which lists one instruction a line in
    execution order (None for a module that is not
    ``is_scheduled=true``).

    A buffer is born at its instruction with the bytes of its result
    (``_array_bytes``; a tuple's elements apart) and dies after its
    last use; parameters and what the root returns live throughout,
    and an output that shares a donated parameter's buffer
    (``input_output_alias``) is counted once. Views
    (``_VIEW_OPCODES``) and a ``-done`` add nothing and pass a use on
    to their operand; an asynchronous pair's buffer is born at its
    ``-start``; a fusion that writes into an operand's buffer
    (``aliasing_operands``) and a ``while`` (its state is updated in
    place) add nothing. What a ``while``, ``conditional`` or ``call``
    holds INSIDE its body is not counted: ``bodies_not_counted`` says
    how many sit on the schedule and the largest body's own peak
    beside its parameters by the same pass.

    Returns ``walk_peak`` (bytes), ``position`` of ``instructions`` and
    the ``instruction`` / ``op_name`` there, ``walk_over_compiler``
    (against ``compiler_peak``, None without one) and at most
    ``PEAK_GROUPS_MAX`` ``groups`` in order of bytes: ``{scope,
    direction, bytes, buffers}`` with ``op_scope``'s scope and
    direction, a parameter under the root of its argument's path with
    direction ``argument``, the groups past the largest summed as
    ``other`` and buffers without an ``op_name`` as ``unnamed``."""
    end = hlo_text.find("\n")
    header = hlo_text if end < 0 else hlo_text[:end]
    if "is_scheduled=true" not in header:
        return None
    entry = _ENTRY_RE.search(hlo_text)
    if entry is None:
        return None
    records = _parse_computation(hlo_text, entry.end())
    if not records:
        return None
    donated = set()
    aliases = header.find("input_output_alias={")
    if aliases >= 0:
        donated = {
            int(out or 0) for out, _ in _PARAMETER_ALIAS_RE.findall(
                header[aliases:])
        }
    peak, position, live = _walk(records, donated)
    by_name = {record["name"]: record for record in records}
    groups = {}
    for index, nbytes in live:
        record = records[index]
        name = _op_name(record, by_name)
        if name is None:
            key = ("unnamed", "")
        elif record["opcode"] == "parameter":
            key = (_argument_scope(name), "argument")
        else:
            key = op_scope(name)
        entry = groups.setdefault(key, [0, 0])
        entry[0] += nbytes
        entry[1] += 1
    unnamed = groups.pop(("unnamed", ""), None)
    ranked = sorted(groups.items(), key=lambda kv: -kv[1][0])
    room = PEAK_GROUPS_MAX - (unnamed is not None)
    if len(ranked) > room:
        rest = ranked[room - 1:]
        ranked = ranked[:room - 1] + [(("other", ""), [
            sum(e[0] for _, e in rest), sum(e[1] for _, e in rest)])]
    if unnamed is not None:
        ranked.append((("unnamed", ""), unnamed))
    bodies = [r for r in records if r["opcode"] in _BODY_OPCODES]
    at = records[position]
    return {
        "walk_peak": peak,
        "position": position,
        "instructions": len(records),
        "instruction": at["name"],
        "op_name": _op_name(at, by_name),
        "walk_over_compiler": (
            round(peak / compiler_peak, 4) if compiler_peak else None),
        "groups": [
            {"scope": scope, "direction": direction, "bytes": nbytes,
             "buffers": count}
            for (scope, direction), (nbytes, count) in ranked
        ],
        "bodies_not_counted": {
            "instructions": len(bodies),
            "largest_body_peak": _largest_body_peak(hlo_text, bodies),
        },
    }


def _largest_body_peak(hlo_text, bodies):
    """The largest peak, beside its parameters, of the computations
    that ``bodies`` (``while`` / ``conditional`` / ``call``
    instructions) run, one level down; None without any."""
    names = set()
    for record in bodies:
        for single, several in _BODY_RE.findall(record["attributes"]):
            names.update(_OPERAND_RE.findall(several) or [single])
    names.discard("")
    if not names:
        return None
    largest = 0
    for name in names:
        # the computation's own first line: ``%name (parameters) -> ... {``
        at = hlo_text.find("\n%%%s (" % name)
        body = hlo_text.find("{\n", at) if at >= 0 else -1
        if body < 0:
            continue
        records = _parse_computation(hlo_text, body + 2)
        peak, position, live = _walk(records)
        largest = max(largest, sum(
            nbytes for index, nbytes in live
            if records[index]["opcode"] != "parameter"))
    return largest


# ``scope_mix``: the rows an ``xla_compile`` event carries at most (a
# row is under 800 bytes: the event stays under 256 KB a compile); the
# mixed fusions past them, the smallest by the bytes they hold outside
# their root's family, are counted in ``dropped``
SCOPE_MIX_MAX = 300
# interior opcodes listed beside a row's bytes: where a fusion's time
# is, when it is not in moving its bytes
_HEAVY_OPCODES = frozenset({
    "dot", "convolution", "reduce", "gather", "scatter"})
# interior instructions that are no work of any family
_NO_WORK_OPCODES = _VIEW_OPCODES | {"parameter", "constant"}
_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
_CALLS_RE = re.compile(r"\bcalls=%([\w.\-]+)")


def scope_mix(hlo_text):
    """The fusions of a compiled program that hold the work of MORE
    THAN ONE family (``scopes.family`` of an interior instruction's
    ``op_name``), in the one pass over the text that the train step's
    compile already pays for. A device trace charges a fusion's whole
    time to its own ``op_name``, which is its root's: these rows say
    how far that can be trusted, operation by operation
    (``benchmark/lib/step_account.py`` joins them to the trace by
    instruction name).

    Every ``fusion`` instruction of every computation counts (ENTRY, a
    ``while`` / ``call`` / ``conditional`` body). Of its fused
    computation, instructions without an ``op_name`` (the compiler's
    own) and parameters, constants and views say nothing; an
    instruction whose ``op_name`` has no registered scope is of the
    family ``unnamed``. Returns ``fusions`` (all), ``mixed``, ``rows``:
    at most ``SCOPE_MIX_MAX`` of ``{"op": instruction, "root": family
    of the fusion's own op_name, "bytes": {family: result bytes of the
    interior instructions of that family}, "heavy": {opcode:
    [families]} for dot / convolution / reduce / gather / scatter
    interiors}``, largest first by the bytes outside the root's
    family, and ``dropped`` ``{"rows", "bytes"}``: the mixed fusions
    past the cap and those bytes of theirs."""
    families = functools.lru_cache(maxsize=None)(
        lambda op_name: scopes.family(op_name)[0])
    inside = {}   # computation -> ({family: bytes}, {opcode: {family}})
    fusions = []  # (instruction, computation called, own op_name)
    current = None
    for line in hlo_text.split("\n"):
        head = _INSTRUCTION_RE.match(line)
        if head is None:
            started = _COMPUTATION_RE.match(line)
            if started is not None and line.endswith("{"):
                current = inside.setdefault(started.group(1), ({}, {}))
            continue
        shape, tail = _split_result(line[head.end():])
        opcode = tail.partition("(")[0]
        # (``find`` first: a line's ``backend_config`` is long)
        at = tail.find('op_name="')
        name = _OP_NAME_RE.match(tail, at) if at >= 0 else None
        if opcode == "fusion":
            called = _CALLS_RE.search(tail)
            if called is not None:
                fusions.append((
                    head.group(2), called.group(1),
                    name.group(1) if name else None))
        if name is None or opcode in _NO_WORK_OPCODES or current is None:
            continue
        family = families(name.group(1))
        nbytes, heavy = current
        nbytes[family] = nbytes.get(family, 0) + _shape_bytes(shape)
        if opcode in _HEAVY_OPCODES:
            heavy.setdefault(opcode, set()).add(family)
    rows = []
    for instruction, called, op_name in fusions:
        nbytes, heavy = inside.get(called, ({}, {}))
        if len(nbytes) < 2:
            continue
        root = families(op_name) if op_name else scopes.UNNAMED
        rows.append((
            sum(n for family, n in nbytes.items() if family != root),
            {"op": instruction, "root": root, "bytes": dict(nbytes),
             "heavy": {
                 opcode: sorted(found)
                 for opcode, found in sorted(heavy.items())}}))
    rows.sort(key=lambda row: -row[0])
    return {
        "fusions": len(fusions),
        "mixed": len(rows),
        "rows": [row for _, row in rows[:SCOPE_MIX_MAX]],
        "dropped": {
            "rows": max(0, len(rows) - SCOPE_MIX_MAX),
            "bytes": sum(foreign for foreign, _ in rows[SCOPE_MIX_MAX:]),
        },
    }


def peak_live_text(live, shown=4):
    """``peak_live`` on the memory log line: ``live at the peak
    (<op_name>): <scope> 2.10 GB x12, ...`` for the largest groups."""
    return "live at the peak (%s): %s" % (
        live["op_name"] or live["instruction"],
        ", ".join(
            "%s%s %.2f GB x%d" % (
                group["scope"],
                " " + group["direction"]
                if group["direction"] in ("backward", "recompute") else "",
                group["bytes"] / 1e9, group["buffers"])
            for group in live["groups"][:shown]
        ),
    )


# ---------------------------------------------------------------------------
# the compile split: what jax says of its own stages and of its cache

def install_listeners():
    """Registers the ``jax.monitoring`` listeners, once a process and
    only where device obs is on. ``instrumented_jit`` calls it, and
    the start-up record does before the backend starts, so that the
    totals hold the programs of start-up too."""
    global _listeners_installed
    if _listeners_installed or not device_obs_enabled():
        return
    with _lock:
        if _listeners_installed:
            return
        _listeners_installed = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_stage_start)
    monitoring.register_event_time_span_listener(_on_stage_span)
    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_duration_secs_listener(_on_cache_seconds)


def set_phase_source(source):
    """``source()`` names the start-up phase open on the calling
    thread; an ``xla_cache_miss`` carries it. None once start-up is
    over."""
    global _phase_source
    _phase_source = source


def _count_call():
    with _lock:
        _stage_totals["listener_calls"] += 1


def _on_stage_start(event, value, **kwargs):
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    _count_call()
    tls = _stage_tls
    tls.depth = getattr(tls, "depth", 0) + 1
    if stage == "backend":
        # the cache's answer belongs to this program alone (a compile
        # that raised never reported its span)
        for fact in ("cache", "retrieval", "saved"):
            tls.__dict__.pop(fact, None)


def _on_cache_event(event, **kwargs):
    answer = _CACHE_ANSWER.get(event)
    if answer is not None:
        _count_call()
        _stage_tls.cache = answer


def _on_cache_seconds(event, duration, **kwargs):
    fact = _CACHE_SECONDS.get(event)
    if fact is not None:
        _count_call()
        setattr(_stage_tls, fact, duration)


def _on_stage_span(event, start, end, **kwargs):
    """One stage ended on this thread. Only an outermost stage's
    seconds count (jax traces a nested ``jit`` inside its caller's
    trace, and an eager op run while tracing compiles inside it); a
    backend stage is one program whatever encloses it."""
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    tls = _stage_tls
    depth = tls.depth = max(0, getattr(tls, "depth", 1) - 1)
    seconds = end - start
    span = {"stage": stage, "start": start, "end": end}
    if stage == "backend":
        facts = tls.__dict__
        span["cache"] = facts.pop("cache", "off")
        retrieval = facts.pop("retrieval", 0.0)
        saved = facts.pop("saved", 0.0)
        if span["cache"] == "hit":
            span["retrieval_s"] = retrieval
            span["saved_s"] = saved
    with _lock:
        totals = _stage_totals
        totals["listener_calls"] += 1
        if depth == 0:
            totals[stage + "_s"] += seconds
        if stage == "backend":
            totals["requests"] += 1
            if span["cache"] == "hit":
                totals["hits"] += 1
                totals["retrieval_s"] += span["retrieval_s"]
            elif span["cache"] == "miss":
                totals["misses"] += 1
    if depth == 0:
        spans = getattr(tls, "spans", None)
        if spans is None:
            spans = tls.spans = collections.deque(maxlen=_SPANS_KEPT)
        spans.append(span)
    if stage != "backend":
        return
    if span["cache"] == "hit":
        _m_persistent_hits.inc()
    elif span["cache"] == "miss":
        # a warm start that compiles is the finding, and the
        # program's name is the lead
        _m_persistent_misses.inc()
        source = _phase_source
        try:
            events.emit(
                "xla_cache_miss", module=kwargs.get("fun_name"),
                backend_s=round(seconds, 4),
                phase=source() if source is not None else None,
            )
        except Exception as e:
            # jax calls this inside its compile: the journal's trouble
            # must not become the program's
            logger.debug("xla_cache_miss not journaled: %s", e)


def _call_stages(t0, elapsed):
    """The ``stages`` of a wrapped call that compiled: the outermost
    spans that STARTED on the calling thread inside the call,
    ``[t0, t0 + elapsed]`` on the epoch clock. What compiled before
    the call (an eager op) or after it (the cost fetch's relower) is
    not its, and another thread's compile is in another thread's
    list. ``first_run_s`` is the rest of the call: dispatch and what
    the runtime does before it returns. The result is not awaited, so
    it is not the first execution."""
    spans = getattr(_stage_tls, "spans", None) or ()
    mine = [s for s in spans if t0 <= s["start"] <= t0 + elapsed]
    if spans:
        spans.clear()
    stages = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0}
    caches = set()
    for span in mine:
        stages[span["stage"] + "_s"] += span["end"] - span["start"]
        if span["stage"] == "backend":
            caches.add(span["cache"])
            for key in ("retrieval_s", "saved_s"):
                if key in span:
                    stages[key] = stages.get(key, 0.0) + span[key]
    stages["first_run_s"] = elapsed - sum(
        stages[stage + "_s"] for stage in ("trace", "lower", "backend"))
    stages = {key: round(value, 4) for key, value in stages.items()}
    # several programs in one call: a miss among them is the call's
    stages["cache"] = next(
        (c for c in ("miss", "hit") if c in caches), "off")
    stages["spans"] = [
        {"stage": s["stage"], "start": round(s["start"], 6),
         "end": round(s["end"], 6)}
        for s in mine
    ]
    return stages


def stages_text(stages):
    """``stages`` on the compile log line: ``trace 1.20s lower 0.81s
    backend 2.10s (cache hit, retrieval 0.31s, saved 41.20s) first run
    0.42s``."""
    cache = "cache " + stages["cache"]
    if stages["cache"] == "hit":
        cache += ", retrieval %.2fs, saved %.2fs" % (
            stages.get("retrieval_s", 0.0), stages.get("saved_s", 0.0))
    return "trace %.2fs lower %.2fs backend %.2fs (%s) first run %.2fs" % (
        stages["trace_s"], stages["lower_s"], stages["backend_s"], cache,
        stages["first_run_s"])


def compile_totals():
    """The process totals of the compile split (a copy); None where
    the listeners are not installed: nothing was observed, which is
    not the same as nothing compiled."""
    if not _listeners_installed:
        return None
    with _lock:
        return dict(_stage_totals)


def _leaf_spec(leaf):
    """``f32[32,10]``-style spec for one argument leaf; scalars and
    static oddities render as their type name (they still churn the
    cache when they change, so they belong in the provenance)."""
    dtype = getattr(leaf, "dtype", None)
    shape = getattr(leaf, "shape", None)
    if dtype is not None and shape is not None:
        try:
            import jax

            short = jax.dtypes.canonicalize_dtype(dtype).name
        except Exception as e:
            logger.debug("dtype canonicalize failed for %r: %s", dtype, e)
            short = str(dtype)
        return "%s[%s]" % (short, ",".join(str(d) for d in shape))
    return type(leaf).__name__


def _signature(args, kwargs):
    """{leaf path: spec} of a call's arguments, plus the total bytes of
    HOST-resident (numpy) leaves — the h2d payload this signature
    uploads per call."""
    import jax
    import numpy as np

    sig = {}
    host_bytes = 0
    leaves = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        sig[key] = _leaf_spec(leaf)
        if isinstance(leaf, np.ndarray):
            host_bytes += leaf.nbytes
    return sig, host_bytes


def _diff_signatures(old, new):
    """Provenance of a recompile: which leaves changed spec, appeared,
    or vanished relative to the previous compiled signature."""
    changed = []
    for key in sorted(set(old) | set(new)):
        before = old.get(key)
        after = new.get(key)
        if before != after:
            changed.append(
                "%s: %s -> %s" % (key, before or "absent", after or "gone")
            )
    return changed


class _InstrumentedJit:
    """One ``jax.jit`` product plus its sentinel books.

    Per call the steady-state cost is one clock read, the jit call
    itself, one C++ ``_cache_size()`` probe, and two integer adds —
    the 2 % overhead contract in scripts/bench_device_obs_overhead.py
    rides on that list staying exactly this short. Signature
    flattening, provenance diffs, the stages jax reported, trace
    emission, and the AOT cost fetch all happen only on calls that
    compiled.
    """

    def __init__(self, fn, name, jit_kwargs):
        import jax

        self._jitted = jax.jit(fn, **jit_kwargs)
        self.name = name
        self.compiles = 0
        self.cache_hits = 0
        self.compile_secs = 0.0
        self.last_compile_secs = 0.0
        self.cost_flops = 0.0
        self.cost_bytes = 0.0
        # _call_stages() of the last compile
        self.stages = None
        # collective_stats() of the last-compiled signature; None until
        # a cost fetch has read a program
        self.collectives = None
        # pallas_kernels() of the same program
        self.kernels = {}
        # compiled_memory(), peak_live() and scope_mix() of the same
        # program
        self.memory = None
        self.peak_live = None
        self.scope_mix = None
        self._cost_fetches = 0
        self._cost_on = env_bool(COST_ANALYSIS_ENV, True)
        self._cache_size = 0
        self._last_sig = None
        self._sig_host_bytes = 0
        self.last_changed = []
        self._m_compiles = _m_compiles.labels(fn=name)
        self._m_recompiles = _m_recompiles.labels(fn=name)
        with _lock:
            _wrappers.append(weakref.ref(self))

    @property
    def recompiles(self):
        return max(0, self.compiles - 1)

    def __call__(self, *args, **kwargs):
        t0 = time.time()
        out = self._jitted(*args, **kwargs)
        # private jax API (works on the pinned 0.9.0): the one probe
        # that tells a compile from a cache hit at C++ cost
        size = self._jitted._cache_size()
        if size == self._cache_size:
            self.cache_hits += 1
            if self._sig_host_bytes:
                with _lock:
                    _totals["h2d_bytes"] += self._sig_host_bytes
        else:
            self._cache_size = size
            self._on_compile(time.time() - t0, t0, args, kwargs)
        return out

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def __getattr__(self, item):
        # AOT/introspection passthrough (eval_shape, clear_cache, ...)
        return getattr(self._jitted, item)

    # -- compile path (rare by contract) -------------------------------

    def _on_compile(self, elapsed, t0, args, kwargs):
        self.compiles += 1
        self.compile_secs += elapsed
        self.last_compile_secs = elapsed
        recompile = self.compiles > 1
        sig, host_bytes = _signature(args, kwargs)
        self._sig_host_bytes = host_bytes
        changed = (
            _diff_signatures(self._last_sig, sig) if recompile else []
        )
        self._last_sig = sig
        self.last_changed = changed
        stages = self.stages = _call_stages(t0, elapsed)
        self._m_compiles.inc()
        _m_compile_secs.observe(elapsed)
        with _lock:
            _totals["compiles"] += 1
            _totals["compile_secs"] += elapsed
            _totals["h2d_bytes"] += host_bytes
            if recompile:
                _totals["recompiles"] += 1
        trace.complete(
            "compile", t0, fn=self.name, seconds=round(elapsed, 4),
            recompile=recompile,
            changed=changed[:_PROVENANCE_CHANGED_MAX],
        )
        if recompile:
            self._m_recompiles.inc()
            logger.warning(
                "xla recompile #%d of %s (%.2fs): %s",
                self.recompiles, self.name, elapsed,
                "; ".join(changed[:_PROVENANCE_CHANGED_MAX]) or
                "signature unchanged at leaf level",
            )
            events.emit(
                "xla_recompile",
                fn=self.name,
                compiles=self.compiles,
                seconds=round(elapsed, 4),
                changed=changed[:_PROVENANCE_CHANGED_MAX],
                signature=sorted(
                    "%s=%s" % kv for kv in sig.items()
                )[:_PROVENANCE_SIG_MAX],
            )
        fetch_secs = 0.0
        if self._cost_on and self._cost_fetches < _COST_FETCH_CAP:
            t1 = time.time()
            self._fetch_cost(args, kwargs)
            fetch_secs = time.time() - t1
        # both figures on one line: whether the cost fetch's relower is
        # a compile-cache hit or a second cold compile reads off it
        logger.info(
            "xla compile #%d of %s: call %.2fs, cost fetch %.2fs; "
            "stages %s%s%s",
            self.compiles, self.name, elapsed, fetch_secs,
            stages_text(stages),
            "" if self.collectives is None
            else "; collectives " + collectives_text(self.collectives),
            "" if not self.kernels
            else "; kernels " + ", ".join(
                "%s x%d" % item for item in self.kernels.items()),
        )
        if self.memory is not None:
            # a line of its own: the compile line is read by the
            # benchmark's log parser and by pinned tests
            logger.info(
                "xla memory of %s: %s%s", self.name,
                memory_text(self.memory, _hbm_limit),
                "" if self.peak_live is None
                else "; " + peak_live_text(self.peak_live),
            )
        events.emit(
            "xla_compile",
            fn=self.name,
            compiles=self.compiles,
            seconds=round(elapsed, 4),
            cost_fetch_seconds=round(fetch_secs, 4),
            stages=stages,
            collectives=self.collectives,
            kernels=self.kernels,
            memory=self.memory,
            peak_live=self.peak_live,
            scope_mix=self.scope_mix,
        )

    def _fetch_cost(self, args, kwargs):
        """Executable-reported FLOPs/bytes for the signature that just
        compiled. ``lower().compile()`` after the real call re-traces
        and is handed the executable the call compiled (module
        docstring), and never touches the jit call cache;
        donated-and-consumed arguments are fine (lowering reads only
        avals). Unavailable backends simply leave the table fallback
        in charge."""
        self._cost_fetches += 1
        try:
            compiled = self._jitted.lower(*args, **kwargs).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            self.cost_flops = float(cost.get("flops", 0.0) or 0.0)
            self.cost_bytes = float(
                cost.get("bytes accessed", 0.0) or 0.0
            )
            self.memory = compiled_memory(compiled)
            hlo_text = compiled.as_text()
            self.collectives = collective_stats(hlo_text)
            self.kernels = pallas_kernels(hlo_text)
            self.peak_live = peak_live(
                hlo_text, self.memory["peak"] if self.memory else 0)
            self.scope_mix = scope_mix(hlo_text)
        except Exception as e:
            logger.debug("cost analysis unavailable for %s: %s",
                         self.name, e)


def collectives_text(stats):
    """``collective_stats`` on one line: ``412.3 MB: all-gather x98
    310.0 MB, ... (largest all-gather bf16[50304,2048] 206.0 MB)``."""
    if not stats["largest"]:
        return "none"
    return "%.1f MB: %s (largest %s %s %.1f MB)" % (
        stats["bytes"] / 1e6,
        ", ".join(
            "%s x%d %.1f MB" % (kind, entry["count"], entry["bytes"] / 1e6)
            for kind, entry in stats["by_kind"].items() if entry["count"]
        ),
        stats["largest"]["kind"], stats["largest"]["result"],
        stats["largest"]["bytes"] / 1e6,
    )


def instrumented_jit(fn, name=None, **jit_kwargs):
    """``jax.jit`` with the recompile sentinel attached — the ONLY
    sanctioned jit entry point in train/ops/serve scopes (edlint rule
    ``obs-bare-jit``). With ``EDL_DEVICE_OBS=0`` this *is* ``jax.jit``:
    the raw PjitFunction comes back untouched."""
    if not device_obs_enabled():
        import jax

        return jax.jit(fn, **jit_kwargs)
    install_listeners()
    return _InstrumentedJit(
        fn, name or getattr(fn, "__name__", "step_fn"), jit_kwargs
    )


# ---------------------------------------------------------------------------
# host<->device transfer attribution

def record_transfer(direction, nbytes):
    """Fold ``nbytes`` of attributed transfer into the counters
    (direction ``"h2d"`` or ``"d2h"``)."""
    if not device_obs_enabled() or nbytes <= 0:
        return
    _m_transfer_bytes.labels(direction=direction).inc(nbytes)
    with _lock:
        _totals["%s_bytes" % direction] += int(nbytes)


@contextlib.contextmanager
def transfer_span(direction, nbytes=0):
    """Time a host-blocking transfer (the ``np.asarray`` fetch of row
    grads, an eval-output device_get) as a ``transfer`` span — the span
    name scripts/critical_path.py maps to its ``transfer`` segment —
    and count its bytes. Inert when device obs is off."""
    if not device_obs_enabled():
        yield
        return
    t0 = time.time()
    try:
        yield
    finally:
        record_transfer(direction, nbytes)
        trace.complete(
            "transfer", t0, direction=direction, bytes=int(nbytes)
        )


# ---------------------------------------------------------------------------
# device-memory accounting

def _allocator_devices():
    """Each local device's allocator counters, in bytes: ``id``,
    ``in_use``, ``reserved`` (what the loaded programs reserve for
    their temporaries), ``peak_in_use``, ``peak_reserved``, ``limit``
    and ``largest_free_block`` where the allocator reports one; empty
    where the backend has no allocator (the CPU)."""
    import jax

    devices = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if stats.get("bytes_in_use") is None:
            continue
        entry = {"id": int(dev.id)}
        for key, stat in (
            ("in_use", "bytes_in_use"),
            ("reserved", "bytes_reserved"),
            ("peak_in_use", "peak_bytes_in_use"),
            ("peak_reserved", "peak_bytes_reserved"),
            ("limit", "bytes_limit"),
        ):
            entry[key] = int(stats.get(stat) or 0)
        if stats.get("largest_free_block_bytes") is not None:
            entry["largest_free_block"] = int(
                stats["largest_free_block_bytes"])
        devices.append(entry)
    return devices


def _device_peak(entry):
    # on the TPU runtime ``peak_bytes_in_use`` counts buffers (state,
    # batch) and a loaded program's temporaries are counted apart as
    # reserved (PR 22, on the chip; benchmark/lib/window.py)
    return entry["peak_in_use"] + entry["peak_reserved"]


def memory_snapshot():
    """Allocator view of this process's device memory, JSON-ready.

    ``source`` is ``"allocator"`` where ``device.memory_stats()``
    exists (TPU/GPU): ``devices`` then holds every local device apart
    (``_allocator_devices``) and the summary is ONE device's, the
    ``fullest`` (its index in ``devices``: the largest peak, then the
    most in use now): ``bytes_in_use`` its buffers plus what its
    loaded programs reserve, ``peak_bytes`` its peak of both,
    ``limit_bytes`` its limit. Nothing is summed over devices: a mesh
    runs out of memory on one of them. On backends without an
    allocator (CPU CI) ``source`` is ``"live_arrays"``: the in-use
    number is the sum of live jax array nbytes and the peak is a
    host-side watermark across polls. ``limit`` comes from the
    allocator, or ``EDL_HBM_LIMIT_BYTES`` when it reports none."""
    global _hbm_peak, _hbm_limit
    if not device_obs_enabled():
        return {}
    import jax

    devices = []
    try:
        devices = _allocator_devices()
    except Exception as e:
        # degrade to the live-array fallback below; a backend without
        # allocator stats is the expected CPU case, not a fault
        logger.debug("allocator memory_stats unavailable: %s", e)
    in_use = peak = limit = arrays = 0
    fullest = None
    try:
        live = jax.live_arrays()
        arrays = len(live)
        if not devices:
            in_use = sum(getattr(a, "nbytes", 0) for a in live)
    except Exception as e:
        logger.debug("live_arrays unavailable: %s", e)
    if devices:
        fullest = max(
            range(len(devices)), key=lambda i: (
                _device_peak(devices[i]),
                devices[i]["in_use"] + devices[i]["reserved"]))
        entry = devices[fullest]
        in_use = entry["in_use"] + entry["reserved"]
        peak = _device_peak(entry)
        limit = entry["limit"]
    else:
        with _lock:
            _hbm_peak = peak = max(_hbm_peak, in_use)
    if limit <= 0:
        limit = env_int(HBM_LIMIT_ENV, 0)
    _hbm_limit = limit
    _m_hbm_in_use.set(in_use)
    _m_hbm_peak.set(peak)
    _m_live_buffers.set(arrays)
    return {
        "bytes_in_use": int(in_use),
        "peak_bytes": int(peak),
        "limit_bytes": int(limit),
        "live_buffers": int(arrays),
        "source": "allocator" if devices else "live_arrays",
        "devices": devices,
        "fullest": fullest,
    }


def journal_memory(at):
    """One ``device_memory`` journal event from a fresh snapshot: the
    WORKER's, at the three points its loop thread passes once a
    process (``state_init``, ``first_step``, ``teardown``). Never a
    master's: asking jax for its devices would open the chip inside
    the master's process."""
    snapshot = memory_snapshot()
    if snapshot:
        events.emit("device_memory", at=at, **snapshot)


# ---------------------------------------------------------------------------
# aggregation (telemetry-RPC rate, never per step)

def _live_wrappers():
    with _lock:
        refs = list(_wrappers)
    alive = []
    dead = False
    for ref in refs:
        wrapper = ref()
        if wrapper is None:
            dead = True
        else:
            alive.append(wrapper)
    if dead:
        with _lock:
            _wrappers[:] = [r for r in _wrappers if r() is not None]
    return alive


def compile_stats():
    """Per-wrapper sentinel books: {name: {...}} for live wrappers.
    Same-named wrappers (the SPMD per-structure jit caches) fold;
    ``stages`` is the split of a name's last compile (the process
    totals over all programs are ``compile_totals``)."""
    stats = {}
    for wrapper in _live_wrappers():
        entry = stats.setdefault(wrapper.name, {
            "compiles": 0, "recompiles": 0, "cache_hits": 0,
            "compile_secs": 0.0, "last_compile_secs": 0.0,
            "cost_flops": 0.0, "cost_bytes": 0.0, "last_changed": [],
            "stages": None,
        })
        entry["compiles"] += wrapper.compiles
        entry["recompiles"] += wrapper.recompiles
        entry["cache_hits"] += wrapper.cache_hits
        entry["compile_secs"] = round(
            entry["compile_secs"] + wrapper.compile_secs, 4
        )
        entry["last_compile_secs"] = max(
            entry["last_compile_secs"],
            round(wrapper.last_compile_secs, 4),
        )
        entry["cost_flops"] += wrapper.cost_flops
        entry["cost_bytes"] += wrapper.cost_bytes
        if wrapper.last_changed:
            entry["last_changed"] = wrapper.last_changed[
                :_PROVENANCE_CHANGED_MAX
            ]
        if wrapper.stages is not None:
            entry["stages"] = wrapper.stages
    return stats


def compile_count():
    """Compiles this process's wrapped functions have made so far: what
    the phase ledger asks after every step, so that a step which
    carried a compile is not judged slow."""
    return _totals["compiles"]


def telemetry():
    """The device section of a role's TelemetryBlob: cumulative
    process-lifetime compile/transfer totals + a fresh memory
    snapshot. Called on the RPC path (telemetry provider), never per
    step; empty dict when device obs is off."""
    if not device_obs_enabled():
        return {}
    with _lock:
        totals = dict(_totals)
    mem = memory_snapshot()
    return {
        "xla_compiles": int(totals["compiles"]),
        "xla_recompiles": int(totals["recompiles"]),
        "xla_compile_secs_total": round(totals["compile_secs"], 4),
        "hbm_bytes_in_use": mem.get("bytes_in_use", 0),
        "hbm_peak_bytes": mem.get("peak_bytes", 0),
        "hbm_limit_bytes": mem.get("limit_bytes", 0),
        "device_live_buffers": mem.get("live_buffers", 0),
        "h2d_bytes": int(totals["h2d_bytes"]),
        "d2h_bytes": int(totals["d2h_bytes"]),
    }


def reset_for_tests():
    """Test isolation only: drop wrapper registry and totals."""
    global _hbm_peak, _hbm_limit
    with _lock:
        _wrappers[:] = []
        for key in _totals:
            _totals[key] = 0.0 if key == "compile_secs" else 0
        for key, value in _stage_totals.items():
            _stage_totals[key] = type(value)()
        _hbm_peak = _hbm_limit = 0
    _stage_tls.__dict__.clear()
