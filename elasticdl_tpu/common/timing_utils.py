"""The worker's phase ledger: every host second of the loop thread,
named inside the program.

Reference parity: common/timing_utils.py:17-48 -- ``Timing``
accumulates seconds per named phase. Here it is the one clock of the
worker loop (ISSUE 23). It always counts, on ``perf_counter_ns``, and
never waits for the device:

- ``phase(name)`` times a block. It adds the block's SELF nanoseconds
  (its own time less the phases nested in it) to the open record,
  enters ``jax.profiler.TraceAnnotation("edl/<name>")`` -- which the
  runtime ignores unless a profiler session is live, and which then
  puts the phase on the device trace's own clock -- and, when
  ``EDL_TRACE_DIR`` is set, opens ``trace.span("edl/<name>")`` as a
  child of the step's ``train_batch`` root span.
- ``step(number)`` is one loop iteration, the root of its phases:
  ``StepTraceAnnotation("edl/step", step_num=number)`` and the
  ``train_batch`` root span. When it closes, ``other`` is the
  iteration's wall time less the phases, the iteration feeds the
  ``batch_process`` series of ``edl_phase_seconds`` and
  ``edl_step_time_seconds``, a step far above the running median is
  journaled and logged as ``slow_step`` at once, and every
  ``interval`` steps one ``loop_phases`` event leaves the process.
  The loop reads a step's device values one step late, after it has
  dispatched the next (``worker/worker.py``): ``read_ahead`` counts
  the steps read so and ``drained`` those it had to read with nothing
  queued behind them, by reason; both leave with ``loop_phases``.
- ``begin_startup`` / ``begin_teardown`` open the two records that
  cover the process outside the loop (``worker_startup``,
  ``worker_teardown``; the master's are ``master_startup`` and
  ``master_teardown``, filled through ``end_record`` alone); the
  loop's first iteration lands in the start-up record under start-up
  names (``first_task``, ``first_step``). Both carry ``start_ts``,
  their start on the epoch clock of the journal's ``ts``. The worker's
  two are also where the device's memory is journaled
  (``device_memory`` at ``first_step`` and at ``teardown``). While the
  start-up record is open, and only then, a phase that closes also
  takes what jax traced, lowered, compiled or loaded from its cache
  since the last one did (``device_obs.compile_totals``): the
  record's ``compiles``.

The trainers reach the loop thread's ledger through ``current()``.
``end_record_sync`` is what is left of the old blocking clock: the
sparse trainers keep it (it waits for the device only while metrics
are collected, as before) until a cell measures them.
"""

import collections
import os
import resource
import statistics
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory
from elasticdl_tpu.observability import device as device_obs
from elasticdl_tpu.observability import events, trace
from elasticdl_tpu.observability import metrics as obs_metrics

logger = _logger_factory("elasticdl_tpu.common.timing_utils")

# the series that carries "the step" for the step-time gauge and the
# derived rates: one loop iteration's wall time
STEP_PHASE = "batch_process"

# a step is slow when its wall time exceeds SLOW_FACTOR times the
# median of the last SLOW_WINDOW steps and exceeds it by SLOW_MIN_NS
# (Timing._judge says what a step's wall time is under the late read)
SLOW_FACTOR = 1.5
SLOW_MIN_NS = 20_000_000
SLOW_WINDOW = 64
# fewer earlier steps than this give no median worth judging by
SLOW_MIN_SAMPLES = 8
# steps a ``loop_phases`` event covers where the caller names none
DEFAULT_INTERVAL = 100

# the worker's two records outside the loop. At the close of the first
# and the start of the second the device's memory is journaled
# (``device_memory``); never at a master's, whose process must not ask
# jax for its devices: that would open the chip inside it
WORKER_STARTUP = "worker_startup"
WORKER_TEARDOWN = "worker_teardown"

# while the start-up record is open the loop's first iteration goes by
# the names start-up has for it
_STARTUP_NAMES = {"input_wait": "first_task", "dispatch": "first_step"}

# what a start-up phase's ``compiles`` entry holds, of the process
# totals of the compile split
_COMPILE_KEYS = ("requests", "hits", "misses", "trace_s", "lower_s",
                 "backend_s")

_tls = threading.local()


def current():
    """The calling thread's ledger: the one its loop bound, else a
    fresh one (a trainer driven outside any worker loop)."""
    ledger = getattr(_tls, "ledger", None)
    if ledger is None:
        ledger = _tls.ledger = Timing()
    return ledger


def bind(ledger):
    """Makes ``ledger`` the calling thread's; returns the one it
    replaces (None if there was none), for the caller to put back."""
    previous = getattr(_tls, "ledger", None)
    _tls.ledger = ledger
    return previous


def process_age_ns():
    """Nanoseconds since the operating system started this process
    (``/proc/self/stat`` field 22 against ``/proc/uptime``, 10 ms
    ticks), or None where ``/proc`` does not say."""
    try:
        with open("/proc/self/stat") as f:
            # the command name may hold spaces: fields after its ")"
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return max(0, int((uptime - started) * 1e9))


def start_ledger(module_start_ns, main_start_ns, interval=0,
                 event=WORKER_STARTUP):
    """A role's ledger with its start-up record (``event``) opened at
    the process's start and holding ``imports``: from there to the
    first statement of the role's ``main`` (``main_start_ns``).
    ``module_start_ns`` is the first statement the role's module ran:
    where ``imports`` starts if the operating system cannot say when
    the process did."""
    ledger = Timing(interval=interval)
    age_ns = process_age_ns()
    now_ns = time.perf_counter_ns()
    start_ns = module_start_ns if age_ns is None else now_ns - age_ns
    # the operating system counts in 10 ms ticks: never after main began
    start_ns = min(start_ns, main_start_ns)
    ledger.begin_startup(start_ns, event)
    ledger.end_record("imports", start_ns, end=main_start_ns)
    return ledger


def _faults():
    """(involuntary context switches, major page faults) of the
    process so far."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return usage.ru_nivcsw, usage.ru_majflt


class _Phase:
    __slots__ = ("_ledger", "_name", "_annotation", "_span", "_early",
                 "_start", "child_ns")

    def __init__(self, ledger, name):
        self._ledger = ledger
        self._name = name

    def __enter__(self):
        ledger = self._ledger
        label = "edl/" + self._name
        self._annotation = TraceAnnotation(label)
        self._span = self._early = None
        step = ledger._open_step
        if step is not None and step.traced:
            if step.root_open:
                self._span = trace.span(label)
                self._span.__enter__()
            else:
                # before the iteration's trace is open (the wait for
                # its batch): kept, and written when the root opens
                self._early = time.time()
        self.child_ns = 0
        ledger._stack().append(self)
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        elapsed = time.perf_counter_ns() - self._start
        ledger = self._ledger
        stack = ledger._stack()
        stack.pop()
        if self._span is not None:
            self._span.__exit__(*exc)
        elif self._early is not None:
            ledger._open_step.early_spans.append(
                ("edl/" + self._name, self._early, time.time())
            )
        self._annotation.__exit__(*exc)
        if stack:
            stack[-1].child_ns += elapsed
        ledger._add(self._name, elapsed - self.child_ns, elapsed)
        return False


class _Step:
    __slots__ = ("_ledger", "number", "task_id", "_span_args",
                 "_annotation", "_contexts", "_start", "_cancelled",
                 "traced", "root_open", "early_spans", "_wall_start")

    def __init__(self, ledger, number, span_args):
        self._ledger = ledger
        self.number = number
        self.task_id = None
        self._span_args = span_args
        self._cancelled = False

    def cancel(self):
        """The iteration trained nothing (the stream ended, a poll
        came back empty): it leaves no record and no trace."""
        self._cancelled = True

    def has_batch(self, task_id=None):
        """The iteration has its batch, of task ``task_id``, and will
        train: ``slow_step`` names the task, and with EDL_TRACE_DIR set
        the iteration's ``train_batch`` root span opens here, dated
        from the iteration's start, with the phases that came before
        it as its first children. (Opened any earlier, every empty poll
        of an idle loop would write a trace.)"""
        self.task_id = task_id
        if not self.traced or self.root_open:
            return
        self._contexts = (
            trace.root_span(
                "train_batch", start=self._wall_start, role="worker",
                task_id=task_id, **self._span_args
            ),
            trace.task_context(task_id),
        )
        for context in self._contexts:
            context.__enter__()
        self.root_open = True
        for label, start, end in self.early_spans:
            trace.complete(label, start, end=end)

    def __enter__(self):
        ledger = self._ledger
        self._annotation = StepTraceAnnotation(
            "edl/step", step_num=self.number
        )
        self._contexts = ()
        self.traced = trace.enabled()
        self.root_open = False
        if self.traced:
            self.early_spans = []
            self._wall_start = time.time()
        ledger._open_step = self
        if ledger._startup is None:
            ledger._record = {}
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter_ns() - self._start
        ledger = self._ledger
        ledger._open_step = None
        for context in reversed(self._contexts):
            context.__exit__(*exc)
        self._annotation.__exit__(*exc)
        record, ledger._record = ledger._record, ledger._startup
        if self._cancelled or exc[0] is not None:
            return False
        if ledger._startup is not None:
            # the first iteration belongs to start-up, compile and all
            ledger.end_startup()
        else:
            ledger._close_step(self, wall, record)
        return False


class Timing:
    """One thread's ledger; see the module's notes."""

    def __init__(self, interval=0, compile_count=None):
        self._interval = int(interval) or DEFAULT_INTERVAL
        self._compile_count = compile_count or device_obs.compile_count
        self._totals = {}
        self._counts = {}
        # phase -> seconds of its most recent block; consumers derive
        # rates (examples a second, the dense share) without a second
        # clock. ``batch_process`` is the last whole iteration
        self.last_seconds = {}
        # open phases, innermost last; one stack a thread, because a
        # sparse trainer's ledger is also written by its push thread
        self._local = threading.local()
        self._open_step = None
        # the record phases add to: a step's, start-up's, teardown's
        self._record = None
        self._startup = None
        self._startup_start = 0
        # (journal event, epoch seconds of its start) of the open
        # start-up and teardown records
        self._startup_event = self._teardown_event = None
        # start-up only: {phase: compile split} and the totals at the
        # last phase's close
        self._compiles = {}
        self._compile_mark = None
        self._teardown_start = None
        self._walls = collections.deque(maxlen=SLOW_WINDOW)
        self._compiles_seen = self._compile_count()
        self._exempt = 0
        self._run = None  # the steps since the loop last read the device
        self._interval_open = None  # the loop_phases being summed
        # since the last loop_phases: steps read with a later one
        # already dispatched, and steps read with none, by reason
        self._ahead = 0
        self._drains = {}
        self._last_number = 0
        self._faults = _faults()
        self._metrics_on = obs_metrics.metrics_enabled()
        self._phase_series = {}
        if self._metrics_on:
            self._phase_hist = obs_metrics.histogram(
                "edl_phase_seconds",
                "Wall-clock per training-loop phase (the phase ledger)",
                ("phase",),
            )
            self._step_gauge = obs_metrics.gauge(
                "edl_step_time_seconds",
                "Wall time of the most recent loop iteration",
            )

    # -- phases --------------------------------------------------------

    def phase(self, name):
        """Context manager timing one named block of the loop thread."""
        return _Phase(self, name)

    timeit = phase

    def _stack(self):
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def step(self, number, **span_args):
        """Context manager around one loop iteration, the phases'
        root; ``span_args`` go to the ``train_batch`` root span."""
        return _Step(self, number, span_args)

    def _add(self, name, self_ns, elapsed_ns):
        record = self._record
        if record is not None:
            if record is self._startup:
                name = _STARTUP_NAMES.get(name, name)
                self._take_compiles(name)
            record[name] = record.get(name, 0) + self_ns
        self._count(name, self_ns)
        self._observe(name, elapsed_ns / 1e9)

    def _count(self, name, ns):
        self._totals[name] = self._totals.get(name, 0) + ns
        self._counts[name] = self._counts.get(name, 0) + 1

    def _observe(self, name, seconds):
        self.last_seconds[name] = seconds
        if not self._metrics_on:
            return
        series = self._phase_series.get(name)
        if series is None:
            series = self._phase_series[name] = self._phase_hist.labels(
                name
            )
        series.observe(seconds)
        if name == STEP_PHASE:
            self._step_gauge.set(seconds)

    # -- the old start/end surface (sparse trainers, local tools) ------

    def start(self):
        return time.perf_counter_ns()

    def end_record(self, phase, start, end=None):
        elapsed = (time.perf_counter_ns() if end is None else end) - start
        self._add(phase, elapsed, elapsed)

    def end_record_sync(self, phase, start, result=None):
        """``end_record`` after waiting for ``result`` on the device,
        but only while metrics are collected: the sparse trainers'
        device phase, kept as it was until a cell measures them. The
        worker loop never calls this."""
        if self._metrics_on and result is not None:
            import jax

            jax.block_until_ready(result)
        self.end_record(phase, start)

    # -- the late read -------------------------------------------------

    def read_ahead(self):
        """The loop is about to read a step's device values with a
        later step already dispatched: the device has work queued
        while the host does its turn."""
        self._ahead += 1

    def drained(self, reason):
        """The loop is about to read the step in flight with nothing
        queued behind it, because of ``reason`` (a checkpoint, the end
        of the batches, ...): the device idles through the host's
        turn, once."""
        self._drains[reason] = self._drains.get(reason, 0) + 1

    # -- a step closes -------------------------------------------------

    def _close_step(self, step, wall, record):
        other = wall - sum(record.values())
        record["other"] = other
        self._count("other", other)
        self._observe(STEP_PHASE, wall / 1e9)
        self._judge(step, wall, record)
        span = self._interval_open
        if span is None:
            span = self._interval_open = {
                "first_step": step.number, "steps": 0, "wall_ns": 0,
                "phases": {}, "slowest_step": step.number,
                "slowest_wall_ns": 0,
            }
        span["last_step"] = self._last_number = step.number
        span["steps"] += 1
        span["wall_ns"] += wall
        phases = span["phases"]
        for name, ns in record.items():
            phases[name] = phases.get(name, 0) + ns
        if wall > span["slowest_wall_ns"]:
            span["slowest_wall_ns"] = wall
            span["slowest_step"] = step.number
        if step.number % self._interval == 0:
            self._emit_interval()

    def _judge(self, step, wall, record):
        """Is this step slow? The loop reads a step one step late:
        iteration N dispatches step N and then waits for step N - 1
        (``device_wait``), so where every step is read (``JaxTrainer``'s
        health scalars) an iteration's wall time is still one device
        step, the one before its own, and a run is one step. Where
        nothing is read until a step is logged (``SpmdTrainer``) the
        loop runs further ahead and pays for several steps in the
        iteration that reads, so steps are judged in runs that end at
        a ``device_wait``: a run's wall time against its number of
        steps times the median step."""
        run = self._run
        if run is None:
            run = self._run = {"steps": 0, "wall_ns": 0, "phases": {},
                               "judged": True}
        run["steps"] += 1
        run["wall_ns"] += wall
        phases = run["phases"]
        for name, ns in record.items():
            phases[name] = phases.get(name, 0) + ns
        compiles = self._compile_count()
        if compiles != self._compiles_seen:
            # this step carried a compile: neither its run nor the
            # next step's is judged, and neither moves the median
            self._compiles_seen = compiles
            self._exempt = 2
        if self._exempt:
            self._exempt -= 1
            run["judged"] = False
        if "device_wait" not in record:
            return
        self._run = None
        if not run["judged"]:
            return
        steps, wall = run["steps"], run["wall_ns"]
        if len(self._walls) >= SLOW_MIN_SAMPLES:
            median = statistics.median(self._walls)
            if (wall > SLOW_FACTOR * median * steps
                    and wall - median * steps > SLOW_MIN_NS):
                self._slow_step(step, steps, wall, median, phases)
        self._walls.append(wall / steps)

    def _slow_step(self, step, steps, wall, median, record):
        switches, faults = _faults()
        fields = {
            "step": step.number, "task": step.task_id, "steps": steps,
            "wall_ns": wall, "median_ns": int(median),
            "phases": dict(record),
            "invol_ctx_switches": switches - self._faults[0],
            "major_faults": faults - self._faults[1],
        }
        events.emit("slow_step", **fields)
        logger.warning(
            "slow_step number=%d task=%s steps=%d wall_ms=%.3f "
            "median_ms=%.3f invol_ctx_switches=%d major_faults=%d "
            "phases_ms=%s",
            step.number, step.task_id, steps, wall / 1e6, median / 1e6,
            fields["invol_ctx_switches"], fields["major_faults"],
            {name: round(ns / 1e6, 3) for name, ns in sorted(
                record.items(), key=lambda kv: -kv[1])},
        )

    def _emit_interval(self):
        """One ``loop_phases`` event for the steps since the last; the
        process's context switches and page faults are read here, once
        an interval."""
        span, self._interval_open = self._interval_open, None
        if span is None:
            if not self._drains:
                return
            # a drain after the interval's last step closed (the end
            # of the batches): an event of no steps carries it
            span = {
                "first_step": self._last_number, "steps": 0, "wall_ns": 0,
                "phases": {"other": 0}, "slowest_step": self._last_number,
                "slowest_wall_ns": 0, "last_step": self._last_number,
            }
        span["ahead_steps"], self._ahead = self._ahead, 0
        span["drains"], self._drains = self._drains, {}
        switches, faults = _faults()
        span["invol_ctx_switches"] = switches - self._faults[0]
        span["major_faults"] = faults - self._faults[1]
        self._faults = (switches, faults)
        events.emit("loop_phases", **span)

    # -- outside the loop ----------------------------------------------

    def begin_startup(self, start_ns, event=WORKER_STARTUP):
        """Opens the start-up record, journaled as ``event`` and
        back-dated to ``start_ns`` on the ``perf_counter_ns`` clock
        (the process's start)."""
        self._startup = self._record = {}
        self._startup_start = start_ns
        # one reading of each clock puts the record on the journal's
        self._startup_event = (
            event,
            time.time() - (time.perf_counter_ns() - start_ns) / 1e9,
        )
        self._compiles = {}
        self._compile_mark = device_obs.compile_totals()
        device_obs.set_phase_source(self._open_phase)

    def _open_phase(self):
        """The innermost phase open on the calling thread, by its
        start-up name; None between phases."""
        stack = self._stack()
        if not stack:
            return None
        name = stack[-1]._name
        return _STARTUP_NAMES.get(name, name)

    def _take_compiles(self, name):
        """Charges ``name`` with what the process totals of the
        compile split gained since a start-up phase last closed (an
        inner phase closes, and takes, before the one around it)."""
        totals = device_obs.compile_totals()
        if totals is None:
            # no listener in this process: nothing was observed
            return
        mark = self._compile_mark or dict.fromkeys(_COMPILE_KEYS, 0)
        self._compile_mark = totals
        gained = {key: totals[key] - mark[key] for key in _COMPILE_KEYS}
        if not any(gained.values()):
            return
        entry = self._compiles.setdefault(name, dict.fromkeys(
            _COMPILE_KEYS, 0))
        for key, value in gained.items():
            entry[key] += value

    def end_startup(self):
        """Closes the start-up record after the first step returned:
        one ``worker_startup`` event (or what ``begin_startup``
        named), every phase in it and ``other`` for the rest of the
        wall time, and under ``compiles`` what jax compiled or loaded
        in each."""
        record, self._startup = self._startup, None
        if record is None:
            return
        if self._record is record:
            self._record = None
        wall = time.perf_counter_ns() - self._startup_start
        record["other"] = wall - sum(record.values())
        self._take_compiles("other")
        device_obs.set_phase_source(None)
        self._compiles_seen = self._compile_count()
        event, start_ts = self._startup_event
        observed = {}
        if self._compile_mark is not None:
            # only where the compile split's listeners are installed:
            # an absent field says nothing was observed, an empty one
            # that nothing compiled. ``listener_calls`` is what they
            # were called so far: the instrument's cost is this many
            # short Python calls
            observed = {
                "compiles": {
                    name: {key: round(value, 4)
                           for key, value in entry.items()}
                    for name, entry in self._compiles.items()
                },
                "listener_calls": self._compile_mark["listener_calls"],
            }
        events.emit(event, start_ts=start_ts, wall_ns=wall, phases=record,
                    **observed)
        if event == WORKER_STARTUP:
            device_obs.journal_memory("first_step")
        logger.info(
            "%s %.3fs: %s; compiles %s (%s listener calls)",
            event.replace("_startup", " start-up"), wall / 1e9,
            {name: round(ns / 1e9, 3) for name, ns in record.items()},
            observed.get("compiles", "not observed"),
            observed.get("listener_calls", "no"),
        )

    def begin_teardown(self, event=WORKER_TEARDOWN):
        """Opens the teardown record, journaled as ``event``
        (idempotent); a start-up that never saw a step is closed
        first."""
        if self._teardown_start is not None:
            return
        self.end_startup()
        if event == WORKER_TEARDOWN:
            # before the teardown's clock starts: the process's peaks
            device_obs.journal_memory("teardown")
        self._teardown_start = time.perf_counter_ns()
        self._teardown_event = (event, time.time())
        self._record = {}

    def end_teardown(self):
        """One ``worker_teardown`` event (or what ``begin_teardown``
        named), just before the process exits."""
        if self._teardown_start is None:
            return
        record, self._record = self._record, None
        wall = time.perf_counter_ns() - self._teardown_start
        self._teardown_start = None
        record["other"] = wall - sum(record.values())
        event, start_ts = self._teardown_event
        events.emit(event, start_ts=start_ts, wall_ns=wall, phases=record)

    # -- totals --------------------------------------------------------

    def summary(self):
        return {
            phase: {
                "seconds": round(self._totals[phase] / 1e9, 6),
                "count": self._counts[phase],
            }
            for phase in sorted(self._totals)
        }

    def report(self, context=""):
        """The totals since the last report as one INFO line, and the
        steps of an unfinished interval as a last ``loop_phases``; the
        totals then start again. Called when a training stream ends."""
        self._emit_interval()
        if not self._totals:
            return
        logger.info(
            "phase ledger%s, seconds/blocks: %s",
            " (%s)" % context if context else "",
            " ".join(
                "%s=%.6f/%d" % (name, ns / 1e9, self._counts[name])
                for name, ns in sorted(
                    self._totals.items(), key=lambda kv: -kv[1])
            ),
        )
        self._totals.clear()
        self._counts.clear()
