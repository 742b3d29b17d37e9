"""Worker SIGTERM hook: route eviction through the graceful drain.

A K8s eviction / spot preemption / autoscaler scale-down all reach the
worker as SIGTERM. Before ISSUE 7 the flight-recorder hook
(observability/events.py install_crash_hooks) dumped the event ring and
exited — losing the in-flight async push, the dirty device-tier rows,
and the current task to timeouts and chaos-recovery machinery. This
hook composes with it instead of replacing it:

- it is installed FIRST (worker/main.py), so when ``install_crash_hooks``
  registers afterwards and captures it as the previous handler, a
  SIGTERM runs the flight recorder's dump/flush and then CHAINS here;
- once ``bind(worker)`` has run, the chain call flips the worker into
  ``begin_drain`` and RETURNS — the process keeps running, the training
  loop finishes the current task, joins pushes, flushes the device
  tier, deregisters, and exits normally (bounded by the worker's
  ``EDL_DRAIN_DEADLINE_SECS`` watchdog);
- before ``bind`` (SIGTERM during startup) it chains whatever was
  installed before it, or exits 0 — the pre-ISSUE-7 graceful-eviction
  contract.
"""

import signal
import sys
import time

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.worker.drain")


class SigtermDrain:
    """Two-phase SIGTERM handler: install early (main thread, before
    the flight-recorder hook), bind the worker once it exists."""

    def __init__(self):
        self._worker = None
        self._previous = None

    def install(self):
        self._previous = signal.getsignal(signal.SIGTERM)
        try:
            signal.signal(signal.SIGTERM, self._on_term)
        except ValueError:
            # not the main thread (embedded use): no drain hook, the
            # liveness/requeue fallback still covers eviction
            logger.warning(
                "not on main thread; SIGTERM drain hook not installed"
            )
        return self

    def bind(self, worker):
        self._worker = worker

    def _on_term(self, signum, frame):
        worker = self._worker
        if worker is not None:
            # flags only — safe at any interrupt point; the run loop
            # does the flushing, the watchdog bounds it. When the
            # signal arrived is only noted here: the loop journals it
            # (``drain_requested``) once the task is finished
            worker.begin_drain("sigterm", signal_ts=time.time())
            return
        if callable(self._previous):
            self._previous(signum, frame)
        else:
            sys.exit(0)


def install_sigterm_drain():
    """Install and return the hook; call BEFORE
    ``events.install_crash_hooks()`` so the flight recorder chains into
    it (dump first, then drain)."""
    return SigtermDrain().install()
