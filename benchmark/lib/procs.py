"""Starting, watching and stopping the job's processes.

Copied from ``chip_smoke.py`` (PR 21) so that a later change to the
smoke cannot move the yardstick. The harness's own process never
imports jax: a chip belongs to one process, and that process is the
worker (then the reference check), never this parent.
"""

import os
import signal
import socket
import subprocess
import sys
import time


class HarnessFailure(Exception):
    """The run cannot report a result; the message says why."""


class Children:
    """Every process the harness starts, so none outlives it."""

    def __init__(self, cwd):
        self._cwd = cwd
        self._procs = []

    def start(self, argv, env, log_path, stdin=None):
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(
                [sys.executable] + argv, cwd=self._cwd, env=env,
                stdin=stdin, stdout=log, stderr=subprocess.STDOUT,
            )
        self._procs.append(proc)
        return proc

    def stop_all(self, grace=15):
        for proc in self._procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in self._procs:
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        self._procs = []


def child_env(root, platforms, **extra):
    env = dict(os.environ, JAX_PLATFORMS=platforms)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def wait_port(port, proc, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc.poll() is not None:
            raise HarnessFailure(
                "process exited %d before serving :%d"
                % (proc.returncode, port)
            )
        try:
            with socket.create_connection(("127.0.0.1", port), 1.0):
                return
        except OSError:
            time.sleep(0.1)
    raise HarnessFailure("nothing served :%d within %ds" % (port, timeout))


def stop(proc, grace):
    """SIGTERM (the role's orderly stop), then SIGKILL after ``grace``
    seconds; returns (exit code, whether it had to be killed)."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    try:
        return proc.wait(timeout=grace), False
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        return proc.returncode, True


def tail(path, lines=40):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError as e:
        return "(%s)" % e
