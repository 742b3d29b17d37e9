"""``tests/conftest.py``'s limit on every test, by a ``pytest`` run of
its own: a three-test file in a temporary directory whose ``conftest.py``
takes the hooks from the repo's and patches ``LIMIT`` to a second."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CONFTEST = """
import signal
import sys
import time

sys.path.insert(0, %r)
from tests import conftest as guard

guard.LIMIT = 1.0
pytest_runtest_protocol = guard.pytest_runtest_protocol
pytest_runtest_setup = pytest_runtest_call = guard._phase
pytest_runtest_teardown = guard._phase


def found_on_the_way_in(signum, frame):
    raise AssertionError("the run's own handler: nothing arms it")


signal.signal(signal.SIGALRM, found_on_the_way_in)


def pytest_runtest_logreport(report):
    # the timer rings while pytest reports, between two phases
    if report.when == "call" and "held_between" in report.nodeid:
        time.sleep(1.5)


def pytest_sessionfinish(session):
    print("\\nhandler afterwards: %%s, timer afterwards: %%s" %% (
        signal.getsignal(signal.SIGALRM).__name__,
        signal.getitimer(signal.ITIMER_REAL)[0]))
""" % _REPO

_TESTS = """
import signal
import time


def test_that_waits():
    time.sleep(30)


def test_held_between_its_phases():
    pass


def test_with_an_alarm_of_its_own():
    assert signal.getsignal(signal.SIGALRM).__name__ == "_past_the_limit"
    rang = []
    mine = signal.signal(signal.SIGALRM, lambda *a: rang.append(a))
    signal.setitimer(signal.ITIMER_REAL, 0.05)
    time.sleep(0.5)
    signal.signal(signal.SIGALRM, mine)
    assert len(rang) == 1
"""


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """(exit code, output, seconds) of the run."""
    where = tmp_path_factory.mktemp("limit")
    (where / "conftest.py").write_text(textwrap.dedent(_CONFTEST))
    (where / "test_two.py").write_text(textwrap.dedent(_TESTS))
    start = time.monotonic()
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "test_two.py", "-q", "-rfE",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-p", "no:xdist"],
        cwd=where, capture_output=True, text=True, timeout=120)
    return done.returncode, done.stdout + done.stderr, (
        time.monotonic() - start)


def test_a_test_past_the_limit_fails_alone_and_the_run_goes_on(run):
    """The test that sleeps is one failure, with its node id and the
    seconds in its message, after the limit and not after its sleep; the
    test after it runs and passes."""
    code, text, seconds = run
    assert code == 1, text
    assert "1 failed, 2 passed, 1 error" in text, text
    assert ("test_two.py::test_that_waits: past the limit of 1 s on a test"
            in text), text
    assert seconds < 25, seconds


def test_a_timer_that_rings_between_two_phases_fails_the_next_one(run):
    """While pytest reports a phase nothing catches a failure as the
    test's own (it would end the session): the test's next phase, here
    its teardown, runs to its end and carries it, and the run goes on."""
    _, text, _ = run
    assert ("ERROR at teardown of test_held_between_its_phases _" in text
            and "\ntest_two.py::test_held_between_its_phases: past the "
            "limit of 1 s on a test" in text), text


def test_the_alarm_is_as_it_was_found(run):
    """A test that sets an alarm of its own sees it ring (the last of
    the file's passes); after the run the handler that was there before
    is back and no timer is left armed."""
    _, text, _ = run
    assert "test_with_an_alarm_of_its_own" not in text, text
    assert ("handler afterwards: found_on_the_way_in, timer afterwards: 0"
            in text), text
