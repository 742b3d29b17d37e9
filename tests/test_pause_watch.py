"""``scripts/pause_watch.py``: the outside watcher PR 29 used to tell a
pause of the whole machine from a stall of the program."""

import importlib.util
import json
import os
import time

import pytest

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts", "pause_watch.py",
)


@pytest.fixture(scope="module")
def pause_watch():
    spec = importlib.util.spec_from_file_location("pause_watch", _PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_sleep_longer_than_the_floor_is_noted(pause_watch, tmp_path):
    """With a floor under the period every sleep counts as a pause: each
    line carries when it ended and how long it lasted."""
    path = tmp_path / "pauses.ndjson"
    t0 = time.time()
    pause_watch.main(
        [str(path), "--period", "0.02", "--floor", "0.01",
         "--seconds", "0.2"])
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert 2 <= len(lines) <= 12
    for line in lines:
        assert set(line) == {"ts", "seconds"}
        assert 0.01 < line["seconds"] < 5.0
        assert t0 <= line["ts"] <= time.time()


def test_an_undisturbed_watcher_writes_nothing(pause_watch, tmp_path):
    path = tmp_path / "pauses.ndjson"
    pause_watch.main(
        [str(path), "--period", "0.005", "--floor", "30", "--seconds", "0.1"])
    assert path.read_text() == ""
