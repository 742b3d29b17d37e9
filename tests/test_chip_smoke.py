"""chip_smoke.py and what ISSUE 21 put under it: the compile-cache
placement, the peaks table, the smoke's refusal
to run without a chip, and a CPU rehearsal of both smoke phases at a
tiny size through the same code the chip run uses."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from elasticdl_tpu.common import platform  # noqa: E402


# ---------------------------------------------------------------------
# compile cache placement


def _cache_dir_in_child(env):
    """(helper's return, jax's configured dir) in a fresh process."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, jax\n"
         "from elasticdl_tpu.common.platform import "
         "configure_compile_cache\n"
         "print(json.dumps([configure_compile_cache(), "
         "jax.config.jax_compilation_cache_dir]))"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
        check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_env_set_sets_nothing_in_code(tmp_path, monkeypatch):
    import jax

    monkeypatch.setenv(platform.COMPILE_CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert platform.configure_compile_cache() == str(tmp_path)
    # this process imported jax before the variable existed, so any
    # change here could only have come from the helper
    assert jax.config.jax_compilation_cache_dir == before
    # and a fresh process finds jax itself reading the variable
    env = dict(os.environ, **{platform.COMPILE_CACHE_ENV: str(tmp_path)})
    assert _cache_dir_in_child(env) == [str(tmp_path), str(tmp_path)]


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    import jax

    monkeypatch.delenv(platform.COMPILE_CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = platform.configure_compile_cache()
        second = platform.configure_compile_cache()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    env = {
        k: v for k, v in os.environ.items()
        if k != platform.COMPILE_CACHE_ENV
    }
    # two more processes, same answer: nothing in it moves
    assert _cache_dir_in_child(env) == [first, first]
    assert _cache_dir_in_child(env) == [first, first]


def test_no_cache_dir_literal_outside_the_helper():
    hits = subprocess.run(
        ["grep", "-rlI", "jax_compilation_cache_dir", "--include=*.py",
         "elasticdl_tpu", "scripts", "tests",
         "chip_smoke.py", "__graft_entry__.py"],
        capture_output=True, text=True, cwd=REPO,
    ).stdout.split()
    assert sorted(hits) == [
        "elasticdl_tpu/common/platform.py",
        "tests/test_chip_smoke.py",
    ]


# ---------------------------------------------------------------------
# peaks table


def test_peak_flops_known_and_unknown_device():
    assert platform.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="cpu"):
        platform.peak_flops("cpu")


# ---------------------------------------------------------------------
# chip_smoke.py


def test_chip_smoke_refuses_to_run_without_a_chip():
    """Under JAX_PLATFORMS=cpu: non-zero, fast, names the missing
    chip, prints no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True,
        text=True, cwd=tmp_path, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert out.returncode != 0
    assert "no elasticdl_tpu package" in out.stderr
    assert '"ok"' not in out.stdout


def _run_smoke_main(monkeypatch, capsys, tmp_path, sparse_problems):
    """main() with the chip and both phases faked: what it prints."""
    import chip_smoke

    probe = {
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "versions": {"jax": "0", "jaxlib": "0", "libtpu": "0"},
    }
    monkeypatch.setattr(chip_smoke, "WORK_DIR", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "probe_device", lambda: probe)
    monkeypatch.setattr(chip_smoke, "build_native_store", lambda: 0.0)
    monkeypatch.setattr(
        chip_smoke, "run_dense_phase",
        lambda *a: ([], {"steps": 32}, {}),
    )
    monkeypatch.setattr(
        chip_smoke, "run_sparse_phase",
        lambda *a: (sparse_problems, {"steps": 40}, {}),
    )
    try:
        chip_smoke.main()
        code = 0
    except SystemExit as e:
        code = e.code
    lines = capsys.readouterr().out.strip().splitlines()
    with open(tmp_path / "work" / "summary.json") as f:
        summary = json.load(f)
    return code, lines, summary


def test_chip_smoke_last_line_is_exactly_the_contract(
    monkeypatch, capsys, tmp_path
):
    """The last stdout line is ``{"ok", "device": {"platform", "kind",
    "count"}}`` and nothing else; versions and per-phase facts ride
    the summary line before it."""
    code, lines, summary = _run_smoke_main(
        monkeypatch, capsys, tmp_path, []
    )
    assert code == 0
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert lines[-2].startswith("chip_smoke: summary: ")
    detail = json.loads(lines[-2].split("summary: ", 1)[1])
    assert detail == summary and detail["ok"] is True
    assert detail["phases"] == {
        "dense": {"steps": 32}, "sparse": {"steps": 40},
    }
    assert "versions" in detail


def test_chip_smoke_failed_phase_exits_nonzero_with_ok_false(
    monkeypatch, capsys, tmp_path
):
    code, lines, summary = _run_smoke_main(
        monkeypatch, capsys, tmp_path, ["tier reported no hits"]
    )
    assert code not in (0, None)
    assert summary["ok"] is False
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


TINY_TRANSFORMER = '''
from elasticdl_tpu.models.transformer import *  # noqa: F401,F403
from elasticdl_tpu.models.transformer import TransformerLM


def custom_model(mesh=None):
    return TransformerLM(
        vocab_size=512, num_layers=1, num_heads=2, embed_dim=32,
        mesh=mesh,
    )
'''


def test_chip_smoke_rehearsal_on_cpu(tmp_path, monkeypatch):
    """All three phases, tiny, worker on the CPU: the same run_*_phase
    code (processes, gRPC, log parsing, checks) the chip run executes.
    A phase is seconds of Python starting (a master, a worker, two PS)
    around a step that compiles in one, and the three share neither a
    directory nor a port nor a program, so they run side by side, each
    with children of its own, and are checked in their order."""
    from concurrent.futures import ThreadPoolExecutor

    import chip_smoke

    zoo = tmp_path / "tiny_transformer.py"
    zoo.write_text(TINY_TRANSFORMER)
    # the children place their compile cache through the environment
    monkeypatch.setenv(
        platform.COMPILE_CACHE_ENV, str(tmp_path / "jax_cache")
    )
    # one device, as on the one-chip machine (conftest gives THIS
    # process eight virtual ones, and children would inherit them)
    monkeypatch.delenv("XLA_FLAGS")

    class FourDevices(chip_smoke.Children):
        """Children of the four-chip host: four virtual devices."""

        def start(self, argv, env, log_path):
            return super().start(argv, dict(
                env, XLA_FLAGS="--xla_force_host_platform_device_count=4"
            ), log_path)

    on_cpu = dict(
        platform="cpu", worker_platforms="cpu", attention="xla",
    )
    # eight steps: the fewest at which check_training compares the
    # first steps' loss with the last's
    dense = dict(
        chip_smoke.DENSE, model_zoo=str(zoo), seq=128, vocab=512,
        minibatch=4, steps_per_task=4, tasks=2,
    )
    sparse = dict(
        chip_smoke.SPARSE, minibatch=64, steps_per_task=4, tasks=2,
    )
    so_mtime = os.path.getmtime(chip_smoke.NATIVE_SO)
    one, ps, four = chip_smoke.Children(), chip_smoke.Children(), FourDevices()
    try:
        with ThreadPoolExecutor(3) as pool:
            phases = [
                pool.submit(chip_smoke.run_dense_phase, one,
                            str(tmp_path / "dense"), dense, on_cpu),
                pool.submit(chip_smoke.run_sparse_phase, ps,
                            str(tmp_path / "sparse"), sparse, on_cpu,
                            so_mtime),
                # the checks do bite: a chip run must not pass on a CPU
                # worker's log
                pool.submit(chip_smoke.run_dense_phase, four,
                            str(tmp_path / "dense4"), dense,
                            chip_smoke.ON_CHIP | dict(worker_platforms="cpu")),
            ]
        problems, report, _ = phases[0].result()
        assert not problems, problems
        assert report["steps"] == 8
        assert report["attention"] == ["xla"]
        assert "train_step" in report["compiles"]
        problems, report, _ = phases[1].result()
        assert not problems, problems
        assert report["steps"] == 8
        assert report["store_backend"] == ["native", "native"]
        assert report["tier_hits"] > 0
        # four devices, as on the four-chip host: the worker picks the
        # SPMD trainer and the model receives its mesh
        problems, report, logs = phases[2].result()
        assert report["steps"] == 8 and report["device_count"] == 4
        assert "spmd_train_step" in report["compiles"]
        assert len(problems) == 2, problems
        assert any("platform" in p for p in problems)
        assert any("attention" in p for p in problems)
        worker_log = chip_smoke.read(logs["worker"])
        assert "SPMD state placement" in worker_log
        assert "split into 4 shards of (1, 128)" in worker_log
    finally:
        for children in (one, ps, four):
            children.stop_all()
