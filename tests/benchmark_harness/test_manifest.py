"""``BENCHMARK.json`` against the contract's limits, and every file it
names: present, loadable, and consistent with the manifest."""

import ast
import os
import re

import pytest

from tests.benchmark_harness import _common as common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj\w*)_size$|_dim$|_rank$"
    r"|head_size|expansion|experts_per_tok")
MANIFESTS = [common.MANIFEST, common.PRESET]


@pytest.fixture(scope="module", params=MANIFESTS,
                ids=["BENCHMARK.json", "preset"])
def manifest_path(request):
    return request.param


@pytest.fixture(scope="module")
def manifest(manifest_path):
    return common.load(manifest_path)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert os.path.getsize(common.MANIFEST) <= 64 * 1024
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line(word) for word in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for path in manifest["paths"]:
        assert PATH.match(path) and not path.startswith("/")
        assert ".." not in path.split("/")
        assert os.path.isdir(os.path.join(common.REPO, path))
    # the command names no file outside ``paths``
    for word in manifest["command"][1:]:
        if "/" in word:
            assert any(
                word.startswith(p + "/") for p in manifest["paths"])
            assert os.path.exists(os.path.join(common.REPO, word))
    seconds = manifest["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    # a full check at the full 24 cells fits the driver's 43,200 s
    runs = 2 + 14 * 24
    assert runs * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(manifest, manifest_path):
    from benchmark.run import Files

    found = Files(manifest_path)
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    files = [c["file"] for c in configs]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for config in configs:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"]) and config["name"] in used
        assert line(config["source"]) and line(config["why"])
        assert any(
            config["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(config["reduced"]) <= 16
        body = common.load(os.path.join(common.REPO, config["file"]))
        # the manifest and the file agree on what was cut, and no width
        # is ever cut
        assert body["reduced"] == config["reduced"]
        for key in config["reduced"]:
            assert NAME.match(key)
            assert not WIDTH.search(key), key
        # the count a configuration names is a file under the paths
        if body.get("flops"):
            assert callable(found.module("flops", body["flops"]).per_sample)
        for key in ("zoo", "reference", "check"):
            if "/" in body.get(key, ""):
                path = os.path.join(common.REPO, body[key])
                with open(path) as f:
                    ast.parse(f.read())
        # a check names its reference, and the reference stands alone
        assert ("check" in body) == ("reference" in body)
        if "reference" in body:
            with open(os.path.join(common.REPO, body["reference"])) as f:
                assert "elasticdl_tpu" not in {
                    node.module.split(".")[0]
                    for node in ast.walk(ast.parse(f.read()))
                    if isinstance(node, ast.ImportFrom) and node.module}


def test_no_cell_of_the_benchmark_may_name_a_platform():
    """``platform`` in a configuration's file is how a rehearsal trains
    on the CPU. No configuration of the root manifest carries it, and
    the harness refuses one that would (only another ``--manifest`` may
    name a platform)."""
    from benchmark import run as bench_run
    from benchmark.lib.procs import HarnessFailure

    manifest = common.load(common.MANIFEST)
    for config in manifest["configs"]:
        body = common.load(os.path.join(common.REPO, config["file"]))
        assert "platform" not in body, config["name"]
        bench_run.check_platform_key(body, common.MANIFEST, config["name"])
    with pytest.raises(HarnessFailure, match="No result is reported"):
        bench_run.check_platform_key(
            {"platform": "cpu"}, common.MANIFEST, "c")
    # the rehearsals' manifest is another file, so they may
    bench_run.check_platform_key({"platform": "cpu"}, common.PRESET, "c")


def test_published_widths_are_the_source_s():
    published = {
        "hidden_size": 2048, "intermediate_size": 8192,
        "num_attention_heads": 8, "vocab_size": 50304,
        "max_position_embeddings": 2048, "rotary_pct": 0.25,
        "use_parallel_residual": True, "tie_word_embeddings": False,
    }
    manifest = common.load(common.MANIFEST)
    depths = {}
    for config in manifest["configs"]:
        body = common.load(os.path.join(common.REPO, config["file"]))
        for key, value in published.items():
            assert body[key] == value, (config["name"], key)
        depths[config["name"]] = body["num_hidden_layers"]
        assert body["source"] == config["source"]
        assert len(body["departs"]) >= 3
    assert depths["pythia-1b"] == 16
    assert depths["pythia-1b-1chip"] < 16


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    names = [c["name"] for c in cells]
    assert len(set(names)) == len(names)
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for cell in cells:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["config"] in configs
        assert cell["chips"] in (1, 4)
        assert line(cell["why"])
    four = sum(1 for c in cells if c["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_metrics(manifest):
    end_to_end, per_layer = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(end_to_end) <= 16 and 1 <= len(per_layer) <= 128
    names = [m["name"] for m in end_to_end + per_layer]
    assert len(set(names)) == len(names)
    cells = {c["name"] for c in manifest["workloads"]}
    e2e_names = {m["name"] for m in end_to_end}
    assert "setup_s" in e2e_names
    for metric in end_to_end:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in per_layer:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in SOURCES
        assert line(metric["layer"])
        assert metric["moves"] in e2e_names
    for metric in end_to_end + per_layer:
        assert NAME.match(metric["name"])
        assert UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert set(metric.get("workloads", [])) <= cells
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric that moves a metric the cell reports
    for cell in cells:
        mine = {m["name"] for m in end_to_end
                if cell in m.get("workloads", [cell])}
        assert "setup_s" in mine and len(mine) >= 2
        layers = [m for m in per_layer
                  if cell in m.get("workloads", [cell])]
        assert layers and all(m["moves"] in mine for m in layers)


def test_every_named_file_exists_and_loads(manifest, manifest_path):
    from benchmark.run import Files

    files = Files(manifest_path)
    for cell in manifest["workloads"]:
        body = common.load(files.find("workloads", cell["name"] + ".json"))
        assert {"log_every", "steps_per_task", "warmup_steps"} <= set(body)
        assert body["warmup_steps"] % body["log_every"] == 0
        traffic = common.load(
            files.find("traffic", cell["traffic"] + ".json"))
        assert traffic["minibatch"] % cell["chips"] == 0
        assert traffic["records"] % (
            traffic["minibatch"] * body["steps_per_task"]) == 0
        generator = files.module("traffic", traffic["generator"])
        assert callable(generator.generate) and callable(generator.sample)
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        reader = files.module("metrics", metric["name"])
        assert callable(reader.read)
        assert reader.__doc__ and metric["name"] in reader.__doc__


def test_file_names_under_paths_use_the_allowed_characters():
    manifest = common.load(common.MANIFEST)
    for path in manifest["paths"]:
        for base, dirs, names in os.walk(os.path.join(common.REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in names:
                rel = os.path.relpath(os.path.join(base, name), common.REPO)
                assert PATH.match(rel), rel
