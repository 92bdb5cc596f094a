"""``BENCHMARK.json`` against its own rules and against the files it names."""

import os
import re

import pytest

from benchmark.harness.loader import BenchmarkFiles

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"hidden|intermediate|latent|state|proj|width|_dim$|_rank$|_size$|head|expan|experts|length|dtype")


@pytest.fixture(scope="module")
def files():
    return BenchmarkFiles(ROOT)


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(files):
    spec = files.spec
    assert set(spec) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert spec["paths"] == ["benchmark"]
    assert all(one_line(word) for word in spec["command"]) and len(spec["command"]) <= 32
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check with 24 cells fits into its 43,200 seconds
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_and_units(files):
    spec = files.spec
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [entry["name"] for entry in spec[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.fullmatch(name) for name in names), group
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for metric in metrics:
        assert UNIT.fullmatch(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher"), metric
        assert metric["source"] in SOURCES, metric
    for workload in spec["workloads"]:
        assert NAME.fullmatch(workload["config"]) and NAME.fullmatch(workload["traffic"])
        assert one_line(workload["why"]), workload["name"]
    for config in spec["configs"]:
        assert one_line(config["source"]) and one_line(config["why"]), config["name"]
        assert len(config["reduced"]) <= 16
        assert all(NAME.fullmatch(key) for key in config["reduced"])


def test_entries_have_just_their_keys(files):
    spec = files.spec
    for config in spec["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
    for workload in spec["workloads"]:
        assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    for metric in spec["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in spec["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert one_line(metric["layer"])
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}


def test_cells(files):
    spec = files.spec
    configs = {c["name"] for c in spec["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in spec["workloads"]} == configs  # every configuration has a cell
    assert all(w["chips"] in (1, 4) for w in spec["workloads"])
    # at most a quarter of the cells (and one always) may take four chips, and
    # only for what exists only across chips: exactly the cells with a mesh layer
    four = [w["name"] for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
    for listed in spec["workloads"]:
        assert ("mesh" in files.workload(listed["name"])["layers"]) == (listed["chips"] == 4)


def test_every_named_file_exists_and_agrees(files):
    spec = files.spec
    for config in spec["configs"]:
        assert config["file"] == f"benchmark/configs/{config['name']}.json"
        body = files.config(config["name"])
        assert body["name"] == config["name"]
        assert sorted(body["reduced"]) == sorted(config["reduced"])
        assert set(body["reduced_why"]) == set(body["reduced"])
        for path in body["reference"].values():  # the configuration's plain reference files
            assert os.path.exists(os.path.join(ROOT, path)), path
        # a rehearsal shrinks the scale (popsize, the reference's sample sizes),
        # never a width: its keys are whole numbers of the configuration's top
        # level, made smaller, and none is named like a width
        for key, value in body["rehearse"].items():
            assert isinstance(body[key], int) and isinstance(value, int) and 0 < value <= body[key]
            assert not WIDTH.search(key), key
    for entry in spec["workloads"]:
        workload = files.workload(entry["name"])  # raises where the two disagree
        assert workload["why"] == entry["why"]
        assert workload["traffic"]["name"] == entry["traffic"]
        for path in workload["traffic"].get("reference", {}).values():
            assert not path.startswith("benchmark/") or os.path.exists(os.path.join(ROOT, path)), path
        # a cell names the layers it exercises by the names the metrics give theirs
        assert set(workload["layers"]) <= {m["layer"] for m in spec["per_layer"]}
        assert hasattr(files.driver(workload["driver"]), "build")
        assert workload["warmup_generations"] >= 3 and workload["traced_generations"] >= 1


def test_layer_metric_files_agree_with_their_entries(files):
    spec = files.spec
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    on_disk = {
        name[: -len(".py")]
        for name in os.listdir(os.path.join(ROOT, "benchmark", "layer_metrics"))
        if name.endswith(".py")
    }
    assert on_disk == {m["name"] for m in spec["per_layer"]}
    for entry in spec["per_layer"]:
        module = files.layer_metric(entry["name"])
        assert (module.LAYER, module.UNIT, module.BETTER, module.SOURCE, module.MOVES) == (
            entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"],
        )
        assert entry["moves"] in end_to_end
        # a metric applies only where the cell's file names its layer; an entry
        # that lists cells lists exactly those where it applies (a reader may
        # narrow its layer's cells to those it finds something to read in),
        # and one that lists none applies in every cell that has the layer
        for listed in spec["workloads"]:
            workload = files.workload(listed["name"])
            applies = module.applies(workload)
            assert not applies or entry["layer"] in workload["layers"], (entry["name"], listed["name"])
            if "workloads" in entry:
                assert applies == (listed["name"] in entry["workloads"]), (entry["name"], listed["name"])
            else:
                assert applies == (entry["layer"] in workload["layers"]), (entry["name"], listed["name"])
        assert set(entry.get("workloads", [])) <= {w["name"] for w in spec["workloads"]}, entry["name"]


def test_every_cell_reports_what_the_contract_asks(files):
    for listed in files.spec["workloads"]:
        end_to_end = [m["name"] for m in files.metrics("end_to_end", listed["name"])]
        assert "setup_s" in end_to_end and len(end_to_end) >= 2
        assert files.metrics("per_layer", listed["name"])


def test_files_under_paths_are_named_from_allowed_characters():
    for directory, _, names in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in directory:
            continue
        for name in names:
            relative = os.path.relpath(os.path.join(directory, name), ROOT)
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", relative), relative
