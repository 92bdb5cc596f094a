"""A later PR adds a cell, a configuration, a driver or a per-layer metric as
new files and new ``BENCHMARK.json`` entries, and edits no file that is there;
of an entry that is there it may only list its new cells, where the entry's
metric reads them.

Shown on a scratch copy of the benchmark with the fixtures of
``data/extension/`` laid over it (see its README.md): a second traffic for
``oo_searcher`` whose ``eval_mode`` the plain rollout does not know by name,
and a driver of another shape (no policy, no environment, four generations per
call, its own reference check, its own metric). The new cells run, the lint of
the extended tree passes, and every file that was there is byte for byte the
same.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
EXTENSION = os.path.join(ROOT, "benchmark", "tests", "data", "extension")
ADDED_KINDS = ("configs", "workloads", "drivers", "layer_metrics", "reference")


def digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def files_under(top):
    return sorted(
        os.path.relpath(os.path.join(directory, name), top)
        for directory, _, names in os.walk(top)
        if "__pycache__" not in directory
        for name in names
    )


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    checkout = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(
        os.path.join(ROOT, "benchmark"),
        os.path.join(checkout, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", "*.xplane.pb"),
    )
    os.symlink(os.path.join(ROOT, "evotorch_tpu"), os.path.join(checkout, "evotorch_tpu"))
    before = {
        path: digest(os.path.join(checkout, "benchmark", path))
        for path in files_under(os.path.join(checkout, "benchmark"))
    }
    # the PR's new files: none may be there already
    for kind in ADDED_KINDS:
        for name in os.listdir(os.path.join(EXTENSION, kind)):
            target = os.path.join(checkout, "benchmark", kind, name)
            assert not os.path.exists(target), target
            shutil.copy(os.path.join(EXTENSION, kind, name), target)
    # the PR's new entries: appended, no entry that is there touched but for
    # its new cells, appended to the cells a metric that lists them reads
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    new_cells = set()
    with open(os.path.join(EXTENSION, "benchmark_entries.json")) as f:
        for group, entries in json.load(f).items():
            assert not {e["name"] for e in entries} & {e["name"] for e in spec[group]}
            spec[group] = spec[group] + entries
            new_cells |= {e["name"] for e in entries} if group == "workloads" else set()
    with open(os.path.join(EXTENSION, "listed_cells.json")) as f:
        for metric, cells in json.load(f).items():
            (entry,) = [m for m in spec["per_layer"] if m["name"] == metric]
            assert set(cells) <= new_cells and not set(cells) & set(entry["workloads"])
            entry["workloads"] = entry["workloads"] + cells
    with open(os.path.join(checkout, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    after = {
        path: digest(os.path.join(checkout, "benchmark", path))
        for path in files_under(os.path.join(checkout, "benchmark"))
    }
    assert {path: after[path] for path in before} == before  # nothing that was there changed
    assert len(after) > len(before)
    return checkout


def run(checkout, *arguments):
    done = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *arguments, "--rehearse"],
        cwd=checkout,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_second_traffic_is_one_data_file(extended):
    cell = "humanoid_mlp64.fixture_refill"  # a fixture: no cell of the benchmark has its name
    line = run(extended, "--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "0")
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 3
    line = run(extended, "--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "1")
    assert line["correct"] is True
    assert 0 < line["metrics"]["contract.occupancy"]["value"] <= 100


def test_a_second_driver_of_another_shape(extended):
    cell = "sphere_snes.steps4"
    line = run(extended, "--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "0")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4 and line["attempted"] % 4 == 0  # whole calls of four generations
    assert set(line["metrics"]) == {"env_steps_per_s", "generation_s", "setup_s"}
    line = run(extended, "--workload", cell, "--seed", "3", "--seconds", "1", "--trace", "1")
    assert line["correct"] is True
    # its own metric, the counters every cell has, and nothing of a policy or an env
    assert set(line["metrics"]) == {
        "objective.evaluations_per_call", "searcher.steady_compiles", "cache.misses",
    }
    assert line["metrics"]["objective.evaluations_per_call"]["value"] == 4 * 16


def test_the_extended_tree_passes_the_lint(extended):
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmark/tests/test_lint.py", "-q", "-p", "no:cacheprovider"],
        cwd=extended,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert done.returncode == 0, done.stdout[-3000:]
