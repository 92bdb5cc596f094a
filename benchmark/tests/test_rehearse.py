"""Every cell end to end on the CPU (``--rehearse``): the run's shape, never a
speed. ``humanoid_mlp64.budget.pop4`` runs on four virtual devices."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]
CHIPS = {w["name"]: w["chips"] for w in SPEC["workloads"]}


def run(*arguments, env=None):
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *arguments],
        cwd=ROOT,
        env={**os.environ, **(env or {})},
        capture_output=True,
        text=True,
        timeout=900,
    )


def lines(done):
    """The result (the last line) and the detail printed before it."""
    out = done.stdout.strip().splitlines()
    (detail,) = [line for line in out[:-1] if line.startswith("detail: ")]
    return json.loads(out[-1]), json.loads(detail[len("detail: "):])


def listed(group, cell):
    return {m["name"] for m in SPEC[group] if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(cell):
    done = run("--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "0", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line, detail = lines(done)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == listed("end_to_end", cell)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == CHIPS[cell]
    assert detail["rehearse"] is True and detail["counts"]["compiles_in_window"] == 0
    # counted interactions of the whole window over its wall time, not a median of rates
    assert line["metrics"]["env_steps_per_s"]["value"] == pytest.approx(
        detail["counts"]["interactions"] / sum(detail["call_times"])
    )


@pytest.mark.parametrize("cell", ["humanoid_mlp64.episodes", "humanoid_mlp64.budget.pop4"])
def test_traced_line(cell):
    done = run("--workload", cell, "--seed", "6", "--seconds", "1", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line, _ = lines(done)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "breakdown"}
    assert line["correct"] is True
    # the CPU has no device plane in its trace: the readers of the trace find
    # nothing and their metrics are left out; the counters are all there
    assert set(line["metrics"]) == {"searcher.steady_compiles", "contract.occupancy", "cache.misses"}
    assert line["metrics"]["searcher.steady_compiles"]["value"] == 0
    occupancy = line["metrics"]["contract.occupancy"]["value"]
    assert occupancy == 100.0 if "budget" in cell else 0 < occupancy < 100
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_no_result():
    done = run(
        "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
        env={"JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
