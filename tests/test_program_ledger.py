"""Program ledger: guarded introspection, donation verification (static
alias table + runtime buffer deletion), and the perf-regression gate.

The contracts under test (docs/observability.md "Program ledger"):

- the guarded accessors degrade to ``None`` on any backend path that
  lacks cost/memory analysis, and return normalized dicts on the CPU mesh;
- a ledger capture records compile wall-time, FLOPs, peak bytes and the
  donation map of the EXACT compiled program, and a donation XLA silently
  dropped is detected both statically (missing alias entry) and at
  runtime (input buffers left alive);
- every ``donate_argnums`` entry point the repo registers (gaussian tell,
  the one-device and GSPMD generation steps, the batched functional
  search) has its aliasing verified at runtime — the dynamic complement
  of graftlint's static ``donation`` checker;
- the fast-tier REGRESSION GATE: the inventory captured at the gate
  shapes must sit inside ``ledger_baseline.json``'s tolerance bands — a
  program whose FLOPs or peak footprint inflates past the band fails
  tier-1 here instead of OOMing on the TPU months later, and a synthetic
  +20% violation demonstrably trips it; stale entries (improvements, or
  programs no longer captured) fail too, mirroring ``test_lint.py``'s
  baseline discipline.
"""

import copy
import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from evotorch_tpu.observability import (
    ProgramLedger,
    compare_to_baseline,
    counters,
    default_ledger_baseline_path,
    cost_analysis,
    memory_analysis,
    load_ledger_baseline,
    save_ledger_baseline,
    verify_runtime_donation,
)
from evotorch_tpu.observability.inventory import (
    GateConfig,
    capture_inventory,
    donated_programs,
    inventory_keys,
)
from evotorch_tpu.observability.programs import abstract_like, parse_alias_sources


@pytest.fixture(scope="module")
def gate_capture():
    """ONE inventory capture at the gate shapes, shared by every gate test
    (each capture is an AOT compile; sharing keeps the fast tier fast).

    The capture bypasses the persistent compile cache (conftest enables it
    suite-wide): an executable DESERIALIZED from the cache reports a
    constant +1408 bytes of peak memory on this backend, which would skew
    the fingerprints the gate bands against ledger_baseline.json — the
    instrument must measure the program, not the cache's framing (restored
    afterwards, so the rest of the suite keeps its warm cache)."""
    from evotorch_tpu.observability.compilecache import past_persistent_cache

    with past_persistent_cache():
        led = ProgramLedger()
        records, errors = capture_inventory(GateConfig(), led, strict=True)
    assert errors == {}
    return records


# ---------------------------------------------------------------------------
# introspection of the compiled stage
# ---------------------------------------------------------------------------


def test_compiled_stage_analyses():
    """Normalized dicts with the documented fields, including the
    donation-aware peak_bytes derivation — read from the COMPILED stage,
    the one both the CPU and the TPU provide."""
    compiled = jax.jit(lambda x: x * 2.0 + 1.0).lower(jnp.zeros((64, 64))).compile()
    cost = cost_analysis(compiled)
    assert {"flops", "bytes_accessed"} <= set(cost) <= {
        "flops", "transcendentals", "bytes_accessed"
    }
    assert cost["flops"] > 0
    memory = memory_analysis(compiled)
    for field in ("argument_bytes", "output_bytes", "temp_bytes", "peak_bytes"):
        assert field in memory and memory[field] >= 0
    assert memory["peak_bytes"] == (
        memory["argument_bytes"]
        + memory["output_bytes"]
        - memory.get("alias_bytes", 0)
        + memory["temp_bytes"]
    )


def test_parse_alias_sources_handles_nested_braces():
    text = (
        "ENTRY main, input_output_alias={ {0}: (0, {}, may-alias), "
        "{1}: (2, {}, may-alias) }, entry_computation_layout={...}"
    )
    assert parse_alias_sources(text) == [0, 2]
    assert parse_alias_sources("HloModule without any alias table") is None


# ---------------------------------------------------------------------------
# capture + donation verification
# ---------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0,))
def _donating_step(state, key):
    noise = jax.random.normal(key, state["mu"].shape)
    return {"mu": state["mu"] + noise, "sigma": state["sigma"] * 2.0}


@partial(jax.jit, donate_argnums=(0,))
def _dropping_step(state, key):
    # the only output is a scalar: nothing can alias the donated (64, 8)
    # buffer, XLA must drop the donation — the failure mode the ledger
    # exists to catch
    del key
    return jnp.sum(state)


def _toy_state():
    return {"mu": jnp.zeros((64, 8)), "sigma": jnp.ones((64, 8))}


def test_capture_records_costs_and_verified_donation():
    led = ProgramLedger()
    before = counters.get("peak_hbm_bytes")
    record = led.capture(
        "toy.step",
        _donating_step,
        abstract_like(_toy_state()),
        jax.random.key(0),
        shape={"n": 64},
    )
    assert record.key == "toy.step@n=64"
    assert record.compile_seconds > 0 and record.lower_seconds > 0
    assert record.flops is not None and record.flops > 0
    assert record.peak_bytes is not None and record.peak_bytes > 0
    assert record.donation is not None and record.donation.verified is True
    assert list(record.donation.missing) == []
    assert led.get("toy.step", {"n": 64}) is record
    # the registry's high-water gauge saw the capture
    assert counters.get("peak_hbm_bytes") >= record.peak_bytes
    assert counters.get("peak_hbm_bytes") >= before
    payload = record.to_json()
    assert payload["donation"]["verified"] is True


def test_capture_detects_silently_dropped_donation():
    led = ProgramLedger()
    with warnings.catch_warnings():
        # jax itself warns "Some donated buffers were not usable" at compile
        warnings.simplefilter("ignore")
        record = led.capture(
            "toy.dropped",
            _dropping_step,
            abstract_like(jnp.zeros((64, 8))),
            jax.random.key(0),
        )
    assert record.donation is not None
    assert record.donation.verified is False
    assert len(record.donation.missing) > 0
    # and the runtime ground truth agrees: the buffer was NOT invalidated
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _, report = verify_runtime_donation(
            _dropping_step, (jnp.zeros((64, 8)), jax.random.key(0)), (0,)
        )
    assert report == {0: False}


_DONATED_PROGRAM_NAMES = [
    "gaussian.tell",
    "bench.generation",
    "gspmd.generation",
    "gspmd.training_span",
    "functional_batched_search",
]


@pytest.mark.parametrize("name", _DONATED_PROGRAM_NAMES)
def test_runtime_donation_applied_for_every_registered_program(name):
    """The dynamic donation sweep: execute each donate_argnums entry point
    and assert jax invalidated the donated state — XLA consumed the
    aliasing, it was not silently dropped."""
    cases = {n: (fn, args, dn) for n, fn, args, dn in donated_programs()}
    fn, args, donate_argnums = cases[name]
    _, report = verify_runtime_donation(fn, args, donate_argnums)
    assert report == {argnum: True for argnum in donate_argnums}, name


def test_static_donation_verified_across_the_inventory(gate_capture):
    donating = [
        r for r in gate_capture if r.donation is not None and r.donation.donated
    ]
    assert {r.name for r in donating} >= set(_DONATED_PROGRAM_NAMES)
    for record in donating:
        assert record.donation.verified is True, (
            record.key,
            record.donation.to_json(),
        )


# ---------------------------------------------------------------------------
# the perf-regression gate
# ---------------------------------------------------------------------------


def test_regression_gate_matches_checked_in_baseline(gate_capture):
    """THE tier-1 gate: every inventory program's FLOPs and peak bytes sit
    inside ledger_baseline.json's tolerance bands, with no stale entries.
    Refresh flow on intended changes:
    ``python -m evotorch_tpu.observability.report --cpu --write-baseline``."""
    baseline = load_ledger_baseline(default_ledger_baseline_path())
    violations, stale = compare_to_baseline(gate_capture, baseline)
    assert violations == [], "program-ledger regressions:\n" + "\n".join(violations)
    assert stale == [], "stale ledger baseline entries:\n" + "\n".join(stale)


def test_gate_fails_on_synthetic_peak_inflation(gate_capture):
    """A +20% peak-HBM regression (simulated by deflating the baseline)
    demonstrably trips the gate."""
    baseline = copy.deepcopy(load_ledger_baseline(default_ledger_baseline_path()))
    for entry in baseline["programs"]:
        if entry.get("peak_bytes"):
            entry["peak_bytes"] = entry["peak_bytes"] / 1.2
    violations, _ = compare_to_baseline(gate_capture, baseline)
    assert violations, "a +20% peak inflation must violate the 15% band"
    assert any("peak_bytes" in message for message in violations)


def test_gate_flags_improvements_and_orphans_as_stale(gate_capture):
    baseline = copy.deepcopy(load_ledger_baseline(default_ledger_baseline_path()))
    for entry in baseline["programs"]:
        if entry.get("flops"):
            entry["flops"] = entry["flops"] * 1.3  # measured is now -23%
    baseline["programs"].append(
        {"key": "ghost.program@n=1", "flops": 1.0, "peak_bytes": 1}
    )
    violations, stale = compare_to_baseline(gate_capture, baseline)
    assert violations == []
    assert any("improved past" in message for message in stale)
    assert any("no longer captured" in message for message in stale)


def test_gate_flags_unbaselined_programs_as_violations(gate_capture):
    baseline = copy.deepcopy(load_ledger_baseline(default_ledger_baseline_path()))
    baseline["programs"] = [
        e for e in baseline["programs"] if not e["key"].startswith("rollout.budget")
    ]
    violations, _ = compare_to_baseline(gate_capture, baseline)
    assert any("not in ledger_baseline.json" in message for message in violations)


def test_write_baseline_refuses_partial_runs(tmp_path, gate_capture):
    expected = [r.key for r in gate_capture]
    # a program the run never captured -> refuse
    with pytest.raises(ValueError, match="not captured"):
        save_ledger_baseline(
            gate_capture,
            tmp_path / "partial.json",
            expected_keys=expected + ["missing.program@n=1"],
        )
    # a captured program whose gated analysis came back null -> refuse
    broken = dataclasses.replace(gate_capture[0], memory=None)
    with pytest.raises(ValueError, match="gated analysis"):
        save_ledger_baseline(
            [broken], tmp_path / "null.json", expected_keys=[broken.key]
        )
    # the complete run writes, round-trips, and self-compares clean
    path = save_ledger_baseline(
        gate_capture, tmp_path / "full.json", expected_keys=expected
    )
    violations, stale = compare_to_baseline(gate_capture, load_ledger_baseline(path))
    assert violations == [] and stale == []


def test_inventory_keys_match_capture(gate_capture):
    assert inventory_keys(GateConfig()) == [r.key for r in gate_capture]


# ---------------------------------------------------------------------------
# status keys + logger columns
# ---------------------------------------------------------------------------


def test_searcher_status_and_logger_rows_carry_ledger_keys():
    """The per-generation ledger/status keys thread through the scalar
    loggers like PR 8's `compiles`: compile_seconds (per-step compile
    wall-time delta) and peak_hbm_bytes (the ledger gauge) appear in every
    PandasLogger row."""
    from evotorch_tpu import Problem, vectorized
    from evotorch_tpu.algorithms.gaussian import SNES
    from evotorch_tpu.logging import PandasLogger

    @vectorized
    def sphere(xs):
        return jnp.sum(xs**2, axis=-1)

    problem = Problem(
        "min", sphere, solution_length=5, initial_bounds=(-3, 3), seed=0
    )
    searcher = SNES(problem, stdev_init=2.0)
    logger = PandasLogger(searcher)
    searcher.run(2)
    status = dict(searcher.status.items())
    assert isinstance(status["compile_seconds"], float)
    assert status["compile_seconds"] >= 0.0
    assert status["peak_hbm_bytes"] >= 0
    for row in logger._data:
        assert "compile_seconds" in row
        assert "peak_hbm_bytes" in row
