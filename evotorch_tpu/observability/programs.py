"""Program ledger: XLA cost/memory accounting + donation verification.

PR 8 made the *runtime* visible (on-device counters, span traces, compile
counting); this module is the *program*-level sibling: what does a compiled
entry point cost in FLOPs, how much memory does it pin, and did XLA honor
the ``donate_argnums`` contract the code declares? Everything here is
ahead-of-time introspection over :meth:`jax.stages.Wrapped.lower` /
:meth:`jax.stages.Lowered.compile` — no hot-path interception, no hooks on
dispatch. A capture costs ONE extra trace+compile of the program (lowering
on ``ShapeDtypeStruct``s, so no buffers are touched and donated callers are
safe); steady-state execution is never observed or perturbed.

Pieces:

- :func:`cost_analysis` / :func:`memory_analysis` — ``compiled.
  cost_analysis()`` and ``compiled.memory_analysis()`` normalized to plain
  dicts. Both read the COMPILED stage, which the CPU and the TPU both
  provide (``Lowered.cost_analysis()`` is ``None`` through a PJRT C-API
  plug-in such as libtpu); an analysis that fails is an error, not a null
  column.
- donation verification — two independent signals for "XLA actually
  aliased the buffers ``donate_argnums`` promised":
  (a) **static**: the compiled module's ENTRY ``input_output_alias`` table
  (parsed from ``compiled.as_text()`` with a balanced-brace scan) checked
  against the donated flat-parameter indices from ``lowered.args_info`` —
  a donated parameter missing from the table is a silently-dropped
  donation, the failure mode graftlint's static ``donation`` checker
  cannot see (it only proves the *request* is present in source);
  (b) **runtime**: :func:`verify_runtime_donation` executes the program
  and asserts the donated input buffers were invalidated
  (``jax.Array.is_deleted``) — jax only deletes inputs whose donation the
  executable consumed, so a dropped donation leaves them alive.
- :class:`ProgramLedger` — the process-wide registry of
  :class:`ProgramRecord`\\ s keyed ``name@shape``; feeds the observability
  counter registry (``peak_hbm_bytes`` max-gauge) so searcher status rows
  pick the figure up for free.
- the baseline workflow — :func:`save_ledger_baseline` /
  :func:`compare_to_baseline` implement the perf-regression gate
  (tolerance bands like ``analysis/baseline.json``'s grandfathering:
  a program whose FLOPs or peak bytes grow past the band fails tier-1;
  one that *shrinks* past the band is a stale entry that must be
  refreshed in the same change). ``ledger_baseline.json`` next to this
  module is the checked-in per-shape baseline
  (``python -m evotorch_tpu.observability.report --cpu --write-baseline``
  refreshes it, refusing partial captures).

See docs/observability.md ("Program ledger") for the field catalog.
"""

from __future__ import annotations

import json
import re
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .registry import counters

__all__ = [
    "DonationReport",
    "abstract_like",
    "ProgramLedger",
    "ProgramRecord",
    "compare_to_baseline",
    "default_ledger_baseline_path",
    "donated_param_indices",
    "cost_analysis",
    "memory_analysis",
    "ledger",
    "load_ledger_baseline",
    "parse_alias_sources",
    "save_ledger_baseline",
    "verify_runtime_donation",
]


def abstract_like(tree):
    """``ShapeDtypeStruct`` skeleton of a pytree of arrays: lowering on it
    touches no device buffers, so programs that DONATE their inputs can be
    captured on live state without consuming it."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: (
            jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(x, "shape") else x
        ),
        tree,
    )


# ---------------------------------------------------------------------------
# introspection of the compiled stage
# ---------------------------------------------------------------------------

#: normalized cost fields (XLA's HloCostAnalysis names, spaces and all)
_COST_FIELDS = (
    ("flops", "flops"),
    ("transcendentals", "transcendentals"),
    ("bytes_accessed", "bytes accessed"),
)

#: CompiledMemoryStats attributes worth recording (device side; the host_*
#: twins are 0 everywhere we run)
_MEMORY_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` normalized to
    ``{"flops", "transcendentals", "bytes_accessed"}`` floats (the fields of
    XLA's cost model that are stable; the per-operand utilization entries
    are dropped)."""
    cost = compiled.cost_analysis()
    return {
        name: float(cost[xla_key]) for name, xla_key in _COST_FIELDS if xla_key in cost
    }


def memory_analysis(compiled) -> Dict[str, int]:
    """``compiled.memory_analysis()`` normalized to plain int byte fields
    plus the derived ``peak_bytes``.

    ``peak_bytes = argument + output - alias + temp`` — the live-at-once
    footprint of one execution. Donation-aware by construction: an aliased
    (donated) output reuses its argument's buffer, so a DROPPED donation
    shows up as an inflated ``peak_bytes`` — exactly the regression the
    gate exists to catch."""
    mem = compiled.memory_analysis()
    out = {name: int(getattr(mem, attr)) for name, attr in _MEMORY_FIELDS}
    out["peak_bytes"] = (
        out["argument_bytes"] + out["output_bytes"] - out["alias_bytes"] + out["temp_bytes"]
    )
    return out


# ---------------------------------------------------------------------------
# donation verification
# ---------------------------------------------------------------------------


def donated_param_indices(lowered) -> List[int]:
    """Flat ENTRY-parameter indices the lowering marked donated, from
    ``lowered.args_info`` (leaves flatten in parameter order).

    Caveat: with ``keep_unused=False`` (the jit default) an entirely
    UNUSED argument is pruned from the executable and shifts parameter
    numbering; donated state args are by construction used, so the mapping
    is exact for every program this repo registers."""
    import jax

    leaves = jax.tree_util.tree_leaves(lowered.args_info)
    return [i for i, leaf in enumerate(leaves) if leaf.donated]


def parse_alias_sources(hlo_text: str) -> Optional[List[int]]:
    """Parameter numbers appearing as alias *sources* in the compiled
    module's ENTRY ``input_output_alias`` table, or ``None`` when the
    module declares no table at all (no donation was applied).

    The table syntax nests braces — ``{ {0}: (0, {}, may-alias), ... }`` —
    so the extent is found with a balanced-brace scan, not a regex."""
    anchor = hlo_text.find("input_output_alias=")
    if anchor < 0:
        return None
    start = hlo_text.find("{", anchor)
    if start < 0:
        return None
    depth = 0
    end = -1
    for j in range(start, len(hlo_text)):
        ch = hlo_text[j]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    if end < 0:
        return None
    body = hlo_text[start : end + 1]
    # each alias entry's source is "(<param_number>, {<param_index>}..."
    return sorted({int(m.group(1)) for m in re.finditer(r"\((\d+)\s*,", body)})


@dataclass(frozen=True)
class DonationReport:
    """The runtime-verified donation map of one compiled program."""

    donated: Tuple[int, ...]  # flat param indices the code donated
    aliased: Tuple[int, ...]  # param indices XLA actually aliased
    missing: Tuple[int, ...]  # donated but NOT aliased — dropped donations

    @property
    def verified(self) -> Optional[bool]:
        """True when every donated parameter was aliased; None when the
        program donates nothing (nothing to verify)."""
        if not self.donated:
            return None
        return not self.missing

    def to_json(self) -> dict:
        return {
            "donated": list(self.donated),
            "aliased": list(self.aliased),
            "missing": list(self.missing),
            "verified": self.verified,
        }


def _donation_report(lowered, compiled) -> DonationReport:
    donated = donated_param_indices(lowered)
    aliased = parse_alias_sources(compiled.as_text()) or []
    missing = [p for p in donated if p not in aliased]
    return DonationReport(
        donated=tuple(donated), aliased=tuple(aliased), missing=tuple(missing)
    )


def verify_runtime_donation(fn, args: Sequence[Any], donate_argnums: Sequence[int]):
    """Execute ``fn(*args)`` and report, per donated argument position,
    whether its buffers were actually invalidated — the runtime ground
    truth of donation (jax deletes exactly the inputs whose donation the
    executable consumed; a dropped donation leaves them alive and warns).

    Returns ``(outputs, {argnum: all_leaves_deleted})``. The caller must
    treat ``args`` at the donated positions as consumed either way."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    report: Dict[int, bool] = {}
    for argnum in donate_argnums:
        leaves = [
            leaf
            for leaf in jax.tree_util.tree_leaves(args[argnum])
            if isinstance(leaf, jax.Array)
        ]
        report[int(argnum)] = bool(leaves) and all(
            leaf.is_deleted() for leaf in leaves
        )
    return out, report


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


@dataclass
class ProgramRecord:
    """Everything the ledger knows about one (program, shape) pair;
    :meth:`ProgramLedger.capture` fills every field."""

    name: str
    shape: Dict[str, Any] = field(default_factory=dict)
    platform: str = ""
    lower_seconds: float = 0.0
    compile_seconds: float = 0.0
    cost: Optional[Dict[str, float]] = None
    memory: Optional[Dict[str, int]] = None
    donation: Optional[DonationReport] = None

    @property
    def key(self) -> str:
        return program_key(self.name, self.shape)

    @property
    def flops(self) -> Optional[float]:
        return None if self.cost is None else self.cost.get("flops")

    @property
    def bytes_accessed(self) -> Optional[float]:
        return None if self.cost is None else self.cost.get("bytes_accessed")

    @property
    def peak_bytes(self) -> Optional[int]:
        return None if self.memory is None else self.memory.get("peak_bytes")

    def to_json(self) -> dict:
        return {
            "key": self.key,
            "name": self.name,
            "shape": dict(self.shape),
            "platform": self.platform,
            "lower_seconds": round(self.lower_seconds, 4),
            "compile_seconds": round(self.compile_seconds, 4),
            "cost": self.cost,
            "memory": self.memory,
            "donation": None if self.donation is None else self.donation.to_json(),
        }


def program_key(name: str, shape: Dict[str, Any]) -> str:
    """The stable ledger/baseline key: ``name@k1=v1,k2=v2`` with the shape
    dict sorted — human-readable and insensitive to capture order."""
    if not shape:
        return name
    return name + "@" + ",".join(f"{k}={shape[k]}" for k in sorted(shape))


class ProgramLedger:
    """Process-wide registry of captured :class:`ProgramRecord`\\ s.

    :meth:`capture` is the one entry point: AOT-lower the jitted callable
    on the given (abstract or concrete) arguments, compile it, and record
    compile wall-time, cost analysis, memory analysis and the donation
    report. Lowering never executes or consumes buffers, so donated
    programs can be captured on live state safely; pass
    ``jax.ShapeDtypeStruct`` trees to avoid touching device memory at all.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._records: Dict[str, ProgramRecord] = {}

    def capture(
        self,
        name: str,
        fn,
        *args,
        shape: Optional[Dict[str, Any]] = None,
        **kwargs,
    ) -> ProgramRecord:
        import jax

        shape = dict(shape) if shape else {}
        t0 = time.perf_counter()
        lowered = fn.lower(*args, **kwargs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        # analyses run OUTSIDE the timed windows: compile_seconds is the
        # compile, not the cost-analysis pass over the (possibly huge) module
        record = ProgramRecord(
            name=name,
            shape=shape,
            platform=jax.devices()[0].platform,
            lower_seconds=t1 - t0,
            compile_seconds=t2 - t1,
            cost=cost_analysis(compiled),
            memory=memory_analysis(compiled),
            donation=_donation_report(lowered, compiled),
        )
        with self._lock:
            self._records[record.key] = record
        counters.increment("ledger_captures")
        if record.peak_bytes is not None:
            counters.observe_max("peak_hbm_bytes", record.peak_bytes)
        counters.accumulate("ledger_compile_seconds", record.compile_seconds)
        return record

    def records(self) -> List[ProgramRecord]:
        with self._lock:
            return list(self._records.values())

    def get(self, name: str, shape: Optional[Dict[str, Any]] = None) -> Optional[ProgramRecord]:
        with self._lock:
            return self._records.get(program_key(name, shape or {}))

    def clear(self) -> None:
        with self._lock:
            self._records.clear()

    def to_json(self) -> dict:
        return {"programs": [r.to_json() for r in self.records()]}


#: the process-wide ledger every subsystem feeds
ledger = ProgramLedger()


# ---------------------------------------------------------------------------
# the perf-regression baseline
# ---------------------------------------------------------------------------

#: fields the gate asserts, when both sides have a number
GATED_FIELDS = ("flops", "peak_bytes")

#: the tolerance band: measured within [base*(1-tol), base*(1+tol)] passes;
#: above is a violation, below is a stale entry (refresh required, like
#: graftlint's fixed-findings rule)
DEFAULT_TOLERANCE = 0.15


def default_ledger_baseline_path() -> Path:
    return Path(__file__).resolve().parent / "ledger_baseline.json"


def load_ledger_baseline(path=None) -> dict:
    path = Path(path) if path is not None else default_ledger_baseline_path()
    if not path.exists():
        return {"tolerance": DEFAULT_TOLERANCE, "platform": None, "programs": []}
    with open(path) as f:
        return json.load(f)


def save_ledger_baseline(
    records: Sequence[ProgramRecord],
    path=None,
    *,
    expected_keys: Optional[Sequence[str]] = None,
    tolerance: float = DEFAULT_TOLERANCE,
) -> Path:
    """Write the checked-in baseline from a capture run.

    Refuses partial runs: when ``expected_keys`` (the full inventory) is
    given, every expected program must have been captured AND carry every
    gated field — a baseline written from a half-failed capture would
    silently shrink the gate's coverage."""
    records = list(records)
    by_key = {r.key: r for r in records}
    if expected_keys is not None:
        missing = sorted(set(expected_keys) - set(by_key))
        if missing:
            raise ValueError(
                "refusing to write a partial ledger baseline: programs not "
                f"captured: {missing}"
            )
        incomplete = sorted(
            k
            for k in expected_keys
            if any(_record_field(by_key[k], f) is None for f in GATED_FIELDS)
        )
        if incomplete:
            raise ValueError(
                "refusing to write a partial ledger baseline: programs "
                f"missing gated analysis fields {GATED_FIELDS}: {incomplete}"
            )
    path = Path(path) if path is not None else default_ledger_baseline_path()
    platforms = sorted({r.platform for r in records})
    payload = {
        "tolerance": tolerance,
        "platform": platforms[0] if len(platforms) == 1 else platforms,
        "programs": [
            {
                "key": r.key,
                "flops": r.flops,
                "peak_bytes": r.peak_bytes,
                "bytes_accessed": r.bytes_accessed,
            }
            for r in sorted(records, key=lambda r: r.key)
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def _record_field(record: ProgramRecord, fieldname: str):
    return getattr(record, fieldname)


def compare_to_baseline(
    records: Sequence[ProgramRecord], baseline: dict
) -> Tuple[List[str], List[str]]:
    """The regression gate: returns ``(violations, stale)`` message lists.

    - a captured program absent from the baseline, or a gated field that
      GREW past the tolerance band, is a **violation** (fails tier-1);
    - a baseline entry whose program is no longer captured, or a gated
      field that SHRANK past the band, is **stale** — the improvement must
      refresh the baseline in the same change (mirrors
      ``tests/test_lint.py``'s stale-entry rule), so the gate's bands
      always track reality."""
    tolerance = float(baseline.get("tolerance", DEFAULT_TOLERANCE))
    base_by_key = {e["key"]: e for e in baseline.get("programs", [])}
    rec_by_key = {r.key: r for r in records}
    violations: List[str] = []
    stale: List[str] = []
    for key, record in sorted(rec_by_key.items()):
        entry = base_by_key.get(key)
        if entry is None:
            violations.append(
                f"{key}: not in ledger_baseline.json — new program; refresh "
                "the baseline (report --write-baseline)"
            )
            continue
        for fieldname in GATED_FIELDS:
            base_value = entry.get(fieldname)
            if base_value is None:
                continue
            measured = _record_field(record, fieldname)
            if measured is None:
                violations.append(
                    f"{key}: {fieldname} regressed to unavailable "
                    f"(baseline {base_value:g})"
                )
                continue
            if measured > base_value * (1.0 + tolerance):
                violations.append(
                    f"{key}: {fieldname} {measured:g} exceeds baseline "
                    f"{base_value:g} by more than {tolerance:.0%} "
                    f"({measured / base_value - 1.0:+.1%})"
                )
            elif measured < base_value * (1.0 - tolerance):
                stale.append(
                    f"{key}: {fieldname} {measured:g} improved past the "
                    f"{tolerance:.0%} band vs baseline {base_value:g} — "
                    "refresh the baseline (report --write-baseline)"
                )
    for key in sorted(set(base_by_key) - set(rec_by_key)):
        stale.append(f"{key}: baseline entry for a program no longer captured")
    return violations, stale
