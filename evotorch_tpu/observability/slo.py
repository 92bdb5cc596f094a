"""Declarative SLO watchdog over the decoded per-group telemetry.

Rules are plain declarative records (``Rule`` dataclasses or equivalent
dicts) evaluated once per generation against a decoded
:class:`~evotorch_tpu.observability.devicemetrics.GroupTelemetry` matrix —
the lag-by-one wire that the engines already emit, so checking SLOs costs
zero extra device syncs.  Four rule kinds cover the fairness/starvation
contract the multi-tenant eval service and island PBT need:

``occupancy_floor``
    every group's (or one group's) lane occupancy must be >= ``threshold``.
    Groups that were allotted zero capacity are skipped (vacuously true).
``starvation_ceiling``
    the share of refills landing in the TOP queue-wait bucket (waits >=
    the last histogram edge) must be <= ``threshold`` — the on-device
    starvation figure, per group or global.
``no_steady_compiles``
    the ``steady_compiles`` status key (retrace sentinel) must be 0.
    Skipped when the key is absent from ``status``.
``min_progress``
    every group's (or one group's) env-step count must be >= ``threshold``
    — a starved tenant shows up here even when its occupancy is undefined.
``min_model_efficiency``
    the ``model_efficiency`` status key (an achieved fraction of nominal
    peak FLOPs, where a caller puts one in ``status``) must be >=
    ``threshold``. Skipped when the key is absent.
``max_nonfinite_share``
    the share of quarantined (non-finite-scored) solutions must be <=
    ``threshold``. Reads the exact ``eval_nonfinite_share`` status key when
    present (quarantined count / popsize); otherwise falls back to the
    telemetry matrix's per-group episode-denominated share — which also
    serves pinned-group rules (``group=g``). A diverging tenant shows up
    here before its quarantined scores distort anyone's ranking; see
    docs/resilience.md.

Three *search-health* kinds read the float32 health plane (schema v4
score statistics) and the algorithm status keys through the stateful
:class:`~evotorch_tpu.observability.health.HealthMonitor` the watchdog
owns (``SLOWatchdog.health``; ``state_dict()``/``load_state_dict()``
checkpoint the window state):

``plateau``
    the per-generation score mean (per group, or the global mean when
    ``group=None``) must keep a statistically significant trend — an EWMA
    slope gated on the stream's own noise floor
    (:class:`~evotorch_tpu.observability.health.EWMATrend`). Violation
    once the no-significant-trend streak reaches ``threshold``
    generations. Falls back to the ``mean_eval``/``score_mean`` status
    keys for global rules when the wire has no health plane.
``stdev_collapse``
    the ``stdev_norm`` status key must stay >= ``threshold`` x its
    first-seen baseline (default threshold 0.01): a distribution whose
    spread imploded by 100x relative to where the run started has stopped
    exploring. Skipped until the key appears.
``score_snr_floor``
    the population score signal-to-noise ratio ``|mean| / std`` must be
    >= ``threshold`` — per group or global, from the health plane.
    Skipped when fewer than 2 scores were seen; a zero std (all scores
    identical) gives infinite SNR and passes.

The watchdog surfaces as searcher status keys (``slo_ok`` /
``slo_violations`` / ``slo_detail``) via ``VecNEProblem(slo=...)`` and as
the evaluation server's per-dispatch verdict (``serving/server.py``).

See docs/observability.md "Per-group telemetry & SLOs".
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Optional, Tuple, Union

from .devicemetrics import GroupTelemetry
from .health import HealthMonitor

__all__ = [
    "Rule",
    "RULE_KINDS",
    "SLOReport",
    "SLOWatchdog",
]


RULE_KINDS = (
    "occupancy_floor",
    "starvation_ceiling",
    "no_steady_compiles",
    "min_progress",
    "min_model_efficiency",
    "max_nonfinite_share",
    "plateau",
    "stdev_collapse",
    "score_snr_floor",
)


@dataclass(frozen=True)
class Rule:
    """One declarative SLO rule.

    ``group=None`` means "every group" for the per-group kinds (and the
    global figure for ``starvation_ceiling``); an int pins the rule to a
    single group row.
    """

    kind: str
    threshold: float = 0.0
    group: Optional[int] = None

    def __post_init__(self):
        if self.kind not in RULE_KINDS:
            raise ValueError(
                f"unknown SLO rule kind {self.kind!r}; expected one of {RULE_KINDS}"
            )


@dataclass(frozen=True)
class SLOReport:
    """Outcome of one watchdog evaluation (one generation)."""

    ok: bool
    violations: Tuple[str, ...] = field(default_factory=tuple)
    checked: int = 0

    def as_status(self) -> Dict[str, Any]:
        status: Dict[str, Any] = {
            "slo_ok": bool(self.ok),
            "slo_violations": len(self.violations),
        }
        if self.violations:
            status["slo_detail"] = "; ".join(self.violations)
        return status

    def summary(self) -> str:
        if self.ok:
            return f"SLO ok ({self.checked} rules)"
        return f"SLO FAIL ({len(self.violations)}/{self.checked}): " + "; ".join(
            self.violations
        )


def _coerce_rule(rule: Union[Rule, Dict[str, Any]]) -> Rule:
    if isinstance(rule, Rule):
        return rule
    if isinstance(rule, dict):
        return Rule(**rule)
    raise TypeError(f"SLO rule must be a Rule or a dict, got {type(rule).__name__}")


class SLOWatchdog:
    """Evaluates a fixed rule set against per-group telemetry each call.

    The search-health rule kinds (``plateau``, ``stdev_collapse``,
    ``score_snr_floor``) are *stateful*: the watchdog owns a
    :class:`~evotorch_tpu.observability.health.HealthMonitor` whose trend
    windows advance one step per :meth:`check` call.
    ``state_dict()``/``load_state_dict()`` round-trip that window state so
    checkpointed runs resume with identical verdict timing.
    """

    def __init__(
        self,
        rules: Optional[Iterable[Union[Rule, dict]]] = None,
        *,
        health: Optional[HealthMonitor] = None,
    ):
        if rules is None or rules is True:
            rules = DEFAULT_RULES
        self.rules: Tuple[Rule, ...] = tuple(_coerce_rule(r) for r in rules)
        self.health = health if health is not None else HealthMonitor()

    def __repr__(self):
        return f"SLOWatchdog(rules={list(self.rules)!r})"

    # --------------------------------------------------------- serialization
    def state_dict(self) -> Dict[str, Any]:
        return {"health": self.health.state_dict()}

    def load_state_dict(self, state: Dict[str, Any]) -> "SLOWatchdog":
        health_state = state.get("health")
        if health_state:
            self.health.load_state_dict(health_state)
        return self

    # ------------------------------------------------------------ evaluation
    def check(
        self,
        telemetry: Optional[GroupTelemetry],
        *,
        status: Optional[Dict[str, Any]] = None,
    ) -> SLOReport:
        """Evaluate every rule; telemetry=None checks only status-keyed rules."""
        violations = []
        checked = 0
        for rule in self.rules:
            outcome = self._check_rule(rule, telemetry, status or {})
            if outcome is None:  # rule not applicable (no data) — skipped
                continue
            checked += 1
            if outcome:
                violations.append(outcome if isinstance(outcome, str) else str(outcome))
        return SLOReport(
            ok=not violations, violations=tuple(violations), checked=checked
        )

    def _check_rule(self, rule, telemetry, status):
        """Returns None (skipped), False (passed) or a violation string."""
        if rule.kind == "no_steady_compiles":
            compiles = status.get("steady_compiles")
            if compiles is None:
                return None
            if int(compiles) > 0:
                return f"steady_compiles={int(compiles)} (expected 0)"
            return False
        if rule.kind == "min_model_efficiency":
            efficiency = status.get("model_efficiency")
            if efficiency is None:  # no ledger columns on this run — skip
                return None
            if float(efficiency) < rule.threshold:
                return (
                    f"model_efficiency={float(efficiency):.4g} < "
                    f"{rule.threshold:g}"
                )
            return False
        if rule.kind == "max_nonfinite_share":
            share = None
            if rule.group is None:
                share = status.get("eval_nonfinite_share")
            if share is None:
                if telemetry is None:
                    return None
                share = telemetry.nonfinite_share(group=rule.group)
            if float(share) > rule.threshold:
                label = "global" if rule.group is None else f"g{rule.group}"
                return (
                    f"nonfinite_share {label}={float(share):.3f} > "
                    f"{rule.threshold:g}"
                )
            return False
        if rule.kind == "stdev_collapse":
            value = status.get("stdev_norm")
            if value is None:
                return None
            value = float(value)
            self.health.observe("stdev_norm", value, group=rule.group)
            baseline = self.health.baseline("stdev_norm", group=rule.group)
            if baseline is None or baseline <= 0.0:
                return None
            if value < rule.threshold * baseline:
                return (
                    f"stdev_norm={value:.4g} < {rule.threshold:g} x "
                    f"baseline {baseline:.4g} (collapse)"
                )
            return False
        if rule.kind == "plateau":
            # group=None reads the GLOBAL score mean (like
            # starvation_ceiling's global figure), not every group
            value = None
            if telemetry is not None and telemetry.has_health:
                stats = telemetry.score_stats(group=rule.group)
                if stats["count"] > 0:
                    value = stats["mean"]
            if value is None and rule.group is None:
                value = status.get("score_mean", status.get("mean_eval"))
            if value is None:
                return None
            trend = self.health.observe(
                "score_mean", float(value), group=rule.group
            )
            if trend.stall_streak >= max(rule.threshold, 1.0):
                label = "global" if rule.group is None else f"g{rule.group}"
                return (
                    f"plateau {label}: no significant score trend for "
                    f"{trend.stall_streak} generations "
                    f"(|trend| {abs(trend.delta_ewma):.3g} <= "
                    f"noise floor {trend.noise_floor:.3g})"
                )
            return False
        if rule.kind == "score_snr_floor":
            if telemetry is None or not telemetry.has_health:
                return None
            stats = telemetry.score_stats(group=rule.group)
            if stats["count"] < 2:
                return None
            snr = (
                float("inf")
                if stats["std"] <= 0.0
                else abs(stats["mean"]) / stats["std"]
            )
            if snr < rule.threshold:
                label = "global" if rule.group is None else f"g{rule.group}"
                return f"score_snr {label}={snr:.3g} < {rule.threshold:g}"
            return False
        if telemetry is None:
            return None
        groups = (
            range(telemetry.num_groups) if rule.group is None else (rule.group,)
        )
        if rule.kind == "occupancy_floor":
            failed = []
            for g in groups:
                t = telemetry.group(g)
                if t.capacity <= 0:  # no lanes allotted: vacuously true
                    continue
                if t.occupancy < rule.threshold:
                    failed.append(f"g{g}={t.occupancy:.3f}")
            if failed:
                return f"occupancy < {rule.threshold:g}: " + ", ".join(failed)
            return False
        if rule.kind == "starvation_ceiling":
            targets = (None,) if rule.group is None else (rule.group,)
            failed = []
            for g in targets:
                share = telemetry.starvation_share(group=g)
                if share > rule.threshold:
                    label = "global" if g is None else f"g{g}"
                    failed.append(f"{label}={share:.3f}")
            if failed:
                return f"starvation > {rule.threshold:g}: " + ", ".join(failed)
            return False
        if rule.kind == "min_progress":
            failed = []
            for g in groups:
                steps = int(telemetry.group(g).env_steps)
                if steps < rule.threshold:
                    failed.append(f"g{g}={steps}")
            if failed:
                return f"env_steps < {rule.threshold:g}: " + ", ".join(failed)
            return False
        raise AssertionError(rule.kind)  # unreachable: ctor validates


#: defaults when ``VecNEProblem(slo=True)`` asks for a watchdog without
#: spelling rules out: no silent retraces, nobody fully starved
DEFAULT_RULES: Tuple[Rule, ...] = (
    Rule("no_steady_compiles"),
    Rule("starvation_ceiling", threshold=0.5),
    Rule("min_progress", threshold=1),
)
