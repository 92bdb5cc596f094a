"""A generation outside the evaluation program, by phase.

``searcher.outside_eval_ms`` is one subtraction (the ``bench.generation`` span
minus the evaluation program's device time inside it). The library names the
programs its searcher dispatches after the phase that dispatches them
(``evotorch_tpu/observability/scopes.py``: ``phase_jit`` makes
``jit_evotorch_tpu_<phase>_<what>``), and the trace's ``XLA Modules`` line has
one event per executed program, already on the host's clock (``trace.load``).
So the subtraction splits, per ``bench.generation`` span and on the first
device, into:

- device seconds of each program inside the span by the phase in its name
  (``ask``, ``grad``, ``update``; ``evaluate`` and ``status`` for the small
  programs those phases dispatch beside the evaluation program itself, which
  is left out as ``outside_eval_ms`` leaves it out);
- ``unnamed``: every other program: eager ops (each its own
  ``jit_<primitive>``), anything ``phase_jit`` missed;
- ``idle``: the parts of the span during which NO program ran (the gaps
  between MODULE intervals; ``device.idle_share`` goes by ops and also counts
  the gaps inside a program);
- ``dispatches``: the programs that started inside the span, the evaluation
  included.

A program that straddles the span's edge counts for the part inside. Programs
do not overlap on one device, so the parts and the evaluation add up to the
span. Every metric is the median over the traced generations.

A trace without device planes (a CPU rehearsal) gives nothing. A library that
names no program (a commit before the names) gives no ``ask`` / ``grad`` /
``update`` figure, never a row of zeros; what needs no name is still read.
"""

import json
import re
import statistics
import sys
from collections import defaultdict

from benchmark.harness import trace as intervals
from benchmark.harness.scopes import module_name

PROGRAM = re.compile(r"^jit_evotorch_tpu_([a-z]+)_")
TOP = 3  # ops of a phase, and unnamed programs, printed for people


def phase_of(name):
    """``jit_evotorch_tpu_ask_sample(123)`` -> ``ask``; None without the prefix."""
    named = PROGRAM.match(name)
    return named.group(1) if named else None


def split(trace):
    """One dict per ``bench.generation`` span: ``seconds`` (``{phase or
    "unnamed": device seconds}``), ``idle`` (seconds), ``dispatches``. None
    without a device plane or a generation."""
    generations = trace.generations()
    if not trace.planes or not generations:
        return None
    evaluation = trace.evaluation_module()
    modules = trace.planes[0].modules
    out = []
    for start, end in generations:
        seconds, ran, dispatches = defaultdict(float), [], 0
        for s, e, name, _ in modules:
            if e <= start or s >= end:
                continue
            ran.append((s, e))
            dispatches += start <= s
            if name != evaluation:
                seconds[phase_of(name) or "unnamed"] += (min(e, end) - max(s, start)) / 1e9
        idle = intervals.length(intervals.gaps(intervals.merge(ran), start, end)) / 1e9
        out.append({"seconds": dict(seconds), "idle": idle, "dispatches": dispatches})
    return out


def top_ops(trace):
    """For people: ``{phase or "unnamed": [[op label, seconds], ...]}`` of the
    ``TOP`` largest ops (self time, first device, inside the generations) of
    the programs of each phase, and under ``"unnamed programs"`` the largest
    programs without a name."""
    evaluation = trace.evaluation_module()
    plane = trace.planes[0]
    spans = intervals.merge(trace.generations())
    ops = sorted(plane.ops)
    by_phase, programs, at = defaultdict(lambda: defaultdict(float)), defaultdict(float), 0
    for s, e, name, _ in sorted(plane.modules):
        if name == evaluation or not intervals.clip(spans, s, e):
            continue
        key = phase_of(name) or "unnamed"
        if key == "unnamed":
            programs[module_name(name)] += (e - s) / 1e9
        while at < len(ops) and ops[at][0] < s:
            at += 1
        inside = []
        while at < len(ops) and ops[at][0] < e:
            inside.append(ops[at])
            at += 1
        for text, ns in intervals.self_times(inside).items():
            by_phase[key][intervals.op_label(text)] += ns / 1e9

    def top(totals):
        return [[k, v] for k, v in sorted(totals.items(), key=lambda item: -item[1])[:TOP]]

    out = {key: top(totals) for key, totals in by_phase.items()}
    out["unnamed programs"] = top(programs)
    return out


def of(run):
    """The run's split, taken once; the medians and the largest ops go to
    stderr for whoever reads the run."""

    def compute():
        generations = split(run.trace)
        if generations is None:
            return None
        keys = sorted({key for g in generations for key in g["seconds"]})
        summary = {
            "ms": {k: 1e3 * statistics.median(g["seconds"].get(k, 0.0) for g in generations) for k in keys},
            "idle_ms": 1e3 * statistics.median(g["idle"] for g in generations),
            "dispatches": statistics.median(g["dispatches"] for g in generations),
            "top_ops_s": top_ops(run.trace),
        }
        print("benchmark: phases: " + json.dumps(summary), file=sys.stderr)
        return generations

    return run.memo("phases", compute)


def _median(run, read):
    generations = of(run)
    return None if generations is None else statistics.median(read(g) for g in generations)


def named_ms(run, phase):
    """Device ms a generation of the programs named for ``phase``; None where
    no program of the traced generations carries a phase at all."""
    generations = of(run)
    if generations is None or all(key == "unnamed" for g in generations for key in g["seconds"]):
        return None
    return _median(run, lambda g: 1e3 * g["seconds"].get(phase, 0.0))


def unnamed_ms(run):
    return _median(run, lambda g: 1e3 * g["seconds"].get("unnamed", 0.0))


def idle_ms(run):
    return _median(run, lambda g: 1e3 * g["idle"])


def dispatches(run):
    return _median(run, lambda g: g["dispatches"])
