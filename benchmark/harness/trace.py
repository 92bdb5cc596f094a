"""From a profiler trace (``.xplane.pb``) to numbers.

What a v5e trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Modules`` has one event per executed
program (``jit_<name>(<fingerprint>)``, with a ``run_id``) and whose line
``XLA Ops`` has one event per executed HLO op, named by its HLO text; a
``while`` op's event spans the events of its body's ops, so ops nest. The line
``Async XLA Ops`` holds the start-to-done spans of asynchronous ops (copies,
and collectives that were split in two). The plane ``/host:CPU`` has one line per host
thread: the main thread's (named after the executable, ``python`` or
``python3``) holds the ``jax.profiler.TraceAnnotation`` spans, another the
runtime's ``DoEnqueueProgram`` events with the ``run_id`` of the program they
launch.

The device's clock is not the host's: in the recordings a program "starts" on
the device about 1.3 ms before the host enqueues it. ``load`` shifts the
device's events by the largest such lead (a program cannot start before it is
enqueued), so that an idle gap is attributed to what the host was really doing.

Everything below ``load`` is plain arithmetic on ``(start, end)`` pairs in
nanoseconds and is checked in benchmark/tests/test_trace.py on recorded traces.
"""

import contextlib
import re
import statistics
from collections import defaultdict

ANNOTATION_PREFIXES = ("bench.", "evotorch_tpu.")
GENERATION_SPAN = "bench.generation"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all)")


@contextlib.contextmanager
def recording(trace_dir):
    """Trace what runs inside: device ops and host annotations, no python
    call stacks and no HLO dump (the file stays tens of MB, the host is
    barely slowed)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# -- arithmetic on intervals --------------------------------------------------


def merge(intervals):
    """Sorted, disjoint intervals covering the same points."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [(start, end) for start, end in merged]


def clip(intervals, low, high):
    return [(max(s, low), min(e, high)) for s, e in intervals if e > low and s < high]


def length(intervals):
    return sum(end - start for start, end in intervals)


def gaps(merged, low, high):
    """The parts of [low, high] that the disjoint sorted ``merged`` leave out."""
    out, at = [], low
    for start, end in clip(merged, low, high):
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if high > at:
        out.append((at, high))
    return out


def self_times(events):
    """``{name: time}`` where an event's time excludes the events nested in it
    (a ``while`` op's own time is what its body's ops do not cover).
    ``events``: (start, end, name)."""
    totals = defaultdict(float)
    stack = []  # [end, name, own time so far]

    def close():
        end, name, own = stack.pop()
        totals[name] += own

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close()
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    while stack:
        close()
    return dict(totals)


def innermost(spans, at):
    """The name of the shortest span open at time ``at``; None outside all."""
    best = None
    for start, end, name in spans:
        if start <= at < end and (best is None or end - start < best[0]):
            best = (end - start, name)
    return None if best is None else best[1]


def op_label(hlo_text):
    """``%fusion.12 = bf16[8,64]{...} fusion(...)`` -> ``fusion.12 bf16[8,64]``."""
    name, _, rest = hlo_text.partition(" = ")
    name = name.lstrip("%")
    shape = re.match(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return f"{name} {shape.group(0)}" if shape else name


# -- the trace ----------------------------------------------------------------


class DevicePlane:
    def __init__(self, name):
        self.name = name
        self.ops = []  # (start, end, HLO text)
        self.async_ops = []
        self.modules = []  # (start, end, program name, run_id)

    def shift(self, ns):
        for events in (self.ops, self.async_ops):
            events[:] = [(s + ns, e + ns, n) for s, e, n in events]
        self.modules[:] = [(s + ns, e + ns, n, r) for s, e, n, r in self.modules]


class Trace:
    def __init__(self, planes, spans, clock_shift_ns=0.0):
        self.planes = planes
        self.spans = spans  # host annotations: (start, end, name)
        self.clock_shift_ns = clock_shift_ns
        generations = self.generations()
        if generations:
            self.window = (generations[0][0], generations[-1][1])
        else:
            self.window = (0.0, 0.0)
        self._busy = [merge([(s, e) for s, e, _ in plane.ops]) for plane in planes]

    def generations(self):
        return sorted((s, e) for s, e, name in self.spans if name == GENERATION_SPAN)

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy_by_plane(self):
        """Seconds inside the window during which an op ran, per device."""
        return [length(clip(busy, *self.window)) / 1e9 for busy in self._busy]

    @property
    def busy_s(self):
        busy = self.busy_by_plane()
        return sum(busy) / len(busy) if busy else 0.0

    def idle_share(self):
        if not self.planes or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def busy_spread(self):
        busy = self.busy_by_plane()
        if len(busy) < 2 or max(busy) <= 0:
            return None
        return 100.0 * (max(busy) - min(busy)) / max(busy)

    def collective_share(self):
        """Device time of collective ops (either half of a split one, and the
        span between the halves) over the window, averaged over devices."""
        if not self.planes or self.window_s <= 0:
            return None
        shares = []
        for plane in self.planes:
            spans = [
                (s, e)
                for s, e, text in plane.ops + plane.async_ops
                if COLLECTIVE.match(text.partition(" = ")[0].lstrip("%"))
            ]
            shares.append(length(clip(merge(spans), *self.window)) / 1e9)
        return 100.0 * (sum(shares) / len(shares)) / self.window_s

    def op_seconds(self):
        """Self time by op label inside the window, averaged over devices."""
        totals = defaultdict(float)
        for plane in self.planes:
            inside = [
                (max(s, self.window[0]), min(e, self.window[1]), text)
                for s, e, text in plane.ops
                if e > self.window[0] and s < self.window[1]
            ]
            for text, ns in self_times(inside).items():
                totals[op_label(text)] += ns / 1e9 / len(self.planes)
        return dict(totals)

    def module_seconds(self):
        """Device time by program inside the window, on the first device."""
        totals = defaultdict(float)
        for s, e, name, _ in self.planes[0].modules:
            overlap = min(e, self.window[1]) - max(s, self.window[0])
            if overlap > 0:
                totals[name] += overlap / 1e9
        return dict(totals)

    def evaluation_module(self):
        """The program with most device time in the window: the evaluation."""
        modules = self.module_seconds() if self.planes else {}
        return max(modules, key=modules.get) if modules else None

    def evaluation_ops(self):
        """``{HLO text: [self seconds, executions]}`` of the ops that ran inside
        the evaluation program within the window, averaged over devices."""
        evaluation = self.evaluation_module()
        totals = defaultdict(lambda: [0.0, 0.0])
        for plane in self.planes:
            runs = clip(
                [(s, e) for s, e, name, _ in plane.modules if name == evaluation], *self.window
            )
            inside = [
                (s, min(e, high), text)
                for low, high in runs
                for s, e, text in plane.ops
                if low <= s < high
            ]
            for _, _, text in inside:
                totals[text][1] += 1.0 / len(self.planes)
            for text, ns in self_times(inside).items():
                totals[text][0] += ns / 1e9 / len(self.planes)
        return dict(totals)

    def evaluation_seconds(self):
        """Device seconds of the evaluation program's runs inside the window,
        averaged over devices: what its ops' self seconds add up to where the
        trace kept every op."""
        evaluation = self.evaluation_module()
        runs = [
            clip([(s, e) for s, e, name, _ in plane.modules if name == evaluation], *self.window)
            for plane in self.planes
        ]
        return sum(length(r) for r in runs) / 1e9 / len(runs) if runs else 0.0

    def executions(self, hlo_text, low, high):
        """Events of the op ``hlo_text`` that start in [low, high), averaged
        over devices."""
        counts = [sum(1 for s, _, text in plane.ops if text == hlo_text and low <= s < high) for plane in self.planes]
        return sum(counts) / len(counts) if counts else 0.0

    def outside_eval_ms(self):
        """Per generation: the ``bench.generation`` span minus the device time
        of the evaluation program inside it; the median. Ask, gradient, update,
        eager ops and gaps together."""
        evaluation = self.evaluation_module()
        if evaluation is None or not self.generations():
            return None
        outside = []
        for start, end in self.generations():
            inside = sum(
                max(0.0, min(e, end) - max(s, start))
                for s, e, name, _ in self.planes[0].modules
                if name == evaluation
            )
            outside.append((end - start - inside) / 1e6)
        return statistics.median(outside)

    def gap_seconds(self):
        """Idle time of the first device inside the window by the innermost
        host annotation open at the middle of each gap."""
        totals = defaultdict(float)
        if not self.planes:
            return {}
        for start, end in gaps(self._busy[0], *self.window):
            owner = innermost(self.spans, 0.5 * (start + end)) or "(no annotation)"
            totals[owner] += (end - start) / 1e9
        return dict(totals)

    def breakdown(self):
        def top(totals):
            ranked = sorted(totals.items(), key=lambda item: -item[1])[:10]
            return [[name, seconds] for name, seconds in ranked]

        return {"device_ops": top(self.op_seconds()), "idle_gaps": top(self.gap_seconds())}


def load(path, *, chips=1):
    """Read ``path`` with nothing but jax. Device planes are the first
    ``chips`` of ``/device:TPU:<i>``; a trace without any (a CPU rehearsal)
    gives a Trace with no planes, from which every reader returns nothing."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    planes, spans, enqueued = {}, [], {}
    for plane in profile.planes:
        device = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if device:
            out = planes[int(device.group(1))] = DevicePlane(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name == "Async XLA Ops":
                    out.async_ops = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        run_id = dict(e.stats).get("run_id")
                        out.modules.append((e.start_ns, e.start_ns + e.duration_ns, e.name, run_id))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIXES):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                    elif e.name == "DoEnqueueProgram":
                        stats = dict(e.stats)  # run ids count per device
                        launch = (stats.get("device_ordinal"), stats.get("run_id"))
                        enqueued.setdefault(launch, e.start_ns)
    ordered = [planes[i] for i in sorted(planes)][:chips]
    # a program cannot start before the host enqueues it: the largest lead is
    # (a lower bound of) how far the device's clock runs behind the host's
    lead = 0.0
    for ordinal, plane in zip(sorted(planes), ordered):
        for start, _, _, run_id in plane.modules:
            if (ordinal, run_id) in enqueued:
                lead = max(lead, enqueued[ordinal, run_id] - start)
    for plane in ordered:
        plane.shift(lead)
    return Trace(ordered, spans, clock_shift_ns=lead)
