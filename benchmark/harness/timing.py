"""The clock: one ``generation()`` call at a time, and what a window's times say.

The arithmetic is bench.py's (whole generations, ``block_until_ready`` before
the clock stops, counted interactions over wall time), copied here so that the
benchmark reads nothing from a script later PRs may change.
"""

import math
import time

import jax
import numpy as np


class Run:
    """What one process measured; the per-layer metric readers get this."""

    def __init__(self, *, session, files, workload, config, scale, rehearse):
        self.session = session
        self.files = files  # loader.BenchmarkFiles: a reader may load a file of its own
        self.workload = workload
        self.config = config
        self.scale = scale  # popsize and the reference's sample sizes, as run
        self.popsize = int(scale["popsize"])
        self.rehearse = rehearse
        self.times = []  # wall seconds of each call, warm-up included
        self.compiles = []  # compilations seen during each call
        self.marks = [session.mark()]  # marks[i + 1]: after call i
        self.compile_log = None
        self.cache_at_setup_end = None
        self.setup_s = None
        self.counts = None
        self.device_record = None
        self.trace = None
        self._memo = {}

    def generation(self):
        """One ``session.generation()`` call to its evaluations ready, under the
        two host spans an idle gap can be attributed to; the mark is taken off
        the clock."""
        compiles_before = self.compile_log.count
        with jax.profiler.TraceAnnotation("bench.generation"):
            start = time.perf_counter()
            self.session.generation()
            with jax.profiler.TraceAnnotation("bench.block"):
                self.session.block()
            self.times.append(time.perf_counter() - start)
        self.compiles.append(self.compile_log.count - compiles_before)
        self.marks.append(self.session.mark())

    def memo(self, key, compute):
        """Per-layer metrics that share a reduction (a time and its roofline
        share) take it once."""
        if key not in self._memo:
            self._memo[key] = compute()
        return self._memo[key]


def summarize(times):
    """Median, sample count, extremes, and the highest percentile that still
    has ten samples beyond it (none under 20 samples)."""
    count = len(times)
    summary = {
        "count": count,
        "median": float(np.median(times)) if count else 0.0,
        "min": min(times, default=0.0),
        "max": max(times, default=0.0),
        "tail_percentile": None,
        "tail": None,
    }
    if count >= 20:
        q = math.floor(100.0 * (count - 10) / count)
        summary["tail_percentile"] = q
        summary["tail"] = float(np.percentile(times, q))
    return summary
