"""Find a cell's files by the names ``BENCHMARK.json`` gives.

Everything that belongs to one cell, one configuration, one driver or one
per-layer metric sits in a file of its own; a later PR adds files and
``BENCHMARK.json`` entries and edits nothing that is here.

- ``benchmark/workloads/<cell>.json``: config, chips, driver, the layers the
  cell exercises, traffic parameters (with the plain rollout the cell is held
  to), warm-up and traced generations, why.
- ``benchmark/configs/<config>.json``: env, network, searcher recipe, dtype,
  popsize, source, reduced, assumed, the plain forward it is held to, and
  under ``rehearse`` its keys of scale with their rehearsal values.
- ``benchmark/drivers/<driver>.py``: ``build(files, config, workload, seed,
  scale)`` returns a session (see drivers/oo_searcher.py for the protocol).
- ``benchmark/layer_metrics/<metric>.py``: ``LAYER``, ``UNIT``, ``BETTER``,
  ``SOURCE``, ``MOVES``, ``applies(workload)`` and ``measure(run)``.
"""

import importlib.util
import json
import os


class BenchmarkFiles:
    def __init__(self, root):
        self.root = root
        self.dir = os.path.join(root, "benchmark")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _json(self, *parts):
        with open(os.path.join(self.dir, *parts)) as f:
            return json.load(f)

    def _module(self, kind, name):
        return self.module_at(os.path.join("benchmark", kind, name + ".py"))

    def module_at(self, path):
        """The python file at ``path`` (relative to the checkout) as a module."""
        # dots in a metric's name are part of the file's name, not packages
        stem = os.path.splitext(path)[0].replace(".", "_").replace(os.sep, ".")
        spec = importlib.util.spec_from_file_location(stem, os.path.join(self.root, path))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def workload(self, name):
        listed = [w for w in self.spec["workloads"] if w["name"] == name]
        if not listed:
            raise SystemExit(f"BENCHMARK.json lists no workload {name!r}")
        workload = self._json("workloads", name + ".json")
        for key in ("name", "config", "chips"):
            if workload[key] != listed[0][key]:
                raise SystemExit(
                    f"workloads/{name}.json and BENCHMARK.json disagree on {key!r}"
                )
        return workload

    def config(self, name):
        return self._json("configs", name + ".json")

    def driver(self, name):
        return self._module("drivers", name)

    def metrics(self, group, workload_name):
        """The ``end_to_end`` or ``per_layer`` entries that exist in this cell."""
        return [
            m
            for m in self.spec[group]
            if "workloads" not in m or workload_name in m["workloads"]
        ]

    def layer_metric(self, name):
        return self._module("layer_metrics", name)
