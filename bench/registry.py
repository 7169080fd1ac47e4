"""Where the benchmark's data and readers are found, by name.

Everything that belongs to one configuration, one cell, one traffic kind,
one comparison or one per-layer metric is a file of its own under the benchmark's
directory; `BENCHMARK.json` beside it names them. Nothing here lists
them: a later PR adds a file and an entry and edits nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys


class Registry:
    def __init__(self, root: str):
        """``root`` holds `BENCHMARK.json` and the directory `bench/`."""
        self.root = root
        self.dir = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)

    def _json(self, *parts: str) -> dict:
        with open(os.path.join(self.dir, *parts)) as f:
            return json.load(f)

    def _module(self, kind: str, name: str):
        path = os.path.join(self.dir, kind, f"{name}.py")
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"no {kind} file for {name!r}: {path} is missing")
        if os.path.dirname(path) not in sys.path:
            # a reader may import its neighbours
            sys.path.insert(0, os.path.dirname(path))
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def entry(self, workload: str) -> dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == workload:
                return w
        raise SystemExit(f"BENCHMARK.json has no workload {workload!r}")

    def cell(self, workload: str) -> dict:
        cell = self._json("workloads", f"{workload}.json")
        entry = self.entry(workload)
        if cell["config"] != entry["config"] or \
                cell["chips"] != entry["chips"]:
            raise SystemExit(
                f"{workload}: its file and BENCHMARK.json disagree")
        return cell

    def config(self, name: str) -> dict:
        for c in self.benchmark["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise SystemExit(f"BENCHMARK.json has no configuration {name!r}")

    def peaks(self) -> dict:
        return self._json("peaks.json")

    def traffic(self, kind: str):
        return self._module("traffic", kind)

    def comparison(self, name: str):
        return self._module("compare", name)

    def reader(self, metric: str):
        return self._module("metrics", metric)

    def metrics(self, group: str, workload: str) -> list[dict]:
        """The entries of ``group`` ("end_to_end" or "per_layer") that
        this cell reports."""
        return [m for m in self.benchmark[group]
                if "workloads" not in m or workload in m["workloads"]]
