"""Find a cell's data by name: the cell in ``BENCHMARK.json``, its
configuration's file, its traffic mix (``traffic/<name>.json`` under one
of ``paths``), the reader of each per-layer metric
(``readers/<metric>.py``) and the configuration's reference
(``reference/<name>.py``). Adding a cell, a mix, a configuration or a
metric is adding files and entries; nothing here names one."""

from __future__ import annotations

import importlib.util
import json
import os


class Cell:
    def __init__(self, benchmark_path: str, workload: str) -> None:
        self.root = os.path.dirname(os.path.abspath(benchmark_path))
        with open(benchmark_path, encoding="utf-8") as f:
            self.benchmark = json.load(f)
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in {benchmark_path}; "
                             f"there are {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        [entry] = [c for c in self.benchmark["configs"]
                   if c["name"] == self.workload["config"]]
        with open(os.path.join(self.root, entry["file"]),
                  encoding="utf-8") as f:
            self.config = json.load(f)
        with open(self._find("traffic", self.workload["traffic"] + ".json"),
                  encoding="utf-8") as f:
            self.traffic = json.load(f)
        self.reference = _load_module(
            self._find("reference", self.config["reference"] + ".py"))

    def _find(self, kind: str, filename: str) -> str:
        for path in self.benchmark["paths"]:
            candidate = os.path.join(self.root, path, kind, filename)
            if os.path.exists(candidate):
                return candidate
        raise SystemExit(f"no {kind}/{filename} under "
                         f"{self.benchmark['paths']}")

    def _metrics(self, group: str) -> list[dict]:
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def end_to_end(self) -> list[dict]:
        return self._metrics("end_to_end")

    def per_layer(self) -> list[dict]:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self._metrics("per_layer") if m["moves"] in mine]

    def reader(self, metric: str):
        return _load_module(self._find("readers", metric + ".py")).read


def _load_module(path: str):
    name = "perfbench_" + os.path.splitext(os.path.basename(path))[0] \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
