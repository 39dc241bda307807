"""Find everything a cell needs from the names in BENCHMARK.json.

The harness knows no cell, configuration, traffic mix or metric by name.
Each is a file of its own under ``benchmark/``:

    configs/<config>/config.json     sizes as run, source, reduced, assumed
    configs/<config>/reference.py    the plain reference, beside the sizes
    configs/<config>/adapter.py      builds the system under test from a seed
    configs/<config>/flops.py        model FLOPs and kernel bytes from shapes
    traffic/<traffic>.json           parameters of one traffic mix (and its kind)
    kinds/<kind>.py                  how a kind of cell (train, serve) is run
    metrics/<metric>.py              one reader per per-layer metric

A later PR adds a cell by adding such files and entries to BENCHMARK.json.
A name that has no file is an error at start-up, never a silent null.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(Exception):
    """BENCHMARK.json and the files under ``benchmark/`` do not agree."""


def load_module(path: str, name: str):
    """Import one file by path under a name that cannot collide."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    mod_name = "benchmark._found." + name.replace("/", ".").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


class Spec:
    """BENCHMARK.json plus the directory its files are found in."""

    def __init__(self, root: str | None = None):
        self.root = os.path.abspath(root or os.path.dirname(BENCH_DIR))
        self.bench_dir = os.path.join(self.root, "benchmark")
        self.doc = _read_json(os.path.join(self.root, "BENCHMARK.json"))
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    # -- one cell ------------------------------------------------------------

    def cell(self, workload: str) -> "Cell":
        if workload not in self.workloads:
            raise SpecError(
                f"no workload {workload!r} in BENCHMARK.json "
                f"(have {sorted(self.workloads)})"
            )
        return Cell(self, self.workloads[workload])

    def metrics_for(self, table: dict, workload: str, reported: set) -> list:
        """Metrics of one table that this cell reports. A metric with a
        ``workloads`` key is reported in the cells it lists; one without
        is reported wherever the end-to-end metric it moves is."""
        out = []
        for name, m in table.items():
            cells = m.get("workloads")
            if cells is not None:
                if workload in cells:
                    out.append(m)
            elif "moves" not in m or m["moves"] in reported:
                out.append(m)
        return out


class Cell:
    def __init__(self, spec: Spec, entry: dict):
        self.spec = spec
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        if self.config_name not in spec.configs:
            raise SpecError(f"cell {self.name}: unknown config {self.config_name!r}")
        cfg_entry = spec.configs[self.config_name]
        self.config_path = os.path.join(spec.root, cfg_entry["file"])
        self.config_dir = os.path.dirname(self.config_path)
        self.config = _read_json(self.config_path)
        self.traffic = _read_json(
            os.path.join(spec.bench_dir, "traffic", self.traffic_name + ".json")
        )
        self.kind_name = self.traffic["kind"]
        self.end_to_end = spec.metrics_for(spec.end_to_end, self.name, set())
        # setup_s and every end-to-end metric without a workloads key are
        # reported everywhere.
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = spec.metrics_for(spec.per_layer, self.name, reported)

    def _config_module(self, stem: str):
        return load_module(
            os.path.join(self.config_dir, stem + ".py"),
            f"configs.{self.config_name}.{stem}",
        )

    @property
    def adapter(self):
        return self._config_module("adapter")

    @property
    def reference(self):
        return self._config_module("reference")

    @property
    def flops(self):
        return self._config_module("flops")

    @property
    def kind(self):
        return load_module(
            os.path.join(self.spec.bench_dir, "kinds", self.kind_name + ".py"),
            f"kinds.{self.kind_name}",
        )

    def metric_reader(self, metric: str):
        mod = load_module(
            os.path.join(self.spec.bench_dir, "metrics", metric + ".py"),
            f"metrics.{metric}",
        )
        if not hasattr(mod, "read"):
            raise SpecError(f"metric file for {metric!r} has no read(ctx)")
        return mod.read

    def check_files(self) -> None:
        """Every file this cell names exists and loads: start-up error
        otherwise."""
        self.adapter, self.reference, self.flops, self.kind
        for m in self.per_layer:
            self.metric_reader(m["name"])
