"""Finds a cell's files by the names ``BENCHMARK.json`` gives: one
configuration with its model module, one traffic mix, one job script, one
reader per per-layer metric. Adding any of them adds files and entries; no
file here changes. Imports no jax: the harness process must not hold the
chip."""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]   # the benchmark's tree
REPO = ROOT.parent                           # the checkout


# How a job enters the program: the harness drives these two kinds and no
# other. An architecture needs neither a third; a third is a change to the
# harness (run.py's submit keys, its window, its result), so a benchmark PR.
JOB_KINDS = ("train", "serve")


class SpecError(Exception):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo: Path = REPO) -> dict:
    return load_json(repo / "BENCHMARK.json")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def load_model(model: str, root: Path = ROOT):
    """The model module a configuration names with ``"model"``:
    ``models/<model>.py``, everything the benchmark knows of one
    architecture (the program's model configuration, the leaf table, the
    seeded weights in the program's tree, the counts)."""
    path = root / "models" / f"{model}.py"
    if not path.is_file():
        raise SpecError(f"no model module {path}")
    return load_module(path, f"perfbench_model_{model.replace('-', '_')}")


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files
    resolved, and the metrics that list it."""

    def __init__(self, bench: dict, name: str, repo: Path = REPO) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SpecError(
                f"no workload {name!r} in BENCHMARK.json; known: "
                f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_name = self.entry["config"]
        self.config_path = repo / configs[self.config_name]["file"]
        self.config = load_json(self.config_path)
        self.root = self.config_path.parents[1]
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(
            self.root / "traffic" / f"{self.traffic_name}.json")
        if "model" not in self.config:
            raise SpecError(f"{self.config_path} names no \"model\"")
        self.model = load_model(self.config["model"], self.root)
        self.job = self.config["job"]
        if self.job not in JOB_KINDS:
            raise SpecError(
                f"{self.config_path} asks for the job kind {self.job!r}; "
                f"the harness drives {' and '.join(JOB_KINDS)} only")
        self.job_script = self.root / "jobs" / f"{self.job}.py"
        if not self.job_script.is_file():
            raise SpecError(f"no job script {self.job_script}")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def reader(self, metric: str):
        """The metric's own reader: ``metrics/<name>.py`` with
        ``read(run) -> float | None``."""
        path = self.root / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise SpecError(f"per-layer metric {metric!r} has no reader "
                            f"at {path}")
        return load_module(path, "perfbench_metric_" + metric.replace(
            ".", "_").replace("-", "_")).read


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks, keyed by ``device_kind``. A device that
    is not in the table is an error, never a default."""
    table = load_json(root / "yardstick" / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(
            f"device kind {device_kind!r} is not in yardstick/peaks.json "
            f"(known: {sorted(table['devices'])}); add its published "
            f"peaks with their source")
    return table["devices"][device_kind]
