"""BENCHMARK.json against the contract's plain rules, and the proof that a
configuration, a traffic mix, a cell and a per-layer metric are each added
as files plus entries, without touching the harness."""

from __future__ import annotations

import json
import re

import pytest

import tinyrepo
from yardstick import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contracts_rules():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        if m["name"] == "setup_s":   # every cell's, so the driver takes no list
            assert "workloads" not in m
            continue
        assert m["workloads"], "every other metric carries its workloads list"
        assert set(m["workloads"]) <= set(cells)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in bench["per_layer"]:
        assert e2e[m["moves"]], "moves names one end-to-end metric"
        assert set(m["workloads"]) <= set(
            e2e[m["moves"]].get("workloads", cells))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        cell = spec.Cell(bench, w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
        ref = cell.config_path.with_suffix("").as_posix() + ".reference.py"
        assert spec.Path(ref).is_file(), "each configuration has its own"
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_cell_with_its_files_is_added_without_touching_the_harness(tmp_path):
    repo = tinyrepo.make(tmp_path / "repo")
    bench = spec.load_benchmark(repo)
    for name, job in (("tiny.train", "train"), ("tiny.serve", "serve")):
        cell = spec.Cell(bench, name, repo)
        assert cell.job == job and cell.config["hidden_size"] == 64
        assert cell.traffic and cell.job_script.is_file()
        assert cell.root == repo / "perfbench"
    train = spec.Cell(bench, "tiny.train", repo)
    assert "tiny_steps_finished" in {m["name"] for m in train.per_layer}
    read = train.reader("tiny_steps_finished")
    assert read({"job": {"step_ends": [0.1, 0.2]}}) == 2.0
    # nothing that was there changed: the copy's harness files are the repo's
    for rel in ("run.py", "yardstick/spec.py", "yardstick/traffic.py",
                "jobs/train.py", "jobs/serve.py"):
        assert (repo / "perfbench" / rel).read_bytes() == (
            spec.ROOT / rel).read_bytes()
    with pytest.raises(spec.SpecError):
        spec.Cell(bench, "no.such.cell", repo)
