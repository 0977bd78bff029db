"""BENCHMARK.json against the contract's plain rules, and the proof that a
configuration, a traffic mix, a cell, a per-layer metric and a model (an
architecture) are each added as files plus entries, without touching the
harness — and that the harness names no architecture, so it stays so."""

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
    assert_harness_untouched(repo)
    with pytest.raises(spec.SpecError):
        spec.Cell(bench, "no.such.cell", repo)


# What may know no architecture: the harness, the job scripts and the
# yardstick. (What knows one: models/, the configurations, their references.)
HARNESS_FILES = ["run.py", "jobs/serve.py", "jobs/train.py",
                 "jobs/_shared.py"] + sorted(
    f"yardstick/{p.name}" for p in (spec.ROOT / "yardstick").iterdir()
    if p.is_file())


def assert_harness_untouched(repo):
    """Nothing that was there changed: the copy's files are the repo's."""
    for rel in HARNESS_FILES + ["models/dense.py",
                                "models/pinned_leaf_ids.json"]:
        assert (repo / "perfbench" / rel).read_bytes() == (
            spec.ROOT / rel).read_bytes(), rel


def test_an_architecture_with_its_cells_is_added_without_touching_the_harness(
        tmp_path):
    """The toy mixture of experts: a model module the repo does not have,
    two configurations naming it, their plain reference, two cells."""
    repo = tinyrepo.make(tmp_path / "repo")
    bench = spec.load_benchmark(repo)
    assert not (spec.ROOT / "models" / "toymoe.py").exists()
    for name, job in (("toymoe.train", "train"), ("toymoe.serve", "serve")):
        cell = spec.Cell(bench, name, repo)
        assert cell.job == job and cell.config["model"] == "toymoe"
        assert cell.model.__file__ == str(
            repo / "perfbench" / "models" / "toymoe.py")
        assert "router" in cell.model.leaf_table(cell.config)
        ref = cell.config_path.with_suffix("").as_posix() + ".reference.py"
        assert "num_local_experts" in spec.Path(ref).read_text()
    # the cells the repo has still find the model they name
    assert spec.Cell(bench, "tiny.serve", repo).model.__file__.endswith(
        "models/dense.py")
    assert_harness_untouched(repo)


ARCHITECTURE_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_hidden_layers", "rope_theta",
    "rms_norm_eps", "sliding_window", "num_local_experts",
    "num_experts_per_tok")
PROGRAM_CONFIG_NAMES = ("TransformerConfig", "d_model", "n_heads",
                        "n_kv_heads", "n_layers", "d_ff", "n_experts")


def test_the_harness_names_no_architecture():
    """So that the seam cannot silt up again unnoticed: no key of a
    configuration that describes the model's shape, no leaf name (the
    benchmark's or the program's) and no field of the program's model
    configuration in the harness, the job scripts or the yardstick.
    ``vocab_size`` is allowed: it is the traffic's, where ids are drawn."""
    from yardstick import weights

    dense = spec.load_model("dense")
    leaves = set(json.loads(weights.PINNED_IDS.read_text())) | set(
        dense.PROGRAM_LAYER_NAMES) | set(dense.PROGRAM_TOP_NAMES)
    leaves -= {"embed"}      # also a plain word: ``embedding`` in prose
    words = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
    assert len(HARNESS_FILES) > 15
    for rel in HARNESS_FILES:
        found = set(words.findall((spec.ROOT / rel).read_text()))
        named = found & (set(ARCHITECTURE_KEYS) | leaves
                         | set(PROGRAM_CONFIG_NAMES))
        assert not named, f"{rel} names {sorted(named)}"
    # the test can fail: the model module and a reference do name them
    assert {"hidden_size", "q_proj", "wq"} <= set(words.findall(
        (spec.ROOT / "models" / "dense.py").read_text()))


@pytest.mark.parametrize("broken,says", [
    ({"job": "lifecycle"}, "job kind 'lifecycle'"),
    ({"model": "no-such-model"}, "no model module"),
    ({"model": None}, "names no"),
])
def test_a_configuration_the_harness_cannot_drive_is_a_spec_error(
        tmp_path, capsys, monkeypatch, broken, says):
    """Two job kinds are how jobs enter the program; a third is a benchmark
    PR's, and the one command says so in one line and exits 2."""
    import run as harness

    repo = tinyrepo.make(tmp_path / "repo")
    path = repo / "perfbench" / "configs" / "tiny-serve.json"
    config = json.loads(path.read_text())
    config.update(broken)
    if broken.get("model", "") is None:
        del config["model"]
    path.write_text(json.dumps(config))
    with pytest.raises(spec.SpecError, match=says):
        spec.Cell(spec.load_benchmark(repo), "tiny.serve", repo)
    monkeypatch.setattr(spec, "REPO", repo)
    rc = harness.main(["--workload", "tiny.serve", "--seed", "1",
                       "--seconds", "1"])
    err = capsys.readouterr().err.strip()
    assert rc == 2 and says in err and len(err.splitlines()) == 1
