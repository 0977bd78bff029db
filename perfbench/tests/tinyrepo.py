"""A throwaway checkout for rehearsals, tests and the builder's readings
on the chip: a copy of the benchmark's tree beside a link to the program,
to which model modules, configurations, traffic mixes, cells and per-layer
metrics are ADDED as files plus entries of ``BENCHMARK.json`` — no file of
the harness is touched, which is the proof that later PRs can do the same.
An ``extra`` is a directory with any of ``models/``, ``configs/``,
``traffic/``, ``metrics/`` and an ``entries.json``; the default adds the
toy cells (``tiny``: another size of the architecture the benchmark has)
and then the toy mixture of experts (``moe``: another architecture, on
``tiny``'s traffic)."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
REPO = ROOT.parent
TINY = HERE / "data" / "tiny"
MOE = HERE / "data" / "moe"


def make(tmp: Path, *extras: Path) -> Path:
    tmp = Path(tmp)
    shutil.copytree(ROOT, tmp / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "tony_tpu").symlink_to(REPO / "tony_tpu")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for extra in extras or (TINY, MOE):
        add(tmp, bench, extra)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def add(tmp: Path, bench: dict, extra: Path) -> None:
    for sub in ("models", "configs", "traffic", "metrics"):
        if (extra / sub).is_dir():
            for f in (extra / sub).iterdir():
                shutil.copy(f, tmp / "perfbench" / sub / f.name)
    entries = json.loads((extra / "entries.json").read_text())
    # a configuration's plain reference sits beside its file: the extra's
    # own where it brings one, else one of the repo's
    for name, ref in entries["references"].items():
        own = extra / "configs" / f"{ref}.reference.py"
        shutil.copy(own if own.is_file()
                    else ROOT / "configs" / f"{ref}.reference.py",
                    tmp / "perfbench" / "configs" / f"{name}.reference.py")
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += entries[key]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:     # setup_s has none: every cell reports it
            m["workloads"] = m["workloads"] + entries[
                "extend_workloads"].get(m["name"], [])
