"""The builder's readings on the chip that the one command has no option
for: a cell's CONTROL put in the program's place (the run's own comparison
must then say ``correct: false`` against the committed limits), and a
VARIANT of a cell — other traffic parameters (an ``order_seed``, a rate for
the knee's sweep) or other ``--conf`` keys of its job — added as files
plus entries to a throwaway checkout, the committed files untouched. One
line of JSON per run; never a cell's measurement, and run on no CPU.

    python3 perfbench/tests/onchip.py <workload> --seconds 20 --seeds 5,6,7 \\
        [--control fp8] [--traffic rate_rps=2.5 ...] [--conf tony.serving.prefill-chunk=64 ...]
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))
import run as harness  # noqa: E402
import tinyrepo  # noqa: E402
from yardstick import spec  # noqa: E402


def pairs(items) -> dict:
    return {k: json.loads(v) for k, v in (i.split("=", 1) for i in items)}


def variant_repo(tmp: Path, cell: spec.Cell, bench: dict, traffic: dict,
                 conf: dict) -> tuple[Path, str]:
    """A checkout in which ``<cell>.variant`` is the cell with the given
    traffic parameters and job conf keys changed."""
    extra, name = tmp / "extra", f"{cell.name}.variant"
    for sub in ("configs", "traffic", "metrics"):
        (extra / sub).mkdir(parents=True)
    config = dict(cell.config, conf=dict(cell.config["conf"], **conf))
    (extra / "configs" / "variant.json").write_text(json.dumps(config))
    (extra / "traffic" / "variant.json").write_text(
        json.dumps(dict(cell.traffic, **traffic)))
    (extra / "entries.json").write_text(json.dumps({
        "configs": [{"name": "variant", "source": "none (a builder's reading)",
                     "file": "perfbench/configs/variant.json", "reduced": [],
                     "why": f"{cell.config_name} with {conf}"}],
        "workloads": [dict(cell.entry, name=name, config="variant",
                           traffic="variant")],
        "per_layer": [], "references": {"variant": cell.config_name},
        "extend_workloads": {
            m["name"]: [name] for m in bench["end_to_end"] + bench["per_layer"]
            if cell.name in m.get("workloads", ())}}))
    return tinyrepo.make(tmp / "repo", extra), name


def queue_depths(kept: Path, stages) -> dict:
    """Queue depth and active slots over the window's first and last two
    seconds, from the job's own samples of ``ServingEngine.stats()``."""
    job = json.loads((kept / "window.json").read_text())
    t0, t1 = stages.at("window_start"), stages.at("window_end")
    out = {}
    for label, (a, b) in (("start", (t0, t0 + 2)), ("end", (t1 - 2, t1))):
        rows = [r for r in job.get("occupancy", ()) if a <= r[0] < b]
        if rows:
            out[f"queue_{label}"] = statistics.mean(r[2] for r in rows)
            out[f"active_{label}"] = statistics.mean(r[1] for r in rows)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control")
    ap.add_argument("--traffic", nargs="*", default=[])
    ap.add_argument("--conf", nargs="*", default=[])
    args = ap.parse_args()
    traffic, conf = pairs(args.traffic), pairs(args.conf)
    # a fixed path: the checkout's path is part of the compile cache's key
    tmp = Path(tempfile.gettempdir()) / "perfbench-onchip"
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    try:
        repo, workload = spec.REPO, args.workload
        if traffic or conf:
            bench = spec.load_benchmark()
            repo, workload = variant_repo(
                tmp, spec.Cell(bench, workload), bench, traffic, conf)
        for seed in (int(s) for s in args.seeds.split(",")):
            kept = tmp / f"work-{seed}"
            try:
                done = harness.run_cell(repo, workload, seed, args.seconds,
                                        False, control=args.control,
                                        keep_work=kept)
            except harness.BenchFailure as exc:
                print(json.dumps({"workload": workload, "seed": seed,
                                  "failure": str(exc)}), flush=True)
                continue
            result = done["result"]
            stages = done["stages"]
            # setup_s counts from this process's start, so it is left out:
            # the stage of set-up that wanders is given in its place
            line = {"workload": workload, "seed": seed, "traffic": traffic,
                    "conf": conf, "control": args.control,
                    "correct": result["correct"],
                    "attempted": result["attempted"],
                    "failed": result["failed"],
                    "metrics": {k: v["value"]
                                for k, v in result["metrics"].items()
                                if k != "setup_s"},
                    "main_to_devices_s": (stages.at("devices")
                                          - stages.at("script_main")),
                    **queue_depths(kept, stages),
                    "info": result["info"], "compared": result["compared"]}
            print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
