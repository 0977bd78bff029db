"""Rehearsal on the CPU at a toy size: the whole command, no chip. Prints
what a run would print, marked as a rehearsal; never a measurement.

    JAX_PLATFORMS=cpu python3 perfbench/tests/rehearse.py tiny.train 5 [trace] [fault=..] [control=..]
"""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as harness  # noqa: E402
import tinyrepo  # noqa: E402


def main() -> int:
    workload, seconds = sys.argv[1], float(sys.argv[2])
    opts = dict(a.split("=", 1) for a in sys.argv[3:] if "=" in a)
    with tempfile.TemporaryDirectory(prefix="perfbench-tiny-") as tmp:
        repo = tinyrepo.make(Path(tmp) / "repo")
        done = harness.run_cell(
            repo, workload, int(opts.get("seed", 2**31 + 11)), seconds,
            "trace" in sys.argv[3:], require_tpu=False,
            fault=opts.get("fault"), control=opts.get("control"),
            keep_work=Path(opts["keep"]) if "keep" in opts else None)
    done["stages"].print()
    print("REHEARSAL (cpu, toy size; not a measurement):",
          json.dumps(done["result"])[:6000])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
