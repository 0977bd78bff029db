"""What both job scripts share: the loader of a configuration's plain
reference (``configs/<name>.reference.py``, beside its file). Not a job
kind (a job kind is ``<kind>.py`` without the underscore)."""

from __future__ import annotations

from pathlib import Path

from yardstick import spec


def load_reference(config_path: str):
    path = Path(config_path).with_suffix("").as_posix() + ".reference.py"
    return spec.load_module(path, "perfbench_reference")
