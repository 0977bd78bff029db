"""Submits one job through the program's normal path and follows it:
``python -m tony_tpu.client.cli local --executes <job script> ...`` in a
child, every process of the job marked through the environment so that a
run ends only when all of them are gone (the chip is then free for the
next run). The pattern is ``chip_smoke.py``'s ``run_job``, copied so that
the yardstick does not move with the program. Imports no jax."""

from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import threading
import time
import uuid
from pathlib import Path

# Lines of the client's output that mark a stage of set-up.
STAGE_LINES = (
    ("application_staged", re.compile(r"staged application \S+ at (\S+)")),
    ("coordinator_address_known", re.compile(r"RPC server listening on")),
    ("executor_launched", re.compile(r"launched \S+ as pid")),
    ("task_registered", re.compile(r"registered \S+ at")),
    ("application_finished", re.compile(r"application finished: (\S+)")),
)


class Job:
    def __init__(self, repo: Path, script: Path, confs: list[str],
                 task_params: str, log_path: Path, on_stage) -> None:
        self.marker = ("PERFBENCH_RUN", uuid.uuid4().hex)
        self.on_stage = on_stage
        self.app_dir: Path | None = None
        self.final_state: str | None = None
        self._log = open(log_path, "w")
        self._task_log_dir: Path | None = None
        env = dict(os.environ)
        env[self.marker[0]] = self.marker[1]
        env["PYTHONPATH"] = f"{repo}{os.pathsep}" + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        argv = [sys.executable, "-m", "tony_tpu.client.cli", "local",
                "--executes", str(script), "--framework", "jax",
                "--python_binary_path", sys.executable,
                *[a for c in confs for a in ("--conf", c)],
                "--task_params", task_params]
        self.submitted_at = time.time()
        self.proc = subprocess.Popen(
            argv, cwd=repo, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, errors="replace")
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()
        self.task_lines: list[str] = []
        self._tail_stop = threading.Event()
        self._tailer = threading.Thread(target=self._tail, daemon=True)
        self._tailer.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._log.write(line)
            self._log.flush()
            for name, rx in STAGE_LINES:
                m = rx.search(line)
                if not m:
                    continue
                if name == "application_staged":
                    self.app_dir = Path(m.group(1))
                if name == "application_finished":
                    self.final_state = m.group(1)
                self.on_stage(name)

    def _tail(self) -> None:
        """The task's own log lives in the mini-cluster's staging dir,
        which is deleted as the submitter exits: copy it while it lasts."""
        offsets: dict[Path, int] = {}
        while True:
            stop = self._tail_stop.is_set()
            logs = (sorted((self.app_dir / "logs").glob("*.log"))
                    if self.app_dir else ())
            for path in logs:
                try:
                    with open(path, "rb") as f:
                        f.seek(offsets.get(path, 0))
                        chunk = f.read()
                except OSError:
                    continue
                whole, newline, _ = chunk.rpartition(b"\n")
                offsets[path] = (offsets.get(path, 0) + len(whole)
                                 + len(newline))
                self.task_lines.extend(
                    whole.decode(errors="replace").splitlines())
            if stop:
                return
            time.sleep(0.05)

    # -- every process of the job ------------------------------------------
    def marked_pids(self) -> list[int]:
        needle = f"{self.marker[0]}={self.marker[1]}".encode()
        out = []
        for entry in os.listdir("/proc"):
            if not entry.isdigit() or int(entry) == os.getpid():
                continue
            try:
                with open(f"/proc/{entry}/environ", "rb") as f:
                    if needle in f.read().split(b"\0"):
                        out.append(int(entry))
            except OSError:
                continue
        return out

    def wait_exit(self, timeout: float) -> int | None:
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None

    def wait_gone(self, grace_s: float = 30.0) -> bool:
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline:
            if not self.marked_pids():
                return True
            time.sleep(0.02)
        return False

    def stop_all(self) -> None:
        for sig in (signal.SIGTERM, signal.SIGKILL):
            pids = self.marked_pids()
            if not pids:
                break
            for pid in pids:
                try:
                    os.kill(pid, sig)
                except OSError:
                    pass
            deadline = time.monotonic() + 5.0
            while self.marked_pids() and time.monotonic() < deadline:
                time.sleep(0.05)

    def close(self) -> None:
        self._tail_stop.set()
        self._tailer.join(timeout=5)
        self._pump.join(timeout=5)
        self._log.close()
