#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tony_tpu still starts on the chip.

Drives the main path once on ONE TPU chip, through the entry points a user
would call, and checks what comes out by the repo's own means. It fails
(non-zero exit, no result line) when JAX finds no TPU — there is no CPU
fallback — and in a directory that holds nothing else of the repo.

Default phases, one after another, each in a process of its own (a chip
belongs to one process at a time; this parent never initialises JAX):

1. kernels — flash attention forward, backward (``jax.grad``) and RMSNorm
   at the flagship widths against the blockwise / plain-JAX references;
   each lowering must hold the Mosaic custom call.
2. train   — ``python -m tony_tpu.client.cli local --executes
   examples/lm_train.py`` at the flagship 200M widths (depth as published,
   8 layers) on a seeded motif corpus streamed through the framework
   reader: job SUCCEEDED, the user process holds the TPU, loss descended
   (the script's own exit code), a complete checkpoint exists.
3. serve   — ``examples/lm_serve.py`` through the same submitter restores
   that checkpoint; ``/healthz``, a few concurrent ``POST /generate`` of
   different prompt lengths through the URL the executor registered,
   ``/shutdown``, job SUCCEEDED. Then ``examples/lm_generate.py`` as the
   next job decodes the same prompts greedily; tokens must be equal.
4. step-1b — the widest supported model (1.0B: d_model 2048, 13 layers,
   16 heads x 128, adafactor, batch 4 x 2048) in-process through
   ``make_train_step`` for three fenced steps.

``--multichip`` needs four chips and runs ONLY the sharded train steps and
what they are compared with: flagship widths (depth cut to 2 layers) under
dp=2 x tp=2 (GSPMD trunk) and dp=2 x sp=2 (ring attention) against the
same seed and batch on one device, in one process that drives all four.

The last line of stdout is ``{"ok": true, "device": {"platform": "tpu",
"kind": "...", "count": N}}`` with the device as a chip-holding child
reported it. The compile cache goes wherever the environment puts it
(``JAX_COMPILATION_CACHE_DIR``, else the repo's fixed default); this script
sets none.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
import uuid
from pathlib import Path

REPO = Path(__file__).resolve().parent
BUDGET_S = 1150  # of the contract's 1200, compilation included
DEFAULT_PHASES = ("kernels", "train", "serve", "step-1b")

# Flagship 200M widths through the flags lm_train.py already has
# (head_dim = d_model / n_heads = 64, d_ff = 4 * d_model).
MODEL_FLAGS = ("--d-model 1024 --n-layers 8 --n-heads 16 --n-kv-heads 4 "
               "--vocab 32000 --dtype bfloat16")
VOCAB, SEQ, BATCH = 32_000, 2048, 8
TRAIN_STEPS = 40
MAX_NEW = 16
PROMPT_LENGTHS = (24, 40, 72)

# (batch, seq, heads, kv heads, head_dim): the 200M train shape with its
# GQA 4, the head_dim-128 shape at 2k, and the same at 8k.
FLASH_SHAPES = ((8, 2048, 16, 4, 64), (8, 2048, 8, 8, 128),
                (2, 8192, 8, 8, 128))
# RMSNorm at train row counts of the 200M and 1B widths, and at a decode
# step's.
RMS_SHAPES = ((8, 2048, 1024), (4, 2048, 2048), (8, 1, 2048))
# bench_transformer_1b's configuration; the model sees 2048 positions.
ONE_B = dict(
    vocab_size=32_000, d_model=2048, n_layers=13, n_heads=16, head_dim=128,
    d_ff=8192, max_seq=2048, dtype="bfloat16", remat=False,
    layer_scan_unroll=13,
)
ONE_B_BATCH = 4
# Flagship widths for --multichip, depth cut from 8.
MULTICHIP = dict(
    vocab_size=VOCAB, d_model=1024, n_layers=2, n_heads=16, head_dim=64,
    d_ff=4096, max_seq=SEQ, n_kv_heads=4, dtype="bfloat16", remat=False,
)

# One task per job: nothing partitions a chip among local executors, and
# the default conf would add a ps task that opens the chip as well.
ONE_WORKER = ["tony.worker.instances=1", "tony.ps.instances=0"]

DEVICE_RE = re.compile(
    r'platform=(\S+) device_kind="([^"]*)" device_count=(\d+)'
)
# Relative to the reference's largest magnitude; bf16 operands, fp32
# accumulation on both sides (the kernel rounds p to bf16 before p·V).
KERNEL_TOL = 2e-2
# Sharded vs one-device loss, per step, relative.
MULTICHIP_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def _echo(who: str, line: str, log) -> None:
    """Every line goes to the phase's log file; XLA warnings that run to
    kilobytes stay out of stdout."""
    log.write(line + "\n")
    log.flush()
    if len(line) < 400:
        print(f"  {who}| {line}", flush=True)


# ---------------------------------------------------------------------------
# Parent: process plumbing (no jax in this process, ever)
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, seed: int, log_dir: str | None) -> None:
        self.seed = seed
        self.t0 = time.monotonic()
        # Every process this script starts inherits the marker, so a
        # failed run can find and stop all of them, however they were
        # re-parented (client -> coordinator -> executor -> user script).
        self.marker = f"CHIP_SMOKE_RUN={uuid.uuid4().hex}"
        self.tmp = tempfile.TemporaryDirectory(prefix="chip-smoke-")
        self.work = Path(self.tmp.name)
        self.log_dir = Path(log_dir) if log_dir else self.work
        self.log_dir.mkdir(parents=True, exist_ok=True)
        self.devices: list[tuple[str, dict]] = []

    def say(self, msg: str) -> None:
        print(f"[{time.monotonic() - self.t0:6.1f}s] {msg}", flush=True)

    def remaining(self, cap: float) -> float:
        left = BUDGET_S - (time.monotonic() - self.t0)
        if left <= 5:
            raise SmokeFailure("out of time budget")
        return min(cap, left)

    def env(self) -> dict[str, str]:
        env = dict(os.environ)
        key, _, val = self.marker.partition("=")
        env[key] = val
        env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        # The runtime D2H transfer guard around every instrumented step:
        # vacuous on the CPU backend, real here.
        env["TONY_JIT_SANITIZER"] = "1"
        return env

    # -- marked processes ---------------------------------------------------
    def marked_pids(self) -> list[int]:
        needle = self.marker.encode()
        out = []
        for entry in os.listdir("/proc"):
            if not entry.isdigit() or int(entry) == os.getpid():
                continue
            try:
                with open(f"/proc/{entry}/environ", "rb") as f:
                    if needle in f.read().split(b"\0"):
                        out.append(int(entry))
            except OSError:
                continue  # gone, or not ours to read
        return out

    def wait_gone(self, grace_s: float = 20.0) -> None:
        """The next phase needs the chip: the previous one's processes
        must all have exited, not merely its front process."""
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline:
            if not self.marked_pids():
                return
            time.sleep(0.2)
        raise SmokeFailure(
            f"processes still alive after their phase: {self.marked_pids()}"
        )

    def stop_all(self) -> None:
        for sig in (signal.SIGTERM, signal.SIGKILL):
            pids = self.marked_pids()
            if not pids:
                return
            for pid in pids:
                try:
                    os.kill(pid, sig)
                except OSError:
                    pass
            time.sleep(1.0)

    # -- running things -----------------------------------------------------
    def run(self, name: str, argv: list[str], timeout: float,
            on_line=None) -> tuple[int, list[str]]:
        """Run one child to its end, echoing its output; returns (exit
        code, lines). ``on_line`` sees every line as it arrives."""
        lines: list[str] = []
        log = open(self.log_dir / f"{name}.log", "w")
        proc = subprocess.Popen(
            argv, cwd=REPO, env=self.env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, errors="replace",
        )

        def pump():
            for line in proc.stdout:
                line = line.rstrip("\n")
                lines.append(line)
                _echo(name, line, log)
                if on_line is not None:
                    on_line(line)

        reader = threading.Thread(target=pump, daemon=True)
        reader.start()
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop_all()
            proc.kill()
            raise SmokeFailure(f"{name}: no end after {timeout:.0f}s")
        finally:
            reader.join(timeout=10)
            log.close()
        return rc, lines

    def run_child(self, name: str, timeout: float) -> dict:
        """An in-process phase: this file again, with ``--child``. Its
        ``DEVICE {...}`` line is the device it held."""
        rc, lines = self.run(
            name, [sys.executable, str(Path(__file__).resolve()),
                   "--child", name, "--seed", str(self.seed)],
            self.remaining(timeout),
        )
        reports = [json.loads(l[len("DEVICE "):]) for l in lines
                   if l.startswith("DEVICE ")]
        if rc != 0:
            raise SmokeFailure(f"{name}: exit code {rc}")
        if len(reports) != 1:
            raise SmokeFailure(f"{name}: {len(reports)} device reports")
        self.wait_gone()
        return reports[0]

    def run_job(self, name: str, script: str, confs: list[str],
                task_params: str, timeout: float, while_running=None
                ) -> tuple[dict, list[str]]:
        """One job through the normal submitter. Task logs live in the
        mini-cluster's throwaway staging dir, so they are tailed while the
        job runs. ``while_running(url)`` is called once the executor has
        registered the task's URL (serving jobs). Returns the device the
        user process reported, and the task log lines."""
        task_lines: list[str] = []
        state = {"app_dir": None, "url": None, "succeeded": False}
        url_ready = threading.Event()
        done = threading.Event()

        def on_line(line: str) -> None:
            m = re.search(r"staged application \S+ at (\S+)", line)
            if m:
                state["app_dir"] = Path(m.group(1))
            m = re.search(r"tensorboard/profiler: (http://\S+)", line)
            if m:
                state["url"] = m.group(1)
                url_ready.set()
            if "application finished: SUCCEEDED" in line:
                state["succeeded"] = True

        def tail_task_logs() -> None:
            offsets: dict[Path, int] = {}
            with open(self.log_dir / f"{name}.tasks.log", "w") as log:
                while True:
                    finished = done.is_set()
                    app_dir = state["app_dir"]
                    for path in (sorted((app_dir / "logs").glob("*.log"))
                                 if app_dir else ()):
                        try:
                            with open(path, "rb") as f:
                                f.seek(offsets.get(path, 0))
                                chunk = f.read()
                        except OSError:
                            continue
                        # Only whole lines; the rest is read next time.
                        whole, newline, _ = chunk.rpartition(b"\n")
                        offsets[path] = (offsets.get(path, 0)
                                         + len(whole) + len(newline))
                        for line in whole.decode(errors="replace").splitlines():
                            task_lines.append(line)
                            _echo(f"{name}:{path.stem}", line, log)
                    if finished:
                        return
                    # The staging dir is deleted as the submitter exits:
                    # poll fast enough to see a script's last lines.
                    time.sleep(0.05)

        tailer = threading.Thread(target=tail_task_logs, daemon=True)
        tailer.start()
        driver_error: list[Exception] = []

        def drive() -> None:
            if not url_ready.wait(timeout):
                return
            try:
                while_running(state["url"])
            except Exception as exc:  # re-raised below, in the parent
                driver_error.append(exc)
                self.stop_all()

        driver = None
        if while_running is not None:
            driver = threading.Thread(target=drive, daemon=True)
            driver.start()
        argv = [
            sys.executable, "-m", "tony_tpu.client.cli", "local",
            "--executes", str(REPO / "examples" / script),
            "--framework", "jax", "--python_binary_path", sys.executable,
            *[a for c in confs for a in ("--conf", c)],
            "--task_params", task_params,
        ]
        try:
            rc, _ = self.run(name, argv, self.remaining(timeout), on_line)
        finally:
            done.set()
            tailer.join(timeout=10)
        if driver is not None:
            driver.join(timeout=10)
        if driver_error:
            raise SmokeFailure(f"{name}: {driver_error[0]!r}")
        if rc != 0 or not state["succeeded"]:
            raise SmokeFailure(
                f"{name}: submitter exit code {rc}, SUCCEEDED "
                f"{'seen' if state['succeeded'] else 'not seen'}"
            )
        self.wait_gone()
        # Exactly one process of the job — the user script — holds and
        # reports the device; client, coordinator and executor import no
        # jax (tests/test_client.py).
        reports = [m.groups() for m in map(DEVICE_RE.search, task_lines) if m]
        if len(reports) != 1:
            raise SmokeFailure(
                f"{name}: {len(reports)} device reports in the task logs"
            )
        platform, kind, count = reports[0]
        device = {"platform": platform, "kind": kind, "count": int(count)}
        return device, task_lines

    def held(self, phase: str, device: dict, want_count: int) -> None:
        self.say(f"{phase}: device {json.dumps(device)}")
        if device["platform"] != "tpu":
            raise SmokeFailure(
                f"{phase} ran on {device['platform']!r}, not on a TPU"
            )
        if device["count"] != want_count:
            raise SmokeFailure(
                f"{phase} saw {device['count']} devices, wants {want_count}"
            )
        self.devices.append((phase, device))


# ---------------------------------------------------------------------------
# Parent: the orchestrated phases
# ---------------------------------------------------------------------------


def motif_corpus(seed: int):
    """Seeded corpus the LM can learn in a few tens of steps: 64 documents,
    each an 8-token motif repeated to seq+1 tokens with 10% of positions
    replaced by noise, four noisy copies of each. Every motif token is
    unique, so the next token is determined by the current one. Returns
    (records uint16 [256, seq+1], motifs [64, 8])."""
    import numpy as np

    rng = np.random.default_rng(seed)
    motifs = rng.choice(np.arange(1, VOCAB), size=(64, 8), replace=False)
    reps = -(-(SEQ + 1) // 8)
    docs = np.tile(motifs, (4, reps))[:, : SEQ + 1]
    noise = rng.integers(1, VOCAB, size=docs.shape)
    docs = np.where(rng.random(docs.shape) < 0.10, noise, docs)
    return docs[rng.permutation(len(docs))].astype(np.uint16), motifs


def phase_train(smoke: Smoke) -> dict:
    from tony_tpu.checkpoint import layout, stores
    from tony_tpu.io import native

    records, _ = motif_corpus(smoke.seed)
    corpus = smoke.work / "corpus.tokens"
    records.tofile(corpus)
    # native/libtony_io.so is git-ignored and loaded only when present;
    # the user process decides by the same rule, and the smoke needs
    # neither path in particular.
    smoke.say("train: reader path "
              + ("native (libtony_io.so)" if native.available()
                 else "python (native/libtony_io.so not built)"))
    ckpt = smoke.work / "ckpt"
    device, _ = smoke.run_job(
        "train", "lm_train.py", ONE_WORKER,
        f"{MODEL_FLAGS} --seq {SEQ} --batch {BATCH} --steps {TRAIN_STEPS} "
        f"--checkpoint-every {TRAIN_STEPS} --data {corpus} "
        f"--ckpt-dir {ckpt}",
        timeout=480,
    )
    steps = layout.complete_steps(stores.store_for(ckpt))
    if steps[-1:] != [TRAIN_STEPS]:
        raise SmokeFailure(
            f"train: no complete checkpoint at step {TRAIN_STEPS} under "
            f"{ckpt} (complete: {steps})"
        )
    smoke.say(f"train: complete checkpoint at step {steps[-1]}")
    return device


def _http(url: str, body: dict | None = None, timeout: float = 300.0) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def phase_serve(smoke: Smoke) -> list[dict]:
    _, motifs = motif_corpus(smoke.seed)
    prompts = [
        [int(t) for t in list(motifs[doc]) * (n // 8)]
        for doc, n in zip((3, 17, 42), PROMPT_LENGTHS)
    ]
    ckpt = smoke.work / "ckpt"
    served: list[list[int] | None] = [None] * len(prompts)

    def requests(url: str) -> None:
        # The URL is registered when the task launches; the server
        # listens once the checkpoint is restored.
        for _ in range(300):
            try:
                health = _http(f"{url}/healthz", timeout=5.0)
                break
            except OSError:
                time.sleep(1.0)
        else:
            raise SmokeFailure(f"{url}/healthz never answered")
        smoke.say(f"serve: {url}/healthz -> {json.dumps(health)[:200]}")
        errors = []

        def one(i: int) -> None:
            try:
                out = _http(f"{url}/generate", {
                    "prompt": prompts[i], "max_new_tokens": MAX_NEW,
                    "temperature": 0.0,
                })
                served[i] = [int(t) for t in out["tokens"]]
                smoke.say(
                    f"serve: request {i} (prompt {len(prompts[i])}) -> "
                    f"{out['length']} tokens, ttft {out['ttft_ms']:.0f} ms, "
                    f"wall {out['wall_ms']:.0f} ms (first request of each "
                    f"shape includes compilation)"
                )
            except Exception as exc:
                errors.append(exc)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        try:
            _http(f"{url}/shutdown", {})
        finally:
            if errors:
                raise errors[0]

    serve_device, _ = smoke.run_job(
        "serve", "lm_serve.py",
        ["tony.serving.instances=1", "tony.worker.instances=0",
         "tony.ps.instances=0", "tony.chief.name=serving"],
        f"{MODEL_FLAGS} --ckpt {ckpt} --max-seq 512",
        timeout=420, while_running=requests,
    )
    for i, toks in enumerate(served):
        if toks is None or len(toks) != MAX_NEW or not all(
                0 <= t < VOCAB for t in toks):
            raise SmokeFailure(f"serve: request {i} returned {toks}")

    # The reference: single-request greedy decoding of the same
    # checkpoint and prompts (lm_generate decodes each prompt length on
    # its own, unpadded) — the equality tests/test_serving.py pins on CPU.
    generate_device, task_lines = smoke.run_job(
        "generate", "lm_generate.py", ONE_WORKER,
        f"{MODEL_FLAGS} --ckpt {ckpt} --max-seq 512 --max-new {MAX_NEW} "
        f"--prompt {':'.join(','.join(map(str, p)) for p in prompts)}",
        timeout=300,
    )
    want = {}
    for line in task_lines:
        m = re.search(r"generated\[(\d+)\]: ([\d,]+)", line)
        if m:
            want[int(m.group(1))] = [int(t) for t in m.group(2).split(",")]
    for i, toks in enumerate(served):
        if want.get(i) != toks:
            raise SmokeFailure(
                f"serve: request {i}: engine tokens {toks} != single-"
                f"request greedy tokens {want.get(i)}"
            )
    smoke.say(f"serve: {len(served)} requests token-exact against "
              f"lm_generate ({MAX_NEW} greedy tokens each)")
    # Information only: every prompt ends on a motif boundary, so a model
    # that learned the corpus goes round its document's motif again.
    learned = sum(
        tok == int(motifs[doc][j % 8])
        for doc, toks in zip((3, 17, 42), served) for j, tok in enumerate(toks)
    )
    smoke.say(f"serve: {learned} of {len(served) * MAX_NEW} served tokens "
              f"continue their document's motif (information only)")
    return [serve_device, generate_device]


# ---------------------------------------------------------------------------
# Children: the phases that hold the chip in-process
# ---------------------------------------------------------------------------


def _hold_tpu(want_count: int):
    """Open the backend, report it, and refuse anything but a TPU."""
    import jax

    from tony_tpu.parallel.plan import configure_compile_cache

    configure_compile_cache()
    devs = jax.devices()
    print("DEVICE " + json.dumps({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }), flush=True)
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: jax found {devs[0].platform!r}")
    if len(devs) != want_count:
        sys.exit(f"need {want_count} TPU device(s), jax found {len(devs)}")
    return devs


def _rel_err(got, want) -> float:
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree.leaves(jax.device_get(got)),
                    jax.tree.leaves(jax.device_get(want))):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if not np.isfinite(g).all():
            return float("inf")
        worst = max(worst, float(np.max(np.abs(g - w)) / np.max(np.abs(w))))
    return worst


def _mosaic_calls(jitted, *args) -> int:
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def _bytes_in_use(device) -> int:
    return (device.memory_stats() or {}).get("bytes_in_use", 0)


def _require_mosaic(what: str, step_fn, *args) -> None:
    calls = _mosaic_calls(step_fn, *args)
    print(f"{what}: {calls} Mosaic calls in the lowered step", flush=True)
    if not calls:
        sys.exit(f"{what}: no Mosaic call — the blockwise fallback was "
                 f"lowered")


def child_kernels(seed: int) -> None:
    _hold_tpu(1)
    import jax
    import jax.numpy as jnp

    from tony_tpu.ops import flash_attention, rms_norm
    from tony_tpu.parallel.plan import compile_cache_summary

    failures = []

    def check(what: str, err: float, calls: int, want_calls: int) -> None:
        ok = err <= KERNEL_TOL and calls >= want_calls
        print(f"{what}: rel err {err:.2e} (tol {KERNEL_TOL:.0e}), "
              f"{calls} Mosaic call(s) lowered -> {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            failures.append(what)

    for b, t, h, h_kv, d in FLASH_SHAPES:
        keys = jax.random.split(jax.random.key(seed), 4)
        q = jax.random.normal(keys[0], (b, t, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, t, h_kv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, t, h_kv, d), jnp.bfloat16)
        ct = jax.random.normal(keys[3], (b, t, h, d), jnp.float32)
        name = f"flash {h}x{d} kv{h_kv} seq {t}"

        def fwd(q, k, v, force_jax=False):
            return flash_attention(q, k, v, force_jax=force_jax)

        def loss(q, k, v, force_jax=False):
            return jnp.sum(fwd(q, k, v, force_jax).astype(jnp.float32) * ct)

        kernel_fwd = jax.jit(fwd)
        check(f"{name} fwd",
              _rel_err(kernel_fwd(q, k, v),
                       jax.jit(lambda *a: fwd(*a, True))(q, k, v)),
              _mosaic_calls(kernel_fwd, q, k, v), 1)
        kernel_bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        check(f"{name} bwd (jax.grad)",
              _rel_err(kernel_bwd(q, k, v),
                       jax.jit(jax.grad(lambda *a: loss(*a, True),
                                        argnums=(0, 1, 2)))(q, k, v)),
              _mosaic_calls(kernel_bwd, q, k, v), 3)

    for shape in RMS_SHAPES:
        keys = jax.random.split(jax.random.key(seed + 1), 2)
        x = jax.random.normal(keys[0], shape, jnp.bfloat16)
        w = 1.0 + 0.1 * jax.random.normal(keys[1], shape[-1:], jnp.float32)
        kernel = jax.jit(rms_norm)
        check(f"rms_norm {shape}",
              _rel_err(kernel(x, w),
                       jax.jit(lambda x, w: rms_norm(x, w, force_jax=True))(
                           x, w)),
              _mosaic_calls(kernel, x, w), 1)

    print(compile_cache_summary(), flush=True)
    if failures:
        sys.exit(f"kernels failed: {failures}")


def child_step_1b(seed: int) -> None:
    devs = _hold_tpu(1)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tony_tpu.models import TransformerConfig, make_train_step
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh
    from tony_tpu.parallel.plan import compile_cache_summary

    cfg = TransformerConfig(**ONE_B)
    mesh = build_mesh(MeshSpec(), devices=devs[:1])
    init_fn, step_fn = make_train_step(
        cfg, mesh, optimizer=optax.adafactor(1e-3)
    )
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (ONE_B_BATCH, cfg.max_seq + 1)),
        jnp.int32,
    )
    losses = []
    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(seed))
        n_params = sum(x.size for x in jax.tree.leaves(state.params))
        for i in range(3):
            t0 = time.perf_counter()
            state, metrics = step_fn(state, tokens)
            jax.block_until_ready((state, metrics))
            dt = time.perf_counter() - t0
            losses.append(float(jax.device_get(metrics["loss"])))
            print(f"step-1b: step {i + 1} loss {losses[-1]:.4f} "
                  f"wall {dt * 1e3:.1f} ms"
                  + (" (includes compilation)" if i == 0 else ""),
                  flush=True)
    stats = devs[0].memory_stats() or {}
    print(f"step-1b: {n_params / 1e9:.3f}B params, peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')} "
          f"(information only)", flush=True)
    print(compile_cache_summary(), flush=True)
    # Random tokens, one batch fed three times: the loss starts near
    # ln(vocab) and must fall.
    if not (np.isfinite(losses).all() and losses[-1] < losses[0]
            and abs(losses[0] - np.log(cfg.vocab_size)) < 1.5):
        sys.exit(f"step-1b: losses {losses}")


def child_multichip(seed: int) -> None:
    devs = _hold_tpu(4)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models import TransformerConfig, make_train_step
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh
    from tony_tpu.parallel.plan import compile_cache_summary

    cfg = TransformerConfig(**MULTICHIP)
    print(f"multichip: flagship 200M widths, depth cut to {cfg.n_layers} "
          f"of 8 layers", flush=True)
    tokens = jnp.asarray(
        np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (BATCH, cfg.max_seq + 1)),
        jnp.int32,
    )

    def run(name: str, spec: MeshSpec, devices) -> list[float]:
        mesh = build_mesh(spec, devices=devices)
        init_fn, step_fn = make_train_step(cfg, mesh, learning_rate=1e-2)
        losses = []
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(seed))
            _require_mosaic(f"multichip {name}", step_fn, state, tokens)
            for _ in range(3):
                state, metrics = step_fn(state, tokens)
                losses.append(float(jax.device_get(metrics["loss"])))
            if len(devices) > 1:
                _check_spread(name, state.params, devices)
        print(f"multichip {name}: losses "
              f"{[round(l, 4) for l in losses]}", flush=True)
        return losses

    def _check_spread(name, params, devices) -> None:
        """Code that has only ever seen one real chip may put everything
        on the first: every device must hold a shard of every leaf, a
        proper slice wherever the layout splits it, and live memory."""
        split = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            shards = leaf.addressable_shards
            if {s.device for s in shards} != set(devices):
                sys.exit(f"multichip {name}: {path} lives on "
                         f"{sorted(s.device.id for s in shards)}")
            split += shards[0].data.size < leaf.size
        in_use = [_bytes_in_use(d) for d in devices]
        print(f"multichip {name}: {split} parameter leaves split across "
              f"devices, bytes_in_use per device {in_use}", flush=True)
        if not split or not all(in_use):
            sys.exit(f"multichip {name}: parameters are not spread")

    sharded = {
        "dp2xtp2 (GSPMD trunk)": run("dp2xtp2", MeshSpec(dp=2, tp=2), devs),
        "dp2xsp2 (ring attention)": run("dp2xsp2", MeshSpec(dp=2, sp=2),
                                        devs),
    }
    want = run("one device", MeshSpec(), devs[:1])
    failures = []
    for name, got in sharded.items():
        errs = [abs(g - w) / abs(w) for g, w in zip(got, want)]
        ok = np.isfinite(got).all() and max(errs) <= MULTICHIP_TOL
        print(f"multichip {name}: per-step rel err vs one device "
              f"{[f'{e:.1e}' for e in errs]} (tol {MULTICHIP_TOL:.0e}) -> "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            failures.append(name)
    print(compile_cache_summary(), flush=True)
    if failures or not want[-1] < want[0]:
        sys.exit(f"multichip failed: {failures or want}")


CHILDREN = {
    "kernels": child_kernels,
    "step-1b": child_step_1b,
    "multichip": child_multichip,
}


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--multichip", action="store_true",
                   help="needs four chips; runs only the sharded train "
                        "steps and their one-device comparison")
    p.add_argument("--phases", default=",".join(DEFAULT_PHASES),
                   help="comma-separated subset of the default phases "
                        "(serve needs train's checkpoint)")
    p.add_argument("--seed", type=int, default=0,
                   help="seeds the corpus, the prompts and every random "
                        "input")
    p.add_argument("--log-dir", default="",
                   help="keep the phases' full logs here (default: a "
                        "temporary directory)")
    p.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.child:
        CHILDREN[args.child](args.seed)
        return 0

    smoke = Smoke(args.seed, args.log_dir or None)
    phases = ["multichip"] if args.multichip else args.phases.split(",")
    want_count = 4 if args.multichip else 1
    try:
        for phase in phases:
            smoke.say(f"phase {phase}")
            if phase == "train":
                reports = [phase_train(smoke)]
            elif phase == "serve":
                reports = phase_serve(smoke)
            elif phase in CHILDREN:
                reports = [smoke.run_child(phase, timeout=480)]
            else:
                raise SmokeFailure(f"unknown phase {phase!r}")
            for device in reports:
                smoke.held(phase, device, want_count)
            smoke.say(f"phase {phase} passed")
    except SmokeFailure as exc:
        smoke.say(f"FAILED: {exc}")
        return 1
    finally:
        smoke.stop_all()
        smoke.tmp.cleanup()

    first = smoke.devices[0][1]
    if any(device != first for _, device in smoke.devices):
        smoke.say(f"FAILED: phases disagree on the device: {smoke.devices}")
        return 1
    # One process per chip: this parent only ever started children.
    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    print(json.dumps({"ok": True, "device": first}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
