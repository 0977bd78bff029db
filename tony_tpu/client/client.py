"""Submission client: package, stage, launch the coordinator, monitor.

The analogue of ``TonyClient`` (tony-core/.../TonyClient.java): ``init``
mirrors arg parsing + conf layering (:251-340), ``run`` mirrors the
submit-and-monitor flow (:146-208, :631-672). Differences are substrate,
not shape: the "cluster" is a staging directory (local path or mounted
GCS), and the "AM container" is a coordinator subprocess — on a real
deployment the same command line runs on a TPU-VM instead
(coordinator/backend.py TpuVmBackend plans the slice).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import shutil
import subprocess
import sys
import time
import uuid
from pathlib import Path

from tony_tpu import constants, utils
from tony_tpu.cloud.gcs import is_gs_uri
from tony_tpu.conf import keys
from tony_tpu.conf.configuration import TonyConfiguration, load_job_config
from tony_tpu.rpc.client import ApplicationRpcClient

log = logging.getLogger(__name__)

TERMINAL_STATES = {"SUCCEEDED", "FAILED", "KILLED"}

# Declared metric name (TONY-M001 lints module-scope constants): staged
# venv archives dedup into a sha256-keyed blob store, and every re-submit
# or scheduler-pool re-run of the same venv skips the copy entirely.
STAGING_DEDUP_COUNTER = "tony_staging_dedup_hits_total"


def stage_blob(src: Path, blob_root: Path) -> tuple[Path, bool]:
    """Content-hash staging: copy ``src`` into the shared blob store
    under its sha256 (atomic tmp+rename — concurrent submits of the same
    venv race safely) unless an identical blob is already there.
    Returns ``(blob_path, dedup_hit)``. The blob path — keyed by content,
    not by app — is what the frozen conf ships, so identical artifacts
    are staged once per CLUSTER, not once per job."""
    import hashlib

    h = hashlib.sha256()
    with open(src, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    digest = h.hexdigest()
    suffix = "".join(src.suffixes)[-16:]  # keep .zip/.tar.gz readable
    dest = blob_root / digest[:2] / f"{digest}{suffix}"
    if dest.is_file():
        # Refresh the LRU stamp: a venv in active rotation must survive
        # prune_blob_store however old its first upload is.
        try:
            os.utime(dest)
        except OSError:
            pass
        return dest, True
    dest.parent.mkdir(parents=True, exist_ok=True)
    tmp = dest.parent / f".tmp-{os.getpid()}-{dest.name}"
    shutil.copy2(src, tmp)
    tmp.replace(dest)
    return dest, False


def prune_blob_store(blob_root: Path, max_bytes: int,
                     exclude: Path | None = None) -> int:
    """LRU-prune the content-hash blob store down to ``max_bytes``
    (``tony.staging.blob-store-max-bytes``; 0 = unbounded). Returns the
    number of blobs removed. Best-effort: a blob a concurrently-running
    job still references may be pruned if the cap is set too tight —
    size the cap to a few venv generations."""
    if max_bytes <= 0 or not blob_root.is_dir():
        return 0
    blobs = []
    total = 0
    for p in blob_root.rglob("*"):
        if not p.is_file() or p.name.startswith(".tmp-") or p == exclude:
            continue
        try:
            st = p.stat()
        except OSError:
            continue
        blobs.append((st.st_mtime, st.st_size, p))
        total += st.st_size
    removed = 0
    for _, size, p in sorted(blobs):
        if total <= max_bytes:
            break
        try:
            p.unlink()
        except OSError:
            continue
        total -= size
        removed += 1
    if removed:
        log.info("pruned %d blob(s) from %s (cap %d bytes)", removed,
                 blob_root, max_bytes)
    return removed


def build_arg_parser() -> argparse.ArgumentParser:
    """Common options (Utils.getCommonOptions:208-226)."""
    p = argparse.ArgumentParser(prog="tony-tpu", add_help=True)
    p.add_argument("--executes", help="entry point of the training job")
    p.add_argument("--src_dir", help="directory with job sources to package")
    p.add_argument("--python_venv", help="venv/conda archive to ship")
    p.add_argument("--python_binary_path", help="python inside the venv")
    p.add_argument("--task_params", help="args passed to the entry point")
    p.add_argument("--shell_env", action="append", default=[],
                   help="NAME=VALUE env for the training job (repeatable)")
    p.add_argument("--conf_file", help="job config file (tony.json analogue)")
    p.add_argument("--conf", action="append", default=[],
                   help="key=value override (repeatable)")
    p.add_argument("--app_name", help="application name")
    p.add_argument("--framework", help="jax | tensorflow | pytorch")
    return p


class TonyClient:
    def __init__(self) -> None:
        self.conf = TonyConfiguration()
        self.app_id: str | None = None
        self.app_dir: Path | None = None
        self.job_id: str | None = None  # set by a scheduler-mode submit
        self.coordinator_proc: subprocess.Popen | None = None
        self.rpc: ApplicationRpcClient | None = None
        self._urls_printed = False
        # Injectable for tests (no egress here); None = real GcsStorage.
        self._gcs_store = None

    # -- init (TonyClient.init:251-340) ------------------------------------
    def init(self, argv: list[str]) -> "TonyClient":
        args, _ = build_arg_parser().parse_known_args(argv)
        self.conf = load_job_config(conf_file=args.conf_file, overrides=args.conf)
        # Build stamp rides the frozen conf into every process + history
        # (VersionInfo.injectVersionInfo at TonyClient.java:139).
        from tony_tpu.version import inject_version_info

        inject_version_info(self.conf)
        cli_map = {
            keys.K_EXECUTES: args.executes,
            keys.K_SRC_DIR: args.src_dir,
            keys.K_PYTHON_VENV: args.python_venv,
            keys.K_PYTHON_BINARY: args.python_binary_path,
            keys.K_TASK_PARAMS: args.task_params,
            keys.K_APPLICATION_NAME: args.app_name,
            keys.K_FRAMEWORK: args.framework,
        }
        for key, val in cli_map.items():
            if val:
                self.conf.set(key, val)
        if args.shell_env:
            self.conf.set(keys.K_SHELL_ENV, ",".join(args.shell_env))
        return self

    # -- staging (zipArchive + createAMContainerSpec:369-424, 468-491) ------
    def _stage(self) -> Path:
        staging_conf = self.conf.get_str(keys.K_STAGING_LOCATION)
        gs_staging = is_gs_uri(staging_conf)
        if gs_staging:
            # Remote staging (the HDFS-upload analogue,
            # TonyClient.createAMContainerSpec:374-385): build the app dir
            # locally first — the locally-spawned coordinator reads it from
            # disk — then mirror every artifact to gs://, where TPU-VM
            # bootstraps localize from (cloud/bootstrap.py).
            import tempfile

            staging_root = Path(tempfile.mkdtemp(prefix="tony-staging-"))
        else:
            staging_root = Path(
                staging_conf or Path.cwd() / constants.TONY_STAGING_DIR
            )
        self.app_id = f"application_{int(time.time() * 1000)}_{uuid.uuid4().hex[:8]}"
        app_dir = staging_root / self.app_id
        app_dir.mkdir(parents=True, exist_ok=True)

        src_dir = self.conf.get_str(keys.K_SRC_DIR)
        if src_dir:
            utils.zip_dir(src_dir, app_dir / constants.TONY_ARCHIVE)
        venv = self.conf.get_str(keys.K_PYTHON_VENV)
        if venv and gs_staging:
            # Remote staging keeps the per-app copy: the bootstrap
            # localizes the app dir's objects into the executor cwd, so
            # the bare name must resolve there.
            staged = app_dir / Path(venv).name
            shutil.copy2(venv, staged)
            self.conf.set(keys.K_PYTHON_VENV, staged.name)
        elif venv:
            # Local/shared-FS staging dedups by content hash: executors
            # must unzip a *staged* copy (only the staging location is
            # shared, not the client's home dir), but an identical venv
            # already in the blob store makes the copy — the dominant
            # staging cost for multi-GB conda archives — a no-op on
            # every re-submit and scheduler-pool re-run.
            blob, hit = stage_blob(Path(venv), staging_root / "blobs")
            self.conf.set(keys.K_PYTHON_VENV, str(blob))
            if hit:
                from tony_tpu.observability.metrics import default_registry

                default_registry().counter(STAGING_DEDUP_COUNTER).inc()
                log.info("staging dedup: venv %s already in blob store "
                         "(%s)", Path(venv).name, blob.name)
            # This submission's own blob is exempt — a cap tighter than
            # one venv must not delete the artifact the frozen conf we
            # are about to write points at.
            prune_blob_store(
                staging_root / "blobs",
                self.conf.get_int(keys.K_STAGING_BLOB_MAX_BYTES, 0),
                exclude=blob,
            )
        lib_path = self.conf.get_str(keys.K_LIB_PATH)
        if gs_staging and lib_path:
            # The ClusterSubmitter framework copy rides the same app dir as
            # lib.zip; the stage-0 loader on each TPU VM fetches it before
            # anything else (ClusterSubmitter.java:59-63 stages the fat jar).
            utils.zip_dir(lib_path, app_dir / "lib.zip")
        self._resolve_compile_cache_dir()
        # Fresh per-job credentials (TonyClient.getTokens analogue); the
        # frozen conf carries them, so restrict it to the submitting user.
        from tony_tpu import security

        security.prepare_job_security(self.conf)
        secure = self.conf.get_bool(keys.K_SECURITY_ENABLED)
        self.conf.write_final(
            app_dir / constants.TONY_FINAL_CONF,
            mode=0o600 if secure else None,
        )
        if gs_staging:
            from tony_tpu.cloud import default_storage

            store = self._gcs_store or default_storage()
            for f in sorted(app_dir.iterdir()):
                store.upload_file(f, f"{staging_conf}/{self.app_id}/{f.name}")
            log.info(
                "staged %s to %s/%s", self.app_id, staging_conf, self.app_id
            )
        return app_dir

    def _resolve_compile_cache_dir(self) -> None:
        """Pin an EXPLICIT ``tony.compile.cache-dir`` into the frozen
        conf BEFORE it ships: relative and ``~`` paths absolutize
        against the client cwd/home, so the coordinator, every executor,
        and every retry of this job agree on ONE durable cache location
        (a re-submit that resolved a relative path against a different
        cwd would silently recompile cold). The dir is created eagerly:
        a bad path surfaces here, at submission, not as a cold cache on
        the fleet. An EMPTY key stays empty — each host then resolves
        its own default (``plan.default_cache_dir``: a fixed path inside
        that host's checkout). ``gs://`` URIs pass through — jax's cache
        layer reads them natively on TPU-VMs. A process started with
        ``JAX_COMPILATION_CACHE_DIR`` set keeps its cache there whatever
        this key says."""
        if not self.conf.get_bool(keys.K_COMPILE_CACHE_ENABLED, True):
            return
        raw = self.conf.get_str(keys.K_COMPILE_CACHE_DIR, "")
        if not raw or is_gs_uri(raw):
            return
        resolved = os.path.abspath(os.path.expanduser(raw))
        try:
            os.makedirs(resolved, exist_ok=True)
        except OSError as exc:
            log.warning(
                "compile cache dir %s is not creatable (%s); jobs run "
                "with a cold compile every session", resolved, exc,
            )
        self.conf.set(keys.K_COMPILE_CACHE_DIR, resolved)

    # -- submit + monitor (TonyClient.run:146-208) --------------------------
    # The reference fused submit-and-monitor into one blocking call; here
    # they are split so the scheduler path exists: ``submit()`` stages and
    # hands the job off (to a spawned coordinator, or — when
    # ``tony.scheduler.address`` names a daemon — to the multi-tenant
    # scheduler's queue, the YARN-RM-submission analogue), ``monitor()``
    # follows whichever path the submit took, and ``run()`` composes them
    # for the classic blocking flow.
    def submit(self) -> int:
        """Preflight + stage + hand off. 0 on a successful hand-off
        (``self.job_id`` set in scheduler mode, ``self.coordinator_proc``
        in direct mode); nonzero on refusal or submission failure."""
        from tony_tpu.analysis.preflight import run_for_submission

        rc = run_for_submission(self.conf, cwd=os.getcwd())
        if rc:
            return rc
        self.app_dir = self._stage()
        log.info("staged application %s at %s", self.app_id, self.app_dir)
        scheduler = self.conf.get_str(keys.K_SCHED_ADDRESS)
        if scheduler:
            try:
                self.job_id = self._submit_to_scheduler(scheduler)
            except (OSError, ValueError) as exc:
                log.error("scheduler submit to %s failed: %s", scheduler,
                          exc)
                return 1
            log.info("queued as %s on scheduler %s", self.job_id, scheduler)
            return 0
        cmd = [
            sys.executable, "-m", "tony_tpu.coordinator.app_master",
            "--app-dir", str(self.app_dir), "--app-id", str(self.app_id),
        ]
        # The coordinator inherits stdio like the AM inherits the YARN log
        # dir (TonyClient.buildCommand:460-461 redirects to stdout/stderr).
        self.coordinator_proc = subprocess.Popen(cmd)
        return 0

    def monitor(self) -> int:
        """Follow the submitted job to a terminal state."""
        if self.job_id is not None:
            return self._monitor_scheduler()
        try:
            return self._monitor()
        finally:
            self._shutdown()

    def run(self) -> int:
        rc = self.submit()
        if rc:
            return rc
        return self.monitor()

    def _scheduler_retries(self) -> tuple[int, int]:
        """(retries, backoff_ms) for scheduler RPCs — tuned so a thin
        client rides out a control-plane failover (daemon restart or
        standby takeover) instead of failing the user's command."""
        return (
            max(self.conf.get_int(keys.K_SCHED_CLIENT_RETRIES, 5), 1),
            max(self.conf.get_int(keys.K_SCHED_CLIENT_BACKOFF_MS, 250), 1),
        )

    def _submit_to_scheduler(self, addr: str) -> str:
        """POST the staged app dir to the scheduler daemon's JSON API
        (with bounded-backoff retries: a failing-over scheduler answers
        a few hundred ms late, not never). The daemon reads
        priority/tenant from the frozen conf inside the app dir (shared
        filesystem with the daemon, like the staging location itself)."""
        from tony_tpu.scheduler.http import scheduler_request

        retries, backoff_ms = self._scheduler_retries()
        doc = scheduler_request(
            addr, "/api/submit", payload={"app_dir": str(self.app_dir)},
            timeout_s=30, retries=retries, backoff_ms=backoff_ms,
        )
        job_id = doc.get("job_id")
        if not job_id:
            raise ValueError(f"scheduler returned no job_id: {doc}")
        return str(job_id)

    def _monitor_scheduler(self) -> int:
        """Poll the scheduler's job record until terminal, logging state
        transitions (QUEUED → RUNNING → ... PREEMPTED jobs requeue, so a
        RUNNING → QUEUED transition is normal, not a bug)."""
        addr = self.conf.get_str(keys.K_SCHED_ADDRESS)
        interval_s = self.conf.get_int(
            keys.K_CLIENT_MONITOR_INTERVAL_MS, 1000) / 1000
        last_state = None
        misses = 0
        retries, backoff_ms = self._scheduler_retries()
        while True:
            try:
                from tony_tpu.scheduler.http import scheduler_request

                job = scheduler_request(
                    addr, f"/api/job/{self.job_id}", timeout_s=10,
                    retries=retries, backoff_ms=backoff_ms,
                )
                misses = 0
            except (OSError, ValueError):
                # Each miss already burned the full retry budget: a
                # scheduler down this long is down, not failing over.
                misses += 1
                if misses >= 5:
                    log.error("scheduler %s stopped answering", addr)
                    return 1
                time.sleep(interval_s)
                continue
            state = job.get("state")
            if state != last_state:
                log.info("job %s: %s%s", self.job_id, state,
                         f" (slice {job['slice_id']})"
                         if job.get("slice_id") else "")
                last_state = state
            if state in TERMINAL_STATES:
                diag = job.get("diagnostics") or ""
                log.info("job finished: %s %s", state, diag)
                return 0 if state == "SUCCEEDED" else 1
            time.sleep(interval_s)

    def _connect_rpc(self) -> ApplicationRpcClient | None:
        addr_file = self.app_dir / "coordinator.addr"
        # A fresh interpreter on a loaded host can take tens of seconds to
        # reach prepare(), so the address wait gets its own generous
        # deadline; per-call retries are a separate knob.
        timeout_s = self.conf.get_int(keys.K_CLIENT_CONNECT_TIMEOUT_MS, 60000) / 1000.0
        retries = self.conf.get_int(keys.K_CLIENT_CONNECT_RETRIES, 3)

        def read_addr():
            if self.coordinator_proc.poll() is not None:
                raise RuntimeError(
                    f"coordinator exited with {self.coordinator_proc.returncode} "
                    f"before advertising its RPC address"
                )
            if addr_file.is_file():
                return addr_file.read_text().strip()
            return None

        addr = utils.poll_till_non_null(read_addr, interval_s=0.2,
                                        timeout_s=timeout_s)
        if addr is None:
            return None
        host, port = addr.rsplit(":", 1)
        secret = None
        if self.conf.get_bool(keys.K_SECURITY_ENABLED):
            from tony_tpu import security

            secret = security.role_token(
                self.conf.get_str(keys.K_SECRET_KEY), security.CLIENT_ROLE
            )
        return ApplicationRpcClient(
            host, int(port), secret=secret, call_retries=retries,
            call_timeout_s=self.conf.get_int(
                keys.K_RPC_CALL_TIMEOUT_MS, 60000
            ) / 1000.0,
        )

    def _print_task_urls_once(self) -> None:
        if self._urls_printed or self.rpc is None:
            return
        urls = self.rpc.get_task_urls()
        if urls:
            for u in sorted(urls, key=lambda u: u.name):
                log.info("task %s logs: %s", u.name, u.url)  # printTaskUrl:172-174
            self._urls_printed = True

    def _monitor(self) -> int:
        """monitorApplication (TonyClient.java:631-672): poll status, print
        log URLs once, honor the client-side timeout."""
        interval_s = self.conf.get_int(keys.K_CLIENT_MONITOR_INTERVAL_MS, 1000) / 1000
        timeout_ms = self.conf.get_int(keys.K_APPLICATION_TIMEOUT, 0)
        deadline = time.monotonic() + timeout_ms / 1000 if timeout_ms else None
        try:
            self.rpc = self._connect_rpc()
        except RuntimeError as exc:
            # Coordinator died before advertising RPC (the AM-crash path in
            # the reference e2e matrix): a failed submission, not a client
            # bug.
            log.error("%s", exc)
            return 1
        if self.rpc is None:
            log.error("could not reach coordinator RPC")
            return 1
        while True:
            if self.coordinator_proc.poll() is not None:
                # Coordinator death is terminal even without a final status
                # (the AM-crash path in the reference e2e matrix).
                code = self.coordinator_proc.returncode
                log.info("coordinator exited with %s", code)
                return 0 if code == 0 else 1
            try:
                status = self.rpc.get_application_status()
                self._print_task_urls_once()
            except Exception as exc:  # connection refused during teardown
                log.debug("status poll failed: %s", exc)
                time.sleep(interval_s)
                continue
            state = status.get("state", "RUNNING")
            if status.get("tensorboard_url"):
                self._print_tb_once(status["tensorboard_url"])
            if state in TERMINAL_STATES:
                log.info("application finished: %s %s", state,
                         status.get("diagnostics", ""))
                return 0 if state == "SUCCEEDED" else 1
            if deadline is not None and time.monotonic() > deadline:
                log.error("client-side timeout; killing application")
                self.coordinator_proc.kill()
                return 1
            time.sleep(interval_s)

    _tb_printed = False

    def _print_tb_once(self, url: str) -> None:
        if not self._tb_printed:
            log.info("tensorboard/profiler: %s", url)
            self._tb_printed = True

    def _shutdown(self) -> None:
        """finishApplication + cleanup (TonyClient.main:748-757)."""
        if self.rpc is not None:
            try:
                self.rpc.finish_application()
            except Exception:
                pass
            self.rpc.close()
        if self.coordinator_proc is not None:
            try:
                self.coordinator_proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.coordinator_proc.kill()

    def task_urls(self):
        return self.rpc.get_task_urls() if self.rpc else []


def main(argv: list[str] | None = None) -> int:
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(levelname)s client: %(message)s"
    )
    client = TonyClient().init(argv if argv is not None else sys.argv[1:])
    return client.run()


if __name__ == "__main__":
    raise SystemExit(main())
