"""Per-task executor agent — the analogue of ``TaskExecutor.java``
(tony-core/.../TaskExecutor.java:1-343): reserves its rendezvous port,
registers with the coordinator and blocks at the gang barrier, heartbeats,
injects the framework runtime env, execs the user command, and reports the
exit code. Launched by the coordinator's container backend with the identity
env contract (JOB_NAME / TASK_INDEX / TASK_NUM / SESSION_ID / TONY_AM_ADDRESS
/ TONY_CONF_PATH).
"""

from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

from tony_tpu import constants, utils
from tony_tpu.conf import keys
from tony_tpu.conf.configuration import TonyConfiguration
from tony_tpu.observability import metrics as obs_metrics
from tony_tpu.observability import trace as obs_trace
from tony_tpu.observability.flight import FlightRecorder
from tony_tpu.observability.profiling import ExecutorProfiler
from tony_tpu.resilience.faults import ExecutorFaults, FaultPlan
from tony_tpu.rpc.client import ApplicationRpcClient
from tony_tpu.analysis import sync_sanitizer as _sync

log = logging.getLogger(__name__)

# Default for tony.task.max-heartbeat-send-failures (TaskExecutor.
# Heartbeater:234-273).
MAX_CONSECUTIVE_HB_FAILURES = 5

# The in-flight user process (its own session via execute_shell's
# start_new_session): every executor death path must reap ITS process
# group, or ps-style servers blocked in join() outlive the job — the
# orphan leak once found on the build box. The reference
# has no such gap because YARN kills the whole container cgroup
# (TonyApplicationMaster.reset/stop, TonyApplicationMaster.java:526-542).
_user_proc: subprocess.Popen | None = None


def _user_pgid_file() -> Path | None:
    log_dir = os.environ.get(constants.TONY_LOG_DIR)
    if not log_dir:
        return None
    return Path(log_dir) / (
        f".{os.environ[constants.JOB_NAME]}-"
        f"{os.environ[constants.TASK_INDEX]}.userpgid"
    )


def _register_user_proc(proc: subprocess.Popen) -> None:
    global _user_proc
    _user_proc = proc
    # Advertise the user process group so the BACKEND can reap it even if
    # this executor wedges and gets SIGKILLed (the escalation path — a
    # SIGKILL here cannot run any handler).
    pgid_file = _user_pgid_file()
    if pgid_file is not None:
        try:
            pgid_file.write_text(str(proc.pid))
        except OSError:
            pass


def _kill_user_process_group() -> None:
    # No poll() guard: the direct child exiting does not mean its process
    # GROUP is empty (user scripts spawn helpers that inherit the group).
    # The pgid's lifetime is the job's — reuse inside that window is not a
    # realistic risk, and an empty group just raises ProcessLookupError.
    proc = _user_proc
    if proc is not None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        # Retract the advertisement: the backend's unclean-death fallback
        # reaps from this file, and a stale pgid could be recycled by an
        # unrelated process long after this clean reap.
        pgid_file = _user_pgid_file()
        if pgid_file is not None:
            try:
                pgid_file.unlink()
            except OSError:
                pass


def _install_death_handlers() -> None:
    """SIGTERM/SIGINT (the backend's graceful kill) reap the user process
    group before exiting with the conventional 128+signum."""

    def die(signum, frame):
        log.warning("signal %d: reaping user process group and exiting",
                    signum)
        _kill_user_process_group()
        os._exit(128 + signum)

    signal.signal(signal.SIGTERM, die)
    signal.signal(signal.SIGINT, die)


def _die_lost_coordinator() -> None:
    """The executor's lost-coordinator exit: reap the user process group
    (a partitioned executor must not squat its TPU slice as a zombie — a
    ps server blocked in join() would hold the chips forever) and exit
    with the dedicated code the failure classifier reads as INFRA."""
    _kill_user_process_group()
    os._exit(constants.EXIT_CODE_LOST_COORDINATOR)


class Heartbeater(threading.Thread):
    """1 Hz pings to the coordinator. Transient RPC errors are survivable —
    one failed send only bumps a consecutive-failure counter that any
    successful ping resets — but after ``max_failures`` consecutive
    failures the coordinator is presumed gone (session being torn down or
    retried, or a hard partition) and ``on_lost`` fires: by default the
    user process group is reaped and the executor exits
    EXIT_CODE_LOST_COORDINATOR.

    Fault injection: ``drop_pings`` swallows the next N pings and
    ``delay_spec`` (count, ms) sleeps before each of the next N — the
    plan-driven replacements for TEST_TASK_EXECUTOR_NUM_HB_MISS, which
    still works as a deprecated alias."""

    def __init__(
        self,
        client: ApplicationRpcClient,
        task_id: str,
        session_id: str,
        interval_ms: int,
        max_failures: int = MAX_CONSECUTIVE_HB_FAILURES,
        drop_pings: int = 0,
        delay_spec: tuple[int, int] | None = None,
        on_lost=_die_lost_coordinator,
        metrics_source=None,
        on_send=None,
        profile_source=None,
        on_command=None,
        incarnation: int = 0,
    ):
        super().__init__(name="heartbeater", daemon=True)
        self._client = client
        self._task_id = task_id
        self._session_id = session_id
        # Self-healing identity fencing: a replacement executor reuses
        # its task id, so pings carry the incarnation the coordinator
        # launched this copy under (0 stays off the wire).
        self._incarnation = incarnation
        # Telemetry piggyback: a callable returning the latest metrics
        # snapshot (or None). Called per ping; the snapshot rides the
        # heartbeat's optional ``metrics`` arg, so the telemetry plane
        # costs zero extra RPCs. Failures here must never cost a ping.
        self._metrics_source = metrics_source
        # Profiling round trip on the same channel: ``profile_source``
        # yields a finished capture summary to ship (one-shot), and
        # ``on_command`` receives the coordinator's heartbeat-REPLY
        # payload (a pending capture request). Neither may cost a ping.
        self._profile_source = profile_source
        self._on_command = on_command
        self._interval_s = interval_ms / 1000.0
        self._max_failures = max(max_failures, 1)
        self._skip = int(os.environ.get(constants.TEST_TASK_EXECUTOR_NUM_HB_MISS, "0"))
        self._drop = drop_pings
        self._delay_count, self._delay_ms = delay_spec or (0, 0)
        self._on_lost = on_lost
        # Flight-recorder tap: called with (ok: bool) after every send
        # attempt. Must never cost a ping.
        self._on_send = on_send
        self._pending_profile = None
        self.consecutive_failures = 0
        # NOT named _stop: threading.Thread has a private _stop METHOD that
        # join() calls when the thread finishes; shadowing it with an Event
        # makes join() blow up with "'Event' object is not callable".
        self._stopped = threading.Event()

    def stop(self) -> None:
        self._stopped.set()

    def run(self) -> None:
        while not self._stopped.wait(self._interval_s):
            if self._skip > 0:
                self._skip -= 1
                continue
            if self._drop > 0:
                self._drop -= 1
                log.info("fault injection: dropping heartbeat (%d left)",
                         self._drop)
                continue
            if self._delay_count > 0:
                self._delay_count -= 1
                time.sleep(self._delay_ms / 1000.0)
            payload = None
            if self._metrics_source is not None:
                try:
                    payload = self._metrics_source()
                except Exception:
                    log.debug("metrics source failed", exc_info=True)
            # The capture summary is held locally until a send SUCCEEDS:
            # the source is one-shot, and a transient ping failure must
            # not lose the only copy of the result.
            if self._pending_profile is None and \
                    self._profile_source is not None:
                try:
                    self._pending_profile = self._profile_source()
                except Exception:
                    log.debug("profile source failed", exc_info=True)
            try:
                kwargs = {}
                if payload is not None:
                    kwargs["metrics"] = payload
                if self._pending_profile is not None:
                    kwargs["profile"] = self._pending_profile
                if self._incarnation:
                    # 0 stays off the wire (and off pre-healing fakes),
                    # mirroring the RPC stub's optional-arg contract.
                    kwargs["incarnation"] = self._incarnation
                reply = self._client.task_executor_heartbeat(
                    self._task_id, self._session_id, **kwargs
                )
                self._pending_profile = None
                self.consecutive_failures = 0
                self._note_send(True)
                if reply is not None and self._on_command is not None:
                    try:
                        self._on_command(reply)
                    except Exception:
                        log.debug("heartbeat command failed", exc_info=True)
            except Exception:
                self.consecutive_failures += 1
                self._note_send(False)
                log.warning("heartbeat failed (%d consecutive)",
                            self.consecutive_failures)
                if self.consecutive_failures >= self._max_failures:
                    log.error("lost the coordinator — exiting")
                    self._on_lost()
                    return

    def _note_send(self, ok: bool) -> None:
        if self._on_send is None:
            return
        try:
            self._on_send(ok)
        except Exception:
            log.debug("heartbeat send tap failed", exc_info=True)


class TaskExecutor:
    def __init__(self) -> None:
        env = os.environ
        self.job_name = env[constants.JOB_NAME]
        self.task_index = int(env[constants.TASK_INDEX])
        self.task_num = int(env[constants.TASK_NUM])
        self.session_id = env.get(constants.SESSION_ID, "0")
        # Self-healing: the incarnation the coordinator launched this
        # copy under (0 = original; an evicted-and-replaced or
        # speculative copy carries a bumped value and every
        # registration/heartbeat echoes it, so the dead copy's traffic
        # fences out). The resync state below is the survivor half: a
        # heartbeat-reply ``resync`` command parks the user process and
        # re-registers into the patched gang.
        try:
            self.incarnation = int(
                env.get(constants.TONY_TASK_INCARNATION, "0") or 0
            )
        except ValueError:
            self.incarnation = 0
        # The gang generation this executor's registrations CONFIRM:
        # seeded from the launch env (a replacement launched into patch
        # N must confirm N, not whatever is current when its RPC lands),
        # advanced by each applied resync order. All resync state below
        # is guarded by _resync_lock — payload store + event set must be
        # atomic against _take_resync, or a re-sent order interleaving
        # with the consume could leave the event set with no payload and
        # the main loop would exit without relaunching the user process.
        try:
            self._confirm_generation = int(
                env.get(constants.TONY_GANG_GENERATION, "0") or 0
            )
        except ValueError:
            self._confirm_generation = 0
        self._resync_event = threading.Event()
        self._resync_lock = _sync.make_lock("task_executor.TaskExecutor._resync_lock")
        self._resync_payload: dict | None = None
        self._resync_done_generation = 0
        # A resync that superseded the INITIAL registration (a second
        # patch folded in while this — typically replacement — executor
        # was still polling the barrier): its runtime overrides must
        # apply to the very first user-process launch.
        self._startup_resync: dict | None = None
        self.am_host, _, am_port = env[constants.TONY_AM_ADDRESS].rpartition(":")
        self.am_port = int(am_port)
        self.conf = TonyConfiguration.from_final(env[constants.TONY_CONF_PATH])
        self._started_monotonic = time.monotonic()
        # Fault plan (tony.fault.plan rides the frozen conf): resolve this
        # task's slice of it. A plan the coordinator validated but this
        # host cannot read (file path on a remote VM) degrades to no
        # faults rather than failing real work.
        self._fault_plan: FaultPlan | None = None
        self._faults = ExecutorFaults()
        try:
            self._fault_plan = FaultPlan.from_conf(self.conf)
        except Exception:
            log.warning("ignoring unreadable fault plan", exc_info=True)
        if self._fault_plan is not None:
            self._faults = self._fault_plan.for_executor(
                self.task_id, int(self.session_id)
            )
        # The coordinator hands executors their role credential directly —
        # the conf they can read is secret-stripped, so they cannot derive
        # any other role's token (privilege separation, security.py).
        secret = env.get(constants.TONY_EXECUTOR_TOKEN)
        self._call_timeout_s = (
            self.conf.get_int(keys.K_RPC_CALL_TIMEOUT_MS, 60000) / 1000.0
        )
        # Distributed trace: join the coordinator's trace (TONY_TRACE_ID
        # from the launch env); spans flush to the job scratch dir where
        # the coordinator merges them into the per-job Chrome trace.
        self.tracer = obs_trace.Tracer(
            proc=f"executor:{self.task_id}"
        )
        # Crash flight recorder: the user process's recent published
        # reports plus heartbeat-send outcomes; dumped as blackbox-*.json
        # into the scratch dir on a nonzero user exit or the
        # lost-coordinator path, where the coordinator's stop() persists
        # it to history.
        self.flight = FlightRecorder(
            proc=f"executor:{self.task_id}",
            limit=self.conf.get_int(keys.K_HEALTH_FLIGHT_LIMIT, 256),
        )
        # Metrics handoff file: the user process publishes its registry
        # snapshot here (we export TONY_METRICS_FILE into its env); the
        # heartbeater reads it back and piggybacks it on each ping.
        log_dir = env.get(constants.TONY_LOG_DIR)
        # On-demand profiling agent: heartbeat replies deliver capture
        # requests, captures run on a background thread, artifacts land
        # beside the task logs (where the coordinator persists them to
        # history), summaries ride the next heartbeat back. The metrics
        # file doubles as the device seam: the user process's published
        # HBM gauges give captures real device memory on TPU, where
        # this supervisor process never loads jax.
        self.profiler = ExecutorProfiler(
            self.task_id, out_dir=log_dir, session_id=self.session_id,
            metrics_source=self._metrics_snapshot,
        )
        self._metrics_file: Path | None = (
            Path(log_dir) / f".metrics-{self.job_name}-{self.task_index}.json"
            if log_dir else None
        )
        # Checkpoint-flush signal file: a coordinator ``ckpt_flush``
        # command riding a heartbeat reply (live migration / evict-time
        # flush) is relayed to the user process by writing this file —
        # CheckpointManager.flush_requested polls it per step. Stale
        # orders from a previous session must not trigger a save.
        self._ckpt_flush_file: Path | None = (
            Path(log_dir)
            / f".ckpt-flush-{self.job_name}-{self.task_index}.json"
            if log_dir else None
        )
        self._ckpt_flush_req: str | None = None
        if self._ckpt_flush_file is not None:
            try:
                self._ckpt_flush_file.unlink()
            except OSError:
                pass
        if self._metrics_file is not None:
            # The scratch dir is shared across session retries: a previous
            # session's last published snapshot must not ride THIS
            # session's first heartbeats as current data (the coordinator
            # just reset its per-task aggregator for exactly that reason).
            try:
                self._metrics_file.unlink()
            except OSError:
                pass
        self.client = ApplicationRpcClient(
            self.am_host, self.am_port, secret=secret,
            call_timeout_s=self._call_timeout_s,
            fault_hook=self._faults.blackout_hook(self._started_monotonic),
            trace_id=self.tracer.trace_id,
        )
        # The rendezvous port: what this task advertises as host:port. Under
        # the JAX runtime, chief:0's port becomes the jax.distributed
        # coordinator service port (TaskExecutor.java:70-82 reserves the
        # framework server port the same way).
        self.port = utils.reserve_port()
        self.host = "127.0.0.1" if self._local_mode() else utils.local_host()
        self.tb_port: int | None = None
        self.profiler_port: int | None = None
        self.heartbeater: Heartbeater | None = None
        self._venv_dir: Path | None = None

    def _local_mode(self) -> bool:
        return self.am_host in ("127.0.0.1", "localhost")

    def _flush_trace(self) -> None:
        """Write this executor's spans where the coordinator's stop()
        merge picks them up (trace-*.jsonl in the job scratch dir). The
        session id is part of the name: the scratch dir is shared across
        session retries, and the retry waterfall is the trace's headline
        use case — session 2 must not clobber session 1's spans."""
        log_dir = os.environ.get(constants.TONY_LOG_DIR)
        if log_dir:
            self.tracer.write_jsonl(
                Path(log_dir)
                / f"trace-{self.job_name}-{self.task_index}"
                  f"-s{self.session_id}.jsonl"
            )

    @property
    def task_id(self) -> str:
        return f"{self.job_name}:{self.task_index}"

    def _metrics_snapshot(self):
        """Latest user-process metrics snapshot for the heartbeat
        piggyback; None when the user never published (plain liveness
        ping)."""
        if self._metrics_file is None:
            return None
        snap = obs_metrics.load_snapshot_file(self._metrics_file)
        if snap is not None:
            self.flight.record_report(self.task_id, snap)
        return snap

    def _dump_blackbox(self, reason: str) -> None:
        """One blackbox per (executor, session) in the scratch dir —
        later dumps overwrite earlier ones, so the file count stays
        bounded however the process dies."""
        log_dir = os.environ.get(constants.TONY_LOG_DIR)
        if not log_dir:
            return
        self.flight.dump(
            log_dir, reason,
            name=(f"executor-{self.job_name}-{self.task_index}"
                  f"-s{self.session_id}"),
            extra={"task": self.task_id, "session": self.session_id},
        )

    def _lost_coordinator(self) -> None:
        """Heartbeater's on_lost: leave the blackbox (the postmortem's
        only record of WHEN the sends started failing), then take the
        standard lost-coordinator exit."""
        self._dump_blackbox("lost-coordinator")
        _die_lost_coordinator()

    # -- rendezvous (TaskExecutor.registerAndGetClusterSpec:196-213) --------
    def register_and_get_cluster_spec(self) -> dict[str, list[str]]:
        # The heartbeat client retries nothing per-call (call_retries=0)
        # and runs on a short leash — connect AND per-call timeouts scale
        # with the interval, NOT the shared tony.rpc.call-timeout: each
        # failed send must count against the consecutive-failure threshold
        # within about one interval, or "max failures × interval" stops
        # bounding how long a partitioned executor squats its slice (a
        # silent partition leaves the TCP connection up, so a 60s recv
        # timeout would stretch detection to max_failures × 60s).
        interval_ms = self.conf.get_int(keys.K_TASK_HEARTBEAT_INTERVAL_MS,
                                        1000)
        self.heartbeater = Heartbeater(
            ApplicationRpcClient(
                self.am_host, self.am_port, secret=self.client._secret,
                connect_timeout_s=2.0, call_retries=0,
                call_timeout_s=max(2 * interval_ms / 1000.0, 2.0),
                fault_hook=self._faults.blackout_hook(
                    self._started_monotonic
                ),
                trace_id=self.tracer.trace_id,
            ),
            self.task_id,
            self.session_id,
            interval_ms,
            max_failures=self.conf.get_int(
                keys.K_TASK_MAX_HB_SEND_FAILURES,
                MAX_CONSECUTIVE_HB_FAILURES,
            ),
            drop_pings=self._faults.drop_heartbeats,
            delay_spec=self._faults.delay_heartbeats,
            metrics_source=self._metrics_snapshot,
            on_lost=self._lost_coordinator,
            on_send=lambda ok: self.flight.record_rpc(
                "task_executor_heartbeat", ok=ok, task=self.task_id
            ),
            profile_source=self.profiler.take_result,
            on_command=self._on_heartbeat_command,
            incarnation=self.incarnation,
        )
        self.heartbeater.start()
        while True:
            spec = self._poll_register(abort_on_newer_resync=True)
            if spec is not None:
                return spec
            resync = self._take_resync()
            if resync is None:
                raise TimeoutError("timed out waiting for the gang barrier")
            # A second patch folded in while this executor was still
            # polling its initial registration (the barrier now wants a
            # NEWER generation confirmed — re-registering the old one
            # would park the whole gang). _take_resync advanced the
            # confirm generation; re-register for the new patch and
            # carry its runtime overrides into the first launch.
            log.warning(
                "initial registration superseded by gang generation %s; "
                "re-registering", resync.get("generation"),
            )
            self._startup_resync = resync

    def _poll_register(
        self, abort_on_newer_resync: bool = False,
    ) -> dict[str, list[str]] | None:
        """Register (or RE-register, after a healing resync) and poll
        until the gang barrier — possibly a patched generation's re-armed
        one — releases the cluster spec. Registrations echo the
        generation being confirmed, so the coordinator can tell a
        confirm for THIS patch from a stale one.

        ``abort_on_newer_resync``: while polling a patched barrier, a
        SECOND patch may fold in (the order lands on the heartbeat
        thread) — this poll can then never succeed (the server wants the
        newer generation confirmed), so return None early and let the
        exec loop take the newer payload."""
        retry_s = self.conf.get_int(keys.K_TASK_REGISTRATION_RETRY_MS, 500) / 1000.0
        timeout_ms = self.conf.get_int(keys.K_TASK_REGISTRATION_TIMEOUT_MS, 0)
        deadline = (
            time.monotonic() + timeout_ms / 1000.0 if timeout_ms else None
        )
        while True:
            spec = self.client.register_worker_spec(
                self.task_id, f"{self.host}:{self.port}",
                incarnation=self.incarnation,
                generation=self._confirm_generation,
            )
            if spec is not None:
                return spec
            if abort_on_newer_resync:
                with self._resync_lock:
                    if self._resync_payload is not None:
                        return None  # superseded mid-poll
            if deadline is not None and time.monotonic() >= deadline:
                return None
            time.sleep(retry_s)

    # -- self-healing resync (the survivor half of a gang patch) ------------
    def _on_heartbeat_command(self, reply) -> None:
        """Heartbeat-reply command dispatch: the profile half goes to the
        profiler; a ``resync`` order (this task is registered under a
        STALE gang generation — the coordinator patched the gang) parks
        the user process so the main thread can re-register. The
        coordinator re-sends the order every ping until this executor
        re-registers, so acting on repeats must be idempotent. A
        ``ckpt_flush`` order (live migration: checkpoint NOW — the
        coordinator is waiting on the commit marker before tearing the
        job down) is relayed to the user process via the flush-signal
        file; repeats with the same req_id are no-ops."""
        self.profiler.handle_command(reply)
        flush = reply.get("ckpt_flush") if isinstance(reply, dict) else None
        if isinstance(flush, dict):
            self._relay_ckpt_flush(flush)
        resync = reply.get("resync") if isinstance(reply, dict) else None
        if not isinstance(resync, dict):
            return
        try:
            generation = int(resync.get("generation", 0) or 0)
        except (TypeError, ValueError):
            return
        with self._resync_lock:
            if generation <= self._resync_done_generation:
                return  # this patch was already applied
            fresh = not self._resync_event.is_set()
            # Payload store + event set are one atomic region (see
            # __init__): _take_resync consumes both under this lock.
            self._resync_payload = dict(resync)
            self._resync_event.set()
        if fresh:
            log.warning(
                "healing resync ordered (gang generation %d): parking "
                "the user process to re-register", generation,
            )
        # Park: the kill is a no-op when the process is already down,
        # so re-sent orders (and the order landing between exec loops)
        # stay harmless.
        _kill_user_process_group()

    def _relay_ckpt_flush(self, flush: dict) -> None:
        """Write the flush-signal file (atomic rename so the user
        process can never read a torn order). Heartbeat-thread only."""
        if self._ckpt_flush_file is None:
            return
        req_id = str(flush.get("req_id", "") or "")
        if not req_id or req_id == self._ckpt_flush_req:
            return
        self._ckpt_flush_req = req_id
        payload = {"req_id": req_id}
        if flush.get("step") is not None:
            payload["step"] = flush["step"]
        tmp = self._ckpt_flush_file.with_name(
            self._ckpt_flush_file.name + ".tmp"
        )
        try:
            tmp.write_text(json.dumps(payload))
            tmp.rename(self._ckpt_flush_file)
            log.warning(
                "checkpoint flush ordered (req %s, target step %s): "
                "signaled the user process", req_id, flush.get("step"),
            )
        except OSError:
            # Next heartbeat's re-sent order retries.
            self._ckpt_flush_req = None
            log.warning("could not write checkpoint flush signal",
                        exc_info=True)

    def _resync_env(self, cluster_spec: dict[str, list[str]],
                    resync: dict) -> dict[str, str]:
        """The user-process env for a resync'd (or resync-superseded
        initial) launch: the dense runtime view the order carried, the
        checkpoint resume step, and the coordinator's replanned sharding
        note (the user process feeds it to plan_from_mesh / its own plan
        selection on the rebuilt mesh)."""
        env = self.build_task_env(
            cluster_spec,
            runtime_index=resync.get("task_index"),
            runtime_num=resync.get("task_num"),
        )
        if resync.get("resume_step") is not None:
            env[constants.TONY_RESUME_STEP] = str(resync["resume_step"])
        if resync.get("reshard"):
            env[constants.TONY_RESHARD_PLAN] = str(resync["reshard"])
        return env

    def _take_resync(self) -> dict | None:
        """Consume a pending resync order (main thread, between user
        process runs); None when the last run ended for real reasons.
        Consume + event clear + generation advance are one atomic
        region against ``_on_heartbeat_command``."""
        with self._resync_lock:
            if not self._resync_event.is_set():
                return None
            payload, self._resync_payload = self._resync_payload, None
            self._resync_event.clear()
            if payload is not None:
                try:
                    generation = int(payload.get("generation", 0) or 0)
                except (TypeError, ValueError):
                    generation = 0
                self._resync_done_generation = max(
                    self._resync_done_generation, generation,
                )
                self._confirm_generation = max(
                    self._confirm_generation, generation,
                )
        return payload

    # -- env assembly -------------------------------------------------------
    def build_task_env(
        self, cluster_spec: dict[str, list[str]],
        runtime_index: int | None = None,
        runtime_num: int | None = None,
    ) -> dict[str, str]:
        from tony_tpu.executor.runtimes import get_runtime

        # After an elastic shrink the cluster spec is DENSE over the
        # survivors: this executor keeps its original id for
        # registration/liveness, but the runtime env (process id, task
        # index/num the user process sees) must use the dense view the
        # resync order carried. Unpatched runs pass neither override.
        index = self.task_index if runtime_index is None else runtime_index
        num = self.task_num if runtime_num is None else runtime_num
        framework = self.conf.get_str(keys.K_FRAMEWORK, "jax")
        env = get_runtime(framework).build_env(
            cluster_spec, self.job_name, index, self.conf
        )
        env.update(
            {
                constants.JOB_NAME: self.job_name,
                constants.TASK_INDEX: str(index),
                constants.TASK_NUM: str(num),
                constants.SESSION_ID: self.session_id,
            }
        )
        if self.tb_port is not None:
            env[constants.TB_PORT] = str(self.tb_port)
        if self.profiler_port is not None:
            env[constants.PROFILER_PORT] = str(self.profiler_port)
        # Observability contract: the trace id (spans in the user process
        # join the job trace) and the snapshot file the default metrics
        # registry publishes to (observability.report auto-publishes).
        env[constants.TONY_TRACE_ID] = self.tracer.trace_id
        if self._metrics_file is not None:
            env[constants.TONY_METRICS_FILE] = str(self._metrics_file)
        # Data-plane tuning: the reader and device prefetcher read these
        # at construction (io/reader.py), so tony.io.* conf reaches user
        # processes without any API threading.
        env[constants.TONY_IO_PREFETCH_DEPTH] = str(
            self.conf.get_int(keys.K_IO_PREFETCH_DEPTH, 2)
        )
        env[constants.TONY_IO_READ_WORKERS] = str(
            self.conf.get_int(keys.K_IO_READ_WORKERS, 4)
        )
        env[constants.TONY_IO_CHUNK_RECORDS] = str(
            self.conf.get_int(keys.K_IO_CHUNK_RECORDS, 256)
        )
        # Persistent compile cache (tony.compile.* conf → user-process
        # env → parallel/plan.configure_compile_cache, called from
        # runtime.initialize()): a retried/resumed session of an
        # unchanged program reuses the previous session's executables.
        env[constants.TONY_COMPILE_CACHE_ENABLED] = str(
            self.conf.get_bool(keys.K_COMPILE_CACHE_ENABLED, True)
        ).lower()
        cache_dir = self.conf.get_str(keys.K_COMPILE_CACHE_DIR, "")
        if cache_dir:
            env[constants.TONY_COMPILE_CACHE_DIR] = cache_dir
        env[constants.TONY_COMPILE_MIN_ENTRY_SIZE] = str(
            self.conf.get_int(keys.K_COMPILE_MIN_ENTRY_SIZE, 0)
        )
        # Checkpoint pipeline (tony.ckpt.* conf → user-process env →
        # checkpoint/manager.py defaults), plus the flush-signal file
        # the heartbeat thread writes when the coordinator orders a
        # live-migration checkpoint flush.
        env[constants.TONY_CKPT_PIPELINE_DEPTH] = str(
            self.conf.get_int(keys.K_CKPT_PIPELINE_DEPTH, 2)
        )
        env[constants.TONY_CKPT_PERSIST_WORKERS] = str(
            self.conf.get_int(keys.K_CKPT_PERSIST_WORKERS, 1)
        )
        env[constants.TONY_CKPT_DIFFERENTIAL] = str(
            self.conf.get_bool(keys.K_CKPT_DIFFERENTIAL, True)
        ).lower()
        env[constants.TONY_CKPT_FULL_EVERY] = str(
            self.conf.get_int(keys.K_CKPT_FULL_EVERY, 5)
        )
        env[constants.TONY_CKPT_BG_SNAPSHOT] = str(
            self.conf.get_bool(keys.K_CKPT_BG_SNAPSHOT, False)
        ).lower()
        if self._ckpt_flush_file is not None:
            env[constants.TONY_CKPT_FLUSH_FILE] = str(
                self._ckpt_flush_file
            )
        # Continuous HBM gauges (tony.profile.hbm-interval → user-process
        # env → runtime.initialize starts the device-memory monitor, so
        # OOM-adjacent jobs are visible on /metrics before they die).
        env[constants.TONY_PROFILE_HBM_INTERVAL_MS] = str(
            self.conf.get_int(keys.K_PROFILE_HBM_INTERVAL_MS, 5000)
        )
        # Serving engine tuning (tony.serving.* conf → user-process env):
        # the serving task type's script reads these as its engine
        # defaults, so slot/chunk/backpressure sizing is a conf change,
        # not a script change.
        env[constants.TONY_SERVING_SLOTS] = str(
            self.conf.get_int(keys.K_SERVING_SLOTS, 8)
        )
        env[constants.TONY_SERVING_PREFILL_CHUNK] = str(
            self.conf.get_int(keys.K_SERVING_PREFILL_CHUNK, 32)
        )
        env[constants.TONY_SERVING_DECODE_WINDOW] = str(
            self.conf.get_int(keys.K_SERVING_DECODE_WINDOW, 1)
        )
        # Step anatomy (tony.stepstats.* conf → user-process env →
        # observability/stepstats.py): the instrumented train step reads
        # these at construction, so the per-step phase/MFU telemetry and
        # the planner's live-calibration feedback are conf switches, not
        # script changes.
        env[constants.TONY_STEPSTATS_ENABLED] = str(
            self.conf.get_bool(keys.K_STEPSTATS_ENABLED, True)
        ).lower()
        env[constants.TONY_STEPSTATS_CALIBRATE] = str(
            self.conf.get_bool(keys.K_STEPSTATS_CALIBRATE, True)
        ).lower()
        env[constants.TONY_STEPSTATS_WINDOW] = str(
            self.conf.get_int(keys.K_STEPSTATS_WINDOW, 32)
        )
        # Measured autotuner (tony.tune.* conf → user-process env →
        # parallel/autotune.py): consumption switch, search trial
        # budget and the record dir (empty = beside the compile cache,
        # so retries/resumes land warm).
        env[constants.TONY_TUNE_ENABLED] = str(
            self.conf.get_bool(keys.K_TUNE_ENABLED, True)
        ).lower()
        env[constants.TONY_TUNE_TRIAL_BUDGET] = str(
            self.conf.get_int(keys.K_TUNE_TRIAL_BUDGET, 12)
        )
        env[constants.TONY_TUNE_RECORD_DIR] = self.conf.get_str(
            keys.K_TUNE_RECORD_DIR, ""
        )
        env[constants.TONY_SERVING_MAX_QUEUE] = str(
            self.conf.get_int(keys.K_SERVING_MAX_QUEUE, 1024)
        )
        env[constants.TONY_SERVING_PORT] = str(
            self.conf.get_int(keys.K_SERVING_PORT, 0)
        )
        # user-supplied extra env (--shell_env analogue)
        env.update(utils.parse_key_values(self.conf.get_str(keys.K_SHELL_ENV)))
        if self._fault_plan is not None and self._fault_plan.raw and any(
            s.action in ("fail_checkpoint_write", "throttle_io",
                         "degrade_task")
            for s in self._fault_plan.specs
        ):
            # CheckpointManager (fail_checkpoint_write), the input
            # pipeline (throttle_io), and the train loop (degrade_task)
            # run in the USER process and honor these faults from this
            # env.
            env[constants.TONY_FAULT_PLAN] = self._fault_plan.raw
        return env

    def build_task_command(self) -> str:
        """Interpreter + script + params via the shared builder
        (utils.build_user_command); the per-task venv extraction dir is
        remembered for cleanup after the user process exits."""
        command, self._venv_dir = utils.build_user_command(
            self.conf, f"{self.job_name}-{self.task_index}-{os.getpid()}"
        )
        return command

    def _maybe_sleep_for_skew(self) -> None:
        """TEST_TASK_EXECUTOR_SKEW="job#idx#ms" straggler simulation
        (TaskExecutor.java:320-340)."""
        spec = os.environ.get(constants.TEST_TASK_EXECUTOR_SKEW)
        if not spec:
            return
        try:
            job, idx, ms = spec.split("#")
        except ValueError:
            log.warning("bad %s spec %r", constants.TEST_TASK_EXECUTOR_SKEW, spec)
            return
        if job == self.job_name and int(idx) == self.task_index:
            log.info("skew injection: sleeping %sms", ms)
            time.sleep(int(ms) / 1000.0)

    def is_chief(self) -> bool:
        return (
            self.job_name == self.conf.get_str(keys.K_CHIEF_NAME, "worker")
            and self.task_index == int(self.conf.get_str(keys.K_CHIEF_INDEX, "0"))
        )

    # -- main ---------------------------------------------------------------
    def run(self) -> int:
        if os.environ.get(constants.TEST_TASK_EXECUTOR_HANG):
            # Fault injection: hang before ever registering, then die
            # (TaskExecutor.java:301-318).
            log.error("TEST_TASK_EXECUTOR_HANG set — hanging")
            time.sleep(20)
            return 1
        if self._faults.pre_register_exit is not None:
            # Fault injection (exit_executor at pre_register): die before
            # the rendezvous barrier — how a typo'd script path or broken
            # localization looks to the coordinator, whose classifier must
            # read a pre-registration nonzero exit as USER_PERMANENT.
            log.error("fault injection: exiting %d before registration",
                      self._faults.pre_register_exit)
            return self._faults.pre_register_exit
        self._maybe_sleep_for_skew()
        with self.tracer.span("rendezvous", task=self.task_id):
            cluster_spec = self.register_and_get_cluster_spec()
        log.info("barrier released; cluster spec: %s", cluster_spec)
        if self.is_chief() and self.conf.get_bool(keys.K_TENSORBOARD_ENABLED, True):
            self.tb_port = utils.reserve_port()
            try:
                self.client.register_tensorboard_url(
                    self.task_id, f"http://{self.host}:{self.tb_port}"
                )
            except Exception:
                log.warning("could not register TensorBoard URL", exc_info=True)
        if self.conf.get_bool(keys.K_PROFILER_ENABLED, False):
            # The profiler seam SURVEY.md §5.1 reserves: each task gets a
            # port for jax.profiler.start_server; the user script opts in
            # via tony_tpu.profiling.maybe_start_profiler_server().
            self.profiler_port = utils.reserve_port()
        if self._startup_resync is not None:
            env = self._resync_env(cluster_spec, self._startup_resync)
        else:
            env = self.build_task_env(cluster_spec)
        command = self.build_task_command()
        timeout_ms = (
            self.conf.get_int(keys.K_WORKER_TIMEOUT, 0)
            if self.job_name == constants.WORKER_JOB_NAME
            else 0
        )
        while True:
            if not self._resync_event.is_set():
                log.info("executing: %s", command)
                with self.tracer.span("user_process",
                                      task=self.task_id) as up_span:
                    rc = utils.execute_shell(
                        command, timeout_ms=timeout_ms, extra_env=env,
                        on_start=_register_user_proc,
                    )
                    up_span.set(exit_code=rc)
                log.info("user process exited with %d", rc)
            else:
                # The resync order landed before the user process even
                # started (or between runs): skip straight to the
                # re-registration — the stale cluster spec must not run.
                rc = 0
            resync = self._take_resync()
            if resync is None:
                break
            # Survivor half of a gang patch: the user process was parked
            # on purpose (its SIGKILL exit is not a failure); re-register
            # into the patched generation, then relaunch against the new
            # (possibly shrunken + resharded) cluster spec, resuming from
            # the coordinator's checkpoint step.
            if self._metrics_file is not None:
                # The parked process's last snapshot is stale by design;
                # it must not ride the patched gang's first heartbeats.
                try:
                    self._metrics_file.unlink()
                except OSError:
                    pass
            with self.tracer.span("resync", task=self.task_id,
                                  generation=resync.get("generation")):
                cluster_spec = self._poll_register(
                    abort_on_newer_resync=True
                )
            if cluster_spec is None:
                with self._resync_lock:
                    superseded = self._resync_event.is_set()
                if superseded:
                    # A second patch folded in mid-poll: loop back and
                    # take its payload instead of the stale one.
                    continue
                log.error("patched gang barrier never released")
                rc = 1
                break
            log.info("re-registered into patched gang; spec: %s",
                     cluster_spec)
            env = self._resync_env(cluster_spec, resync)
        if rc != 0:
            # The postmortem wants what THIS host saw just before the
            # failure: the last published reports and heartbeat outcomes.
            self._dump_blackbox(f"user-exit-{rc}")
        self._flush_trace()
        if self._venv_dir is not None:
            # Per-task venv extractions are scratch; don't litter the host.
            import shutil

            shutil.rmtree(self._venv_dir, ignore_errors=True)
        try:
            self.client.register_execution_result(
                rc, self.job_name, str(self.task_index), self.session_id
            )
        except Exception:
            # Advisory call: the backend sees our real exit code either way.
            log.warning("could not report execution result", exc_info=True)
        if self.heartbeater is not None:
            self.heartbeater.stop()
        self.client.close()
        return rc


def main() -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s executor %(name)s: %(message)s",
    )
    _install_death_handlers()
    executor = TaskExecutor()
    try:
        return executor.run()
    finally:
        # Belt and braces: no exit path may orphan the user process group.
        _kill_user_process_group()


if __name__ == "__main__":
    sys.exit(main())
