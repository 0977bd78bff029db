"""Container backends — where the reference delegates to YARN
(AMRMClientAsync/NMClientAsync, TonyApplicationMaster.java:876-885,
1017-1092), this build abstracts "start a task somewhere" behind a small
interface with two implementations:

* ``LocalProcessBackend`` — subprocesses on this host (the tony-mini
  analogue, and the substrate for every e2e test).
* ``TpuVmBackend`` — maps the job's ``instances × tpus`` ask onto a legal
  TPU slice topology (``plan_slices``) and drives slice provisioning +
  remote executor lifecycle through an injectable ``TpuApi`` client (the
  concrete cloud REST client is injected by the deployment; tests inject a
  fake — this environment has no egress).

A TPU slice is inherently gang-scheduled — ICI makes the slice atomic — so
the reference's per-container allocation machinery (allocation ids, one
priority per job type) collapses into "provision slice, get N hosts"
(SURVEY §7 stage 4).
"""

from __future__ import annotations

import logging
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass
from pathlib import Path

from tony_tpu import constants
from typing import Mapping, Protocol

from tony_tpu.coordinator.session import TonyTask

log = logging.getLogger(__name__)


class ContainerBackend(Protocol):
    def launch(self, task: TonyTask, env: Mapping[str, str]) -> object:
        """Start the executor for ``task``; returns an opaque handle."""

    def poll(self, handle: object) -> int | None:
        """Exit code if finished, else None."""

    def kill(self, handle: object) -> None:
        ...

    def stop_all(self) -> None:
        ...


@dataclass
class _ProcHandle:
    proc: subprocess.Popen
    task_id: str


class LocalProcessBackend:
    """Executors as local subprocesses, stdio to per-task log files under
    ``log_dir`` (the YARN container-log-dir analogue; these paths are what
    task URLs point at)."""

    def __init__(
        self,
        log_dir: str | os.PathLike[str],
        cwd: str | None = None,
        lib_path: str | None = None,
    ) -> None:
        # Absolute: task_url() builds file:// URIs, and executors launched
        # with a different cwd must still find their log files.
        self.log_dir = Path(log_dir).resolve()
        self.log_dir.mkdir(parents=True, exist_ok=True)
        self._cwd = cwd
        self._lib_path = lib_path
        self._handles: list[_ProcHandle] = []

    def launch(self, task: TonyTask, env: Mapping[str, str]) -> _ProcHandle:
        full_env = dict(os.environ)
        full_env.update({k: str(v) for k, v in env.items()})
        # The executor must import tony_tpu regardless of its cwd (which is
        # the unpacked job archive for client submissions) — the analogue of
        # ClusterSubmitter staging the framework jar on the container
        # classpath (ClusterSubmitter.java:59-63). A staged copy
        # (tony.lib.path, set by the cluster submitter) wins over the
        # coordinator's own install so executors run the submitted version.
        if self._lib_path:
            pkg_root = self._lib_path
        else:
            import tony_tpu

            pkg_root = str(Path(tony_tpu.__file__).parent.parent)
        existing = full_env.get("PYTHONPATH", "")
        if pkg_root not in existing.split(os.pathsep):
            full_env["PYTHONPATH"] = (
                pkg_root + (os.pathsep + existing if existing else "")
            )
        # Writable per-job scratch for user scripts (checkpoints, metrics)
        # — the analogue of the YARN container log/work dir env.
        full_env[constants.TONY_LOG_DIR] = str(self.log_dir)
        logfile = self.log_dir / f"{task.job_name}-{task.index}.log"
        out = open(logfile, "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "tony_tpu.executor.task_executor"],
            env=full_env,
            cwd=self._cwd,
            stdout=out,
            stderr=subprocess.STDOUT,
            start_new_session=True,  # kill() must reap the user script too
        )
        out.close()
        handle = _ProcHandle(proc, task.id)
        self._handles.append(handle)
        log.info("launched %s as pid %d (log %s)", task.id, proc.pid, logfile)
        return handle

    def task_url(self, task: TonyTask) -> str:
        return (self.log_dir / f"{task.job_name}-{task.index}.log").as_uri()

    def poll(self, handle: _ProcHandle) -> int | None:
        return handle.proc.poll()

    # SIGTERM first: the executor's death handler reaps the USER process
    # group (a separate session a killpg here cannot reach — ps servers
    # blocked in join() would otherwise outlive the job, the orphan leak
    # once observed on the build box). SIGKILL only after the grace window —
    # and because SIGKILL runs no handler, the user group is then reaped
    # from the pgid file the executor advertised at spawn.
    KILL_GRACE_S = 5.0

    def _reap_user_group(self, handle: _ProcHandle) -> None:
        """Escalation fallback: kill the USER process group recorded by the
        executor (its own session — unreachable via the executor's pgid)."""
        job, _, index = handle.task_id.partition(":")
        pgid_file = self.log_dir / f".{job}-{index}.userpgid"
        try:
            pgid = int(pgid_file.read_text())
        except (OSError, ValueError):
            return
        try:
            os.killpg(pgid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        # One reap per advertisement: a later teardown path re-reading this
        # file could SIGKILL a RECYCLED pgid (the executor unlinks it on
        # clean exit; the backend must do the same on fallback reaps).
        try:
            pgid_file.unlink()
        except OSError:
            pass

    def _term(self, handle: _ProcHandle) -> None:
        try:
            os.killpg(handle.proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass

    def _escalate(self, handle: _ProcHandle, deadline: float) -> None:
        """Wait until ``deadline`` for a TERM'd executor, then SIGKILL its
        group AND the user group it advertised."""
        try:
            handle.proc.wait(timeout=max(deadline - time.monotonic(), 0.05))
            return
        except subprocess.TimeoutExpired:
            pass
        log.warning(
            "executor %s ignored SIGTERM; escalating to SIGKILL",
            handle.task_id,
        )
        try:
            os.killpg(handle.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        handle.proc.wait()
        self._reap_user_group(handle)

    def kill(self, handle: _ProcHandle) -> None:
        if handle.proc.poll() is None:
            self._term(handle)
            self._escalate(handle, time.monotonic() + self.KILL_GRACE_S)
        else:
            # Executor already gone (kernel OOM kill, operator kill -9):
            # its death handlers never ran, so its user group may still be
            # alive — reap from the advertised pgid (no-op when empty).
            self._reap_user_group(handle)

    def kill_hard(self, handle: _ProcHandle) -> None:
        """SIGKILL with no grace — how preemption looks from inside the
        container, used by fault injection so the executor cannot clean up
        or deregister. Its user process group (a separate session SIGKILL
        leaves behind) is reaped from the advertised pgid file."""
        try:
            os.killpg(handle.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        handle.proc.wait()
        self._reap_user_group(handle)

    def stop_all(self) -> None:
        # TERM everyone first, then wait them against ONE shared deadline:
        # N wedged executors cost one grace window, not N.
        live = [h for h in self._handles if h.proc.poll() is None]
        for h in live:
            self._term(h)
        deadline = time.monotonic() + self.KILL_GRACE_S
        for h in live:
            self._escalate(h, deadline)
        for h in self._handles:
            if h not in live:
                # Died before we got here (uncleanly, perhaps): make sure
                # its user group did not outlive it.
                self._reap_user_group(h)
        self._handles.clear()


# ---------------------------------------------------------------------------
# TPU slice topology planning
# ---------------------------------------------------------------------------
# Legal accelerator configs: generation → {chip_count: (accel_type, hosts)}.
# TPU asks must land on one of these — YARN containers are arbitrary,
# TPU slices are quantized (SURVEY §7 hard part c).
#
# Host counts follow the Cloud TPU VM architecture ("TPU configurations",
# cloud.google.com/tpu/docs — v5e and v4 pages):
#
# * v5e single-host shapes (v5litepod-1/-4/-8) run on one VM with up to 8
#   chips, but every MULTI-host v5e slice is tiled from 4-chip host VMs
#   (machine type ct5lp-hightpu-4t): v5litepod-16 = 4 workers, -32 = 8,
#   -64 = 16, -128 = 32, -256 = 64. (An 8-chip host exists only for the
#   single-host v5litepod-8.) Getting this wrong halves the executor count
#   on real multihost slices.
# * v4 and v5p accelerator-type numbers count TensorCores, not chips
#   (v4-8 / v5p-8 = 4 chips); every v4/v5p host VM has 4 chips, so a
#   slice of C chips has C/4 workers.
# * v6e (Trillium) follows the v5e pattern: the name counts chips,
#   single-host shapes up to 8 chips, multihost slices tiled from
#   4-chip hosts.
#   Keys below are CHIP counts (what ``tony.<job>.tpus`` asks for),
#   values carry the GCP accelerator-type name.
SLICE_SHAPES: dict[str, dict[int, tuple[str, int]]] = {
    "v5e": {
        1: ("v5litepod-1", 1),
        4: ("v5litepod-4", 1),
        8: ("v5litepod-8", 1),
        16: ("v5litepod-16", 4),
        32: ("v5litepod-32", 8),
        64: ("v5litepod-64", 16),
        128: ("v5litepod-128", 32),
        256: ("v5litepod-256", 64),
    },
    "v6e": {
        1: ("v6e-1", 1),
        4: ("v6e-4", 1),
        8: ("v6e-8", 1),
        16: ("v6e-16", 4),
        32: ("v6e-32", 8),
        64: ("v6e-64", 16),
        128: ("v6e-128", 32),
        256: ("v6e-256", 64),
    },
    "v4": {
        4: ("v4-8", 1),
        8: ("v4-16", 2),
        16: ("v4-32", 4),
        32: ("v4-64", 8),
        64: ("v4-128", 16),
    },
    "v5p": {
        4: ("v5p-8", 1),
        8: ("v5p-16", 2),
        16: ("v5p-32", 4),
        32: ("v5p-64", 8),
        64: ("v5p-128", 16),
        128: ("v5p-256", 32),
        256: ("v5p-512", 64),
    },
}


@dataclass(frozen=True)
class SlicePlan:
    accelerator_type: str
    num_slices: int
    hosts_per_slice: int
    chips_per_slice: int

    @property
    def total_hosts(self) -> int:
        return self.num_slices * self.hosts_per_slice


def plan_slices(
    num_instances: int, tpus_per_instance: int, generation: str = "v5e",
    strict: bool = False, accelerator_type: str = "",
) -> SlicePlan:
    """Map ``instances × tpus`` onto legal slice shapes.

    Each instance is one *host process*, so every returned plan satisfies
    ``total_hosts == num_instances`` — the scheduler launches exactly one
    executor per host and a plan with a different host count could not be
    driven. Within that invariant we prefer the fewest slices (largest
    shape), then the least chip overshoot; multi-slice plans are
    DCN-connected.

    ``accelerator_type`` (from ``tony.tpu.accelerator-type`` or a
    ``tony.tpu.topology`` like ``v5e-8``) pins the slice shape. With
    ``strict`` (``tony.tpu.strict-slice-shapes``) chip overshoot is rejected
    instead of absorbed (SURVEY §7 hard part c: TPU slices are quantized,
    YARN containers are not); exact multi-slice tilings are always legal."""
    shapes = SLICE_SHAPES.get(generation)
    if shapes is None:
        raise ValueError(f"unknown TPU generation {generation!r}")
    total_chips = num_instances * tpus_per_instance

    if accelerator_type:
        match = [
            (chips, hosts)
            for chips, (accel, hosts) in shapes.items()
            if accel == accelerator_type
        ]
        if not match:
            raise ValueError(
                f"unknown accelerator type {accelerator_type!r} for "
                f"{generation}; legal: "
                f"{sorted(a for a, _ in shapes.values())}"
            )
        candidates = match
    else:
        candidates = [(c, h) for c, (_, h) in shapes.items()]

    # Host tiling is mandatory; among legal tilings prefer fewest slices,
    # then least chip overshoot.
    best: tuple[int, int, int, int] | None = None  # (n_slices, over, chips, hosts)
    for chips, hosts in candidates:
        if num_instances % hosts:
            continue
        n_slices = num_instances // hosts
        overshoot = n_slices * chips - total_chips
        if overshoot < 0:
            continue
        if strict and overshoot != 0:
            continue
        key = (n_slices, overshoot, chips, hosts)
        if best is None or key < best:
            best = key
    if best is None:
        raise ValueError(
            f"cannot map {num_instances} instances x {tpus_per_instance} "
            f"TPUs onto legal {generation} slice shapes "
            f"{sorted(c for c, _ in candidates)}"
            + (" (strict)" if strict else "")
            + (f" pinned to {accelerator_type}" if accelerator_type else "")
        )
    n_slices, _, chips, hosts = best
    accel = accelerator_type or shapes[chips][0]
    return SlicePlan(accel, n_slices, hosts, chips)


def plan_slices_from_conf(conf) -> dict[str, SlicePlan]:
    """Read the TPU resource keys and plan one slice group per job type that
    asks for chips (``tony.<job>.tpus`` > 0) — the analogue of the reference
    turning ``tony.<job>.gpus`` into YARN GPU capabilities
    (Utils.setCapabilityGPU:146-152, TonyApplicationMaster.java:876-885)."""
    from tony_tpu.conf import keys
    from tony_tpu.utils import parse_container_requests

    topology = conf.get_str(keys.K_TPU_TOPOLOGY, "")
    accelerator_type = conf.get_str(keys.K_TPU_ACCELERATOR_TYPE, "")
    strict = conf.get_bool(keys.K_TPU_SLICE_STRICT, False)
    generation = "v5e"
    if accelerator_type and not topology:
        # An accelerator type alone pins the generation too — find which
        # family it belongs to.
        for gen, shapes in SLICE_SHAPES.items():
            if any(a == accelerator_type for a, _ in shapes.values()):
                generation = gen
                break
        else:
            raise ValueError(
                f"unknown accelerator type {accelerator_type!r}; legal: "
                f"{sorted(a for s in SLICE_SHAPES.values() for a, _ in s.values())}"
            )
    if topology:
        generation, _, chip_str = topology.partition("-")
        if not accelerator_type:
            shapes = SLICE_SHAPES.get(generation)
            if shapes is None:
                raise ValueError(f"unknown TPU generation in topology {topology!r}")
            # A topology that IS a GCP accelerator name (e.g. "v4-16",
            # whose number counts TensorCores, not chips) means that
            # accelerator — the official name wins over reading the number
            # as a chip count (for v5e the two readings coincide because
            # "v5e-8" is not an accelerator name and v5litepod names carry
            # chip counts).
            by_name = [a for a, _ in shapes.values() if a == topology]
            if by_name:
                accelerator_type = by_name[0]
            else:
                try:
                    accelerator_type = shapes[int(chip_str)][0]
                except (KeyError, ValueError):
                    raise ValueError(
                        f"topology {topology!r} is not a legal {generation} "
                        f"shape; legal chip counts: {sorted(shapes)}"
                    ) from None
    plans: dict[str, SlicePlan] = {}
    for job, req in parse_container_requests(conf).items():
        if req.tpus > 0:
            plans[job] = plan_slices(
                req.num_instances, req.tpus, generation,
                strict=strict, accelerator_type=accelerator_type,
            )
    return plans


class TpuApi(Protocol):
    """The injectable seam to the Cloud TPU control plane. The production
    implementation wraps the queued-resource / TPU-VM REST API; tests inject
    a fake (this environment has no egress, so no concrete cloud client
    ships in-tree). One method per lifecycle edge the backend needs."""

    def create_slice(
        self, name: str, accelerator_type: str, num_slices: int
    ) -> None:
        """Request creation of ``num_slices`` slices under one name."""

    def slice_state(self, name: str) -> str:
        """"CREATING" | "READY" | "FAILED"."""

    def start_executor(
        self, name: str, host_index: int, env: Mapping[str, str]
    ) -> object:
        """Start the tony_tpu executor on host ``host_index`` of the slice
        group; returns an opaque command handle."""

    def executor_status(self, handle: object) -> int | None:
        """Exit code if the remote executor finished, else None."""

    def kill_executor(self, handle: object) -> None:
        ...

    def delete_slice(self, name: str) -> None:
        ...


@dataclass
class _TpuHandle:
    task_id: str
    slice_name: str
    host_index: int
    env: dict[str, str]
    remote: object | None = None  # None until the slice is READY
    exit_code: int | None = None
    # Why the backend thinks the task died, when it knows better than the
    # exit code ("preempted" for slice PREEMPTED/FAILED states): consumed
    # by the coordinator's failure classifier as an INFRA signal.
    reason: str | None = None


class TpuVmBackend:
    """Cloud TPU-VM backend: provisions one slice group per job type from
    the coordinator's ``SlicePlan`` and runs the executor on every host.

    Provisioning is asynchronous and driven by the coordinator's monitor
    loop: ``launch`` returns immediately with a pending handle, and each
    ``poll`` advances it — slice CREATING → READY starts the remote
    executor; slice FAILED surfaces as task exit 1 (which fails the session
    and triggers the whole-session retry, the slice-wide restart SURVEY §7
    hard part (b) calls for). This mirrors the reference's async
    RMCallbackHandler.onContainersAllocated → ContainerLauncher flow
    (TonyApplicationMaster.java:980-989) without the callback machinery."""

    # Non-terminal slice states are re-polled at most this often, however
    # many pending host handles share the slice — a 32-host slice must not
    # multiply control-plane requests by 32 every monitor tick.
    STATE_CACHE_TTL_S = 1.0

    def __init__(
        self, api: TpuApi, app_id: str,
        external_slices: Mapping[str, str] | None = None,
    ) -> None:
        """``external_slices`` switches the backend from provision/teardown
        to lease/release: {job_name: slice_name} names slices SOMEONE ELSE
        (the scheduler's warm pool) created and will delete — launch skips
        ``create_slice`` and ``stop_all`` skips ``delete_slice`` for them,
        so a finished job hands its slice back still bootstrapped instead
        of tearing it down."""
        self.api = api
        self.app_id = app_id
        self._plans: dict[str, SlicePlan] = {}
        self._created: set[str] = set()
        self._external = dict(external_slices or {})
        self._handles: list[_TpuHandle] = []
        self._state_cache: dict[str, tuple[float, str]] = {}

    def _slice_state(self, name: str) -> str:
        now = time.monotonic()
        hit = self._state_cache.get(name)
        if hit is not None and (
            hit[1] in ("READY", "FAILED") or now - hit[0] < self.STATE_CACHE_TTL_S
        ):
            return hit[1]
        state = self.api.slice_state(name)
        self._state_cache[name] = (now, state)
        return state

    def prepare_slices(self, plans: Mapping[str, SlicePlan]) -> None:
        """Receive the coordinator's per-job-type slice plans (called before
        any launch)."""
        self._plans = dict(plans)

    def _slice_name(self, job_name: str) -> str:
        return self._external.get(job_name, f"{self.app_id}-{job_name}")

    def launch(self, task: TonyTask, env: Mapping[str, str]) -> _TpuHandle:
        plan = self._plans.get(task.job_name)
        if plan is None:
            raise ValueError(
                f"no slice plan for job type {task.job_name!r} — it has no "
                f"tony.{task.job_name}.tpus ask; TpuVmBackend schedules TPU "
                f"jobs only"
            )
        name = self._slice_name(task.job_name)
        if task.job_name in self._external:
            # Leased from the pool: already created (and usually READY —
            # the poll path start-executes as soon as the state says so).
            pass
        elif name not in self._created:
            log.info(
                "creating %d x %s (%d hosts each) as %s",
                plan.num_slices, plan.accelerator_type, plan.hosts_per_slice,
                name,
            )
            self.api.create_slice(name, plan.accelerator_type, plan.num_slices)
            self._created.add(name)
        handle = _TpuHandle(task.id, name, task.index, dict(env))
        self._handles.append(handle)
        return handle

    def poll(self, handle: _TpuHandle) -> int | None:
        if handle.exit_code is not None:
            return handle.exit_code
        if handle.remote is None:
            state = self._slice_state(handle.slice_name)
            if state in ("FAILED", "PREEMPTED"):
                log.error("slice %s %s before provisioning completed",
                          handle.slice_name, state.lower())
                handle.exit_code = 1
                handle.reason = "preempted"
                return 1
            if state != "READY":
                return None
            handle.remote = self.api.start_executor(
                handle.slice_name, handle.host_index, handle.env
            )
            log.info("slice %s ready; started executor for %s",
                     handle.slice_name, handle.task_id)
            return None
        handle.exit_code = self.api.executor_status(handle.remote)
        if handle.exit_code is not None and handle.exit_code != 0:
            # The executor died nonzero — ask the control plane whether the
            # slice went away underneath it (queued-resources preemption):
            # that reclassifies the death as INFRA however the code reads.
            state = self._slice_state(handle.slice_name)
            if state in ("FAILED", "PREEMPTED", "SUSPENDED"):
                handle.reason = "preempted"
        return handle.exit_code

    def exit_reason(self, handle: _TpuHandle) -> str | None:
        """Backend-reported cause for a nonzero exit ("preempted"), or None
        when the exit code is all the backend knows."""
        return handle.reason

    def kill(self, handle: _TpuHandle) -> None:
        if handle.remote is not None and handle.exit_code is None:
            self.api.kill_executor(handle.remote)

    # Remote containers have no TERM-then-KILL distinction this API can
    # express; a fault-injection hard kill is the same control-plane call.
    kill_hard = kill

    def stop_all(self) -> None:
        for h in self._handles:
            self.kill(h)
        self._handles.clear()
        # Only slices THIS backend created are deleted; leased
        # (external) slices go back to their pool warm.
        for name in self._created:
            try:
                self.api.delete_slice(name)
            except Exception:
                log.warning("could not delete slice %s", name, exc_info=True)
        self._created.clear()
        # A retried session re-creates slices under the same names; stale
        # terminal states must not short-circuit its polls.
        self._state_cache.clear()
