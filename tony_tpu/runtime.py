"""User-facing runtime helpers for training scripts launched by tony_tpu.

The executor injects the env contract; a JAX training script needs exactly
one call before touching devices::

    import tony_tpu.runtime as rt
    rt.initialize()          # no-op when launched standalone / single-process

This is the TPU-native replacement for the reference's convention of user
scripts hand-parsing TF_CONFIG or RANK/INIT_METHOD (e.g.
tony-examples/mnist-tensorflow/mnist_distributed.py:188-220 and
mnist-pytorch/mnist_distributed.py:185-214).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

from tony_tpu import constants


@dataclass(frozen=True)
class TaskContext:
    job_name: str
    task_index: int
    task_num: int
    session_id: str
    process_id: int
    num_processes: int
    coordinator_address: str | None
    # Multi-slice identity (num_slices > 1 jobs only; see
    # executor/runtimes.py JAXRuntime): which DCN-connected slice this
    # process runs on, and its index within the slice.
    slice_index: int = 0
    num_slices: int = 1
    slice_process_id: int = 0

    @property
    def is_distributed(self) -> bool:
        return self.coordinator_address is not None and self.num_processes > 1


def task_context() -> TaskContext:
    env = os.environ
    return TaskContext(
        job_name=env.get(constants.JOB_NAME, "worker"),
        task_index=int(env.get(constants.TASK_INDEX, "0")),
        task_num=int(env.get(constants.TASK_NUM, "1")),
        session_id=env.get(constants.SESSION_ID, "0"),
        process_id=int(env.get(constants.TONY_PROCESS_ID, "0")),
        num_processes=int(env.get(constants.TONY_NUM_PROCESSES, "1")),
        coordinator_address=env.get(constants.TONY_COORDINATOR_ADDRESS),
        slice_index=int(env.get(constants.TONY_SLICE_INDEX, "0")),
        num_slices=int(env.get(constants.TONY_NUM_SLICES, "1")),
        slice_process_id=int(env.get(constants.TONY_SLICE_PROCESS_ID, "0")),
    )


def cluster_spec() -> dict[str, list[str]] | None:
    raw = os.environ.get(constants.CLUSTER_SPEC)
    return json.loads(raw) if raw else None


def initialize(**kwargs) -> TaskContext:
    """Initialize jax.distributed from the injected env. Outside a tony_tpu
    job (or in a single-process job) this is a no-op, so scripts run
    unchanged locally."""
    ctx = task_context()
    # Persistent compile cache first: wiring it before any compilation
    # means a retried/resumed session of an unchanged program skips XLA
    # entirely. Where it lives: parallel/plan.configure_compile_cache.
    from tony_tpu.parallel.plan import configure_compile_cache

    configure_compile_cache()
    if ctx.is_distributed:
        import jax

        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # Multi-process collectives on the CPU backend go over gloo;
            # without it every cross-process psum fails with
            # "Multiprocess computations aren't implemented".
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=ctx.coordinator_address,
            num_processes=ctx.num_processes,
            process_id=ctx.process_id,
            **kwargs,
        )
    # Continuous device-memory telemetry: per-device HBM gauges sampled
    # on a daemon thread into the default registry, so the snapshot that
    # already rides heartbeats shows memory pressure BEFORE an OOM. A
    # no-op without jax or on backends with no memory introspection.
    hbm_ms = os.environ.get(constants.TONY_PROFILE_HBM_INTERVAL_MS)
    if hbm_ms and hbm_ms != "0":
        from tony_tpu.observability.profiling import (
            start_device_memory_monitor,
        )

        try:
            start_device_memory_monitor(interval_s=int(hbm_ms) / 1000.0)
        except (ValueError, TypeError):
            pass
    return ctx


def describe_devices() -> str:
    """``platform=tpu device_kind="TPU v5 lite" device_count=1`` — the
    backend this process holds, for a user script's start-up line: a
    job's log then says which device it actually ran on (a chip belongs
    to one process, and that process is the user script). Initialises
    the backend; a backend that cannot start raises."""
    import jax

    dev = jax.devices()[0]
    return (f'platform={dev.platform} device_kind="{dev.device_kind}" '
            f"device_count={jax.device_count()}")


def tensorboard_port() -> int | None:
    raw = os.environ.get(constants.TB_PORT)
    return int(raw) if raw else None


def sharded_reader(paths: list[str], **kwargs):
    """The executor ↔ user-script data-plane handoff. Where the reference
    hands user Python an HDFS reader over py4j
    (TaskExecutor.getHdfsAvroFileSplitReader:281-294), here the user script
    shares the executor's process tree and just asks for a reader sharded
    by its injected identity::

        reader = tony_tpu.runtime.sharded_reader(
            ["data/*.jsonl" files...], fmt="jsonl")
        print(reader.schema_json())
        for batch in reader: ...

    Sharding uses the global process identity (process_id/num_processes),
    so every record is read exactly once across the whole job regardless of
    job-type layout. All ShardedRecordReader kwargs pass through."""
    from tony_tpu.io.reader import ShardedRecordReader

    ctx = task_context()
    return ShardedRecordReader(
        paths,
        task_index=ctx.process_id,
        num_tasks=ctx.num_processes,
        **kwargs,
    )


def slice_topology() -> dict | None:
    """The coordinator's planned slice for this job type (accelerator_type,
    num_slices, hosts_per_slice, chips_per_slice), or None off-TPU. Use it
    to size a ``jax.sharding.Mesh`` without hardcoding the device count."""
    raw = os.environ.get(constants.TONY_SLICE_TOPOLOGY)
    return json.loads(raw) if raw else None


def build_job_mesh(spec=None, devices=None):
    """Build this job's device mesh from the injected slice topology:
    single-slice jobs get the plain 5-axis mesh; multi-slice jobs get the
    dp-outermost DCN-spanning layout (``parallel.mesh.build_mesh``'s
    ``num_slices``) so only the gradient psum crosses slices. Scripts call
    this instead of hand-building a Mesh::

        rt.initialize()
        mesh = rt.build_job_mesh()          # or pass a MeshSpec
    """
    from tony_tpu.parallel.mesh import build_mesh

    plan = slice_topology()
    num_slices = int(plan["num_slices"]) if plan else 1
    return build_mesh(spec, devices, num_slices=num_slices)
