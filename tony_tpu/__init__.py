"""tony_tpu — a TPU-native distributed-training orchestration framework.

A ground-up rebuild of the capabilities of LinkedIn's TonY (TensorFlow on
YARN) for TPU fleets: submission client + CLI, a control-plane coordinator
that gang-schedules task groups and runs the rendezvous barrier, per-host
executors that inject the distributed runtime env (JAX/TF/PyTorch) and
supervise the user process, heartbeat failure detection with session retry,
a sharded data plane, job history, a mini-cluster for tests — plus the
model/ops/parallelism layer the reference delegates to frameworks, built on
jax.sharding meshes, pjit, and Pallas TPU kernels.
"""

__version__ = "0.1.0"
