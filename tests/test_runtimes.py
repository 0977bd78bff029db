"""Unit tests for the framework runtime env builders (the TaskExecutor
switch analogue, TaskExecutor.java:128-151)."""

import json

import pytest

from tony_tpu.conf import TonyConfiguration
from tony_tpu.executor.runtimes import get_runtime

SPEC = {"worker": ["h0:5000", "h1:5001"], "ps": ["h2:5002"]}


def _conf():
    return TonyConfiguration()


def test_tensorflow_env():
    env = get_runtime("tensorflow").build_env(SPEC, "worker", 1, _conf())
    tf = json.loads(env["TF_CONFIG"])
    assert tf["cluster"] == SPEC
    assert tf["task"] == {"type": "worker", "index": 1}
    assert json.loads(env["CLUSTER_SPEC"]) == SPEC


def test_pytorch_env():
    env = get_runtime("pytorch").build_env(SPEC, "ps", 0, _conf())
    assert env["INIT_METHOD"] == "tcp://h0:5000"
    assert env["MASTER_ADDR"] == "h0"
    assert env["MASTER_PORT"] == "5000"
    assert env["WORLD"] == env["WORLD_SIZE"] == "3"
    # flat order: worker (chief job) first, then ps → ps:0 has rank 2
    assert env["RANK"] == "2"


def test_jax_env_chief_is_process_zero():
    rt = get_runtime("jax")
    chief_env = rt.build_env(SPEC, "worker", 0, _conf())
    assert chief_env["TONY_PROCESS_ID"] == "0"
    assert chief_env["JAX_COORDINATOR_ADDRESS"] == "h0:5000"
    assert chief_env["TONY_NUM_PROCESSES"] == "3"
    ps_env = rt.build_env(SPEC, "ps", 0, _conf())
    assert ps_env["TONY_PROCESS_ID"] == "2"
    assert ps_env["JAX_COORDINATOR_ADDRESS"] == "h0:5000"


def test_jax_env_multislice_megascale(monkeypatch):
    """With the coordinator's slice identity in the executor env, the JAX
    runtime injects the megascale/DCN variables (slice id, slice count,
    coordinator host) alongside the flat jax.distributed identity —
    the per-slice env contract."""
    monkeypatch.setenv("TONY_SLICE_INDEX", "1")
    monkeypatch.setenv("TONY_SLICE_PROCESS_ID", "0")
    monkeypatch.setenv("TONY_NUM_SLICES", "2")
    rt = get_runtime("jax")
    env = rt.build_env(SPEC, "worker", 1, _conf())
    assert env["MEGASCALE_COORDINATOR_ADDRESS"] == "h0"
    assert env["MEGASCALE_NUM_SLICES"] == "2"
    assert env["MEGASCALE_SLICE_ID"] == "1"
    assert env["TONY_SLICE_INDEX"] == "1"
    # jax.distributed still spans all processes with ONE coordinator.
    assert env["JAX_COORDINATOR_ADDRESS"] == "h0:5000"
    assert env["TONY_NUM_PROCESSES"] == "3"


def test_jax_env_single_slice_has_no_megascale():
    env = get_runtime("jax").build_env(SPEC, "worker", 0, _conf())
    assert "MEGASCALE_SLICE_ID" not in env


def test_unknown_framework():
    with pytest.raises(ValueError, match="unknown framework"):
        get_runtime("mxnet")
