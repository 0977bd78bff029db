"""Checkpoint/resume: unit tests for the async per-process-sharded
CheckpointManager and the restore-on-retry e2e the reference's AM-retry
resume path implies (SURVEY §5.4; session retry is
TonyApplicationMaster.reset:526-542)."""

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.checkpoint import CheckpointManager
from tony_tpu.conf import keys
from tony_tpu.coordinator.session import SessionStatus
from tony_tpu.mini import MiniTonyCluster

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _state(val: float):
    # Every leaf varies with ``val`` on purpose: consecutive saves of
    # _state(s) then share no unchanged bytes, so the differential
    # planner writes them full and the legacy GC/completeness tests keep
    # their exact step sets. Partially-static trees (where diffs and
    # donor protection engage) get their own tests below.
    return {
        "step": jnp.asarray(int(val), jnp.int32),
        "params": {"w": jnp.full((8, 4), val),
                   "b": jnp.full(4, val / 2.0)},
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(3, _state(3.0), blocking=True)
    out = mgr.restore(_state(0.0))
    assert int(out["step"]) == 3
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]), 3.0)


def test_save_records_snapshot_stall_metric(tmp_path):
    """Every save observes its synchronous D2H snapshot phase into
    ``tony_ckpt_snapshot_ms`` (the save-stall the train loop pays — the
    batched-transfer satellite's observable)."""
    from tony_tpu.checkpoint import CKPT_SNAPSHOT_HISTOGRAM
    from tony_tpu.observability.metrics import default_registry

    def count():
        h = default_registry().snapshot()["histograms"].get(
            CKPT_SNAPSHOT_HISTOGRAM
        )
        return 0 if h is None else h["count"]

    before = count()
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0), blocking=True)
    mgr.save(2, _state(2.0))
    mgr.wait()
    assert count() == before + 2


def test_saved_num_processes_tolerates_corrupt_metadata(tmp_path):
    """A corrupt metadata.json (unparseable, or parsing to a non-dict,
    or carrying a non-numeric num_processes) must fall back to the
    ambient process count, not abort the restore."""
    mgr = CheckpointManager(tmp_path, num_processes=3)
    for corrupt in (
        b"{not json",            # unparseable
        b"[1, 2]",               # parses to a list
        b'"just a string"',      # parses to a string
        b"17",                   # parses to a number
        b'{"num_processes": "x"}',   # non-numeric value
        b'{"num_processes": null}',  # null value
    ):
        mgr._store.put_file(7, "metadata.json", corrupt)
        assert mgr._saved_num_processes(7) == 3, corrupt
    # And an honest file still wins.
    mgr._store.put_file(7, "metadata.json", b'{"num_processes": 5}')
    assert mgr._saved_num_processes(7) == 5


def test_async_save_is_durable_after_wait(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0))  # async
    mgr.wait()
    assert mgr.latest_step() == 1


def test_latest_complete_wins_and_torn_writes_ignored(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0), blocking=True)
    mgr.save(2, _state(2.0), blocking=True)
    # a torn/incomplete step: dir without metadata must be invisible
    (tmp_path / "step_9").mkdir()
    (tmp_path / "step_9" / ".tmp_process_0.npz").write_bytes(b"garbage")
    assert mgr.latest_step() == 2
    assert int(mgr.restore(_state(0.0))["step"]) == 2


def test_multiprocess_checkpoint_incomplete_until_all_written(tmp_path):
    p0 = CheckpointManager(tmp_path, process_id=0, num_processes=2)
    p1 = CheckpointManager(tmp_path, process_id=1, num_processes=2)
    p0.save(1, _state(1.0), blocking=True)
    assert p0.latest_step() is None  # process 1 hasn't written
    p1.save(1, _state(1.5), blocking=True)
    assert p0.latest_step() == 1
    # each process restores its own shard file
    assert float(p1.restore(_state(0.0))["params"]["w"][0, 0]) == 1.5


def test_gc_keeps_newest(tmp_path):
    mgr = CheckpointManager(tmp_path, max_to_keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state(float(s)), blocking=True)
    assert mgr._complete_steps() == [3, 4]


def test_bfloat16_roundtrips_exactly(tmp_path):
    """np.savez corrupts ml_dtypes (bf16 -> void); the byte+manifest
    encoding must restore the exact dtype and values."""
    state = {"w": jnp.asarray([1.5, -2.25, 3.0], jnp.bfloat16),
             "step": jnp.asarray(4, jnp.int32)}
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, state, blocking=True)
    out = mgr.restore(state)
    assert out["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["w"], np.float32), [1.5, -2.25, 3.0]
    )


def test_async_writer_failure_raises_on_wait(tmp_path, monkeypatch):
    """A failed background write must surface, not silently drop the
    checkpoint."""
    import tony_tpu.checkpoint.stores as ckpt_stores

    def boom(path, tmp, data):
        raise OSError("disk full")

    monkeypatch.setattr(ckpt_stores, "_fsync_write", boom)
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0))  # async
    with pytest.raises(RuntimeError, match="checkpoint write failed"):
        mgr.wait()
    # the failure is consumed; the manager is usable again
    monkeypatch.undo()
    mgr.save(2, _state(2.0), blocking=True)
    assert mgr.latest_step() == 2


def test_explicit_step_missing_or_torn_returns_none(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0), blocking=True)
    assert mgr.restore(_state(0.0), step=7) is None
    # torn: dir exists but no metadata
    (tmp_path / "step_7").mkdir()
    assert mgr.restore(_state(0.0), step=7) is None


def test_gc_reclaims_old_torn_dirs(tmp_path):
    mgr = CheckpointManager(tmp_path, max_to_keep=2, torn_gc_grace_s=0.0)
    mgr.save(1, _state(1.0), blocking=True)
    # a crash leftover older than the kept window
    (tmp_path / "step_0").mkdir()
    (tmp_path / "step_0" / ".tmp_process_0.npz").write_bytes(b"torn")
    time.sleep(0.01)  # let the leftover age past the (zero) grace window
    for s in (2, 3):
        mgr.save(s, _state(float(s)), blocking=True)
    assert mgr._complete_steps() == [2, 3]
    assert not (tmp_path / "step_0").exists()


def test_gc_spares_recently_written_torn_dirs(tmp_path):
    """A torn dir still being written (recent mtime) survives GC: process 0
    must not rmtree a straggler's in-flight older-step write."""
    mgr = CheckpointManager(tmp_path, max_to_keep=2, torn_gc_grace_s=3600.0)
    mgr.save(1, _state(1.0), blocking=True)
    (tmp_path / "step_0").mkdir()
    (tmp_path / "step_0" / ".tmp_process_1.npz").write_bytes(b"in flight")
    for s in (2, 3):
        mgr.save(s, _state(float(s)), blocking=True)
    assert (tmp_path / "step_0").exists()


def test_global_shape_mismatch_raises_not_truncates(tmp_path):
    """Restoring into a template whose GLOBAL leaf shape differs from the
    checkpoint's must raise, not silently hand back wrong-shaped arrays
    (found live pre-r5: a serving job restoring a sharded training
    checkpoint got half of every leaf; now topology differences reassemble
    and only genuine model-definition changes raise)."""
    half = {"w": jnp.ones((4, 2))}
    CheckpointManager(tmp_path, process_id=0, num_processes=1).save(
        1, half, blocking=True
    )
    full_template = {"w": jnp.zeros((8, 2))}
    with pytest.raises(ValueError, match="does not match the template"):
        CheckpointManager(tmp_path).restore(full_template)


def _write_slab_checkpoint(directory, step, slabs, *, extra_leaf=None,
                           store=None):
    """Hand-craft a multi-process slab checkpoint in the manager's on-disk
    format — a format-contract pin that lets single-process tests exercise
    the cross-topology reassembly path (a real cross-process array cannot
    exist in one test process; the mini-cluster e2e covers the real one).
    ``slabs``: list per process of {key: (piece, [[start, stop], ...],
    global_shape)}. ``extra_leaf``: (key, full_array) replicated full-span
    in every process file (the way replicated params are saved).
    ``store``: optional step store (e.g. _ObjectCheckpointStore for the
    gs:// twin); default is the filesystem store over ``directory``."""
    import io as _io
    import json as _json

    from tony_tpu.checkpoint import _MANIFEST, _FsCheckpointStore, _encode

    store = store or _FsCheckpointStore(directory)
    n = len(slabs)
    for pid, leaves in enumerate(slabs):
        leaves = dict(leaves)
        if extra_leaf is not None:
            k, arr = extra_leaf
            leaves[k] = (arr, [[0, d] for d in arr.shape], arr.shape)
        manifest, blobs = {}, {}
        for key, (piece, index, gshape) in leaves.items():
            piece = np.asarray(piece)
            manifest[key] = {
                "dtype": str(piece.dtype),
                "shape": list(gshape),
                "num_shards": 1,
                "shard_shapes": [list(piece.shape)],
                "shard_indices": [index],
            }
            blobs[f"{key}#s0"] = _encode(piece)
        buf = _io.BytesIO()
        np.savez(buf, **blobs, **{_MANIFEST: np.frombuffer(
            _json.dumps(manifest).encode(), dtype=np.uint8)})
        store.put_file(step, f"process_{pid}.npz", buf.getvalue())
    store.put_file(step, "metadata.json", _json.dumps(
        {"step": step, "num_processes": n}).encode())


def test_cross_topology_restore_to_single_process(tmp_path):
    """The train-on-a-slice / serve-on-one-host lifecycle: a 2-process
    slab checkpoint restores into a 1-process full template, every leaf
    reassembled exactly from all shard files (the
    reference got this from TF full-tensor checkpoints,
    tony-examples/mnist-tensorflow/mnist_distributed.py:46-48)."""
    w = np.arange(16.0, dtype=np.float32).reshape(8, 2)
    b = np.asarray([9.0, -3.0], np.float32)
    _write_slab_checkpoint(
        tmp_path, 4,
        [{"['w']": (w[:4], [[0, 4], [0, 2]], (8, 2))},
         {"['w']": (w[4:], [[4, 8], [0, 2]], (8, 2))}],
        extra_leaf=("['b']", b),
    )
    out = CheckpointManager(tmp_path).restore(
        {"w": jnp.zeros((8, 2)), "b": jnp.zeros(2)}
    )
    np.testing.assert_array_equal(np.asarray(out["w"]), w)
    np.testing.assert_array_equal(np.asarray(out["b"]), b)


def test_cross_topology_restore_onto_different_mesh(tmp_path):
    """The same 2-process slab checkpoint re-shards onto a DIFFERENT mesh
    template (4-way dp) — reassemble global, then place under the
    template's NamedSharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    w = np.arange(16.0, dtype=np.float32).reshape(8, 2)
    _write_slab_checkpoint(
        tmp_path, 1,
        [{"['w']": (w[:4], [[0, 4], [0, 2]], (8, 2))},
         {"['w']": (w[4:], [[4, 8], [0, 2]], (8, 2))}],
    )
    mesh = build_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
    sharding = NamedSharding(mesh, P("dp"))
    template = {"w": jax.device_put(jnp.zeros((8, 2)), sharding)}
    out = CheckpointManager(tmp_path).restore(template)
    assert out["w"].sharding == sharding
    np.testing.assert_array_equal(np.asarray(out["w"]), w)


def test_restore_onto_more_processes_than_saved(tmp_path):
    """The fewer-to-more direction: a 1-process checkpoint restored by a
    2-process gang. Rank 1 has no shard file of its own — it must
    reassemble from the donor files (process 0's manifest), not silently
    return None while rank 0 restores (a diverged gang deadlocks at the
    first collective)."""
    state = {"w": jnp.arange(8.0), "step": jnp.asarray(3, jnp.int32)}
    CheckpointManager(tmp_path).save(3, state, blocking=True)
    for pid in (0, 1):
        mgr = CheckpointManager(tmp_path, process_id=pid, num_processes=2)
        out = mgr.restore(
            {"w": jnp.zeros(8), "step": jnp.zeros((), jnp.int32)}
        )
        assert out is not None, f"rank {pid} restore returned None"
        np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(8.0))
        assert int(out["step"]) == 3


def test_cross_topology_incomplete_coverage_raises(tmp_path):
    """Shard files whose union does not tile the global array are a torn
    or inconsistent checkpoint — restore must refuse, not zero-fill."""
    w = np.arange(16.0, dtype=np.float32).reshape(8, 2)
    _write_slab_checkpoint(
        tmp_path, 1,
        [{"['w']": (w[:4], [[0, 4], [0, 2]], (8, 2))},
         {"['w']": (w[:2], [[0, 2], [0, 2]], (8, 2))}],  # rows 4-8 nowhere
    )
    with pytest.raises(ValueError, match="does not cover"):
        CheckpointManager(tmp_path).restore({"w": jnp.zeros((8, 2))})


def test_structure_mismatch_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0), blocking=True)
    with pytest.raises(ValueError, match="structure changed"):
        mgr.restore({"totally": jnp.zeros(2)})


def test_restore_preserves_sharding(tmp_path):
    """Restored leaves land with the template's NamedSharding — the
    per-process sharded restore the multi-chip path needs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=8), devices=jax.devices()[:8])
    sharding = NamedSharding(mesh, P("dp"))
    state = {"w": jax.device_put(jnp.arange(16.0), sharding)}
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, state, blocking=True)
    out = mgr.restore(state)
    assert out["w"].sharding == sharding
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(16.0))


def test_trainstate_roundtrip_on_mesh(tmp_path):
    """The real thing: a make_train_step TrainState (step + params +
    adamw opt_state, sharded over a dp×tp mesh) survives save→restore with
    values and shardings intact, mid-training."""
    from tony_tpu.models import TransformerConfig, make_train_step
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
        d_ff=64, max_seq=32, dtype="float32", remat=False,
    )
    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
    init_fn, step_fn = make_train_step(cfg, mesh)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (4, 17)), jnp.int32
    )
    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        state, _ = step_fn(state, tokens)
        mgr = CheckpointManager(tmp_path)
        mgr.save(int(state.step), state, blocking=True)
        restored = mgr.restore(state)
        assert int(restored.step) == int(state.step) == 1
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
            assert a.sharding == b.sharding
        # training continues from the restored state
        resumed, metrics = step_fn(restored, tokens)
        assert int(resumed.step) == 2 and np.isfinite(float(metrics["loss"]))


def test_sharded_save_restore_across_processes_e2e(tmp_path):
    """2 executor processes checkpoint a global array neither fully owns:
    per-process shard files, completeness gating, and
    make_array_from_single_device_arrays reassembly on restore."""
    cluster = MiniTonyCluster(tmp_path / "cluster")
    conf = cluster.base_conf()
    conf.set(keys.K_FRAMEWORK, "jax")
    conf.set(keys.K_EXECUTES, str(FIXTURES / "ckpt_sharded.py"))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 2)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_SHELL_ENV, f"CKPT_DIR={tmp_path}/ckpt")
    status, coord = cluster.run_job(conf, timeout_s=300)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    # Cross-topology epilogue on REAL 2-process shard files: this test
    # process (1 process) reassembles the global array the cluster saved
    # sharded — the serve-after-train path — and re-shards it onto a
    # local mesh.
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    mgr = CheckpointManager(tmp_path / "ckpt")  # process 0 of 1
    meta = mgr._saved_num_processes(1)
    assert meta == 2, "fixture should have saved from 2 processes"
    # global length from the manifest (device count inside the cluster
    # executors is an executor-env detail this test must not hardcode)
    (n,) = mgr._read_shard_file(1, 0)[0]["['x']"]["shape"]
    out = mgr.restore({"x": jnp.zeros(n)})
    np.testing.assert_array_equal(
        np.asarray(out["x"]), np.arange(n, dtype=np.float32)
    )
    mesh = build_mesh(MeshSpec(dp=2), devices=jax.devices()[:2])
    sharded = jax.device_put(jnp.zeros(n), NamedSharding(mesh, P("dp")))
    out2 = mgr.restore({"x": sharded})
    assert out2["x"].sharding == sharded.sharding
    np.testing.assert_array_equal(
        np.asarray(out2["x"]), np.arange(n, dtype=np.float32)
    )


@pytest.mark.slow
def test_resnet_gang_fault_restart_e2e(tmp_path):
    """BASELINE config 5 (CI-scaled): 2 gang-scheduled workers train the
    in-framework ResNet; worker 0 crashes mid-run, the whole session
    restarts, both workers resume from checkpoints and finish."""
    cluster = MiniTonyCluster(tmp_path / "cluster")
    conf = cluster.base_conf()
    conf.set(keys.K_FRAMEWORK, "jax")
    conf.set(keys.K_EXECUTES, str(FIXTURES / "resnet_train.py"))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 2)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_AM_RETRY_COUNT, 1)
    conf.set(keys.K_SHELL_ENV, f"CKPT_DIR={tmp_path}/ckpt")
    status, coord = cluster.run_job(conf, timeout_s=600)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    assert coord.session.session_id == 2  # fault-restarted once


# ---------------------------------------------------------------------------
# Object-store (gs://) checkpointing: per-object
# PUTs are atomic, metadata.json is the commit marker, completeness is
# reader-side. Runs over FileObjectStorage (the MiniDFS analogue).
# ---------------------------------------------------------------------------

@pytest.fixture
def gcs_emulator(tmp_path):
    from tony_tpu.cloud import set_default_storage
    from tony_tpu.cloud.gcs import FileObjectStorage

    store = FileObjectStorage(tmp_path / "objects")
    set_default_storage(store)
    yield store
    set_default_storage(None)


def test_gs_roundtrip_and_bf16(gcs_emulator):
    state = {"w": jnp.asarray([1.5, -2.25, 3.0], jnp.bfloat16),
             "step": jnp.asarray(7, jnp.int32)}
    mgr = CheckpointManager("gs://ckpts/job1")
    mgr.save(7, state, blocking=True)
    out = mgr.restore(state)
    assert out["w"].dtype == jnp.bfloat16 and int(out["step"]) == 7
    np.testing.assert_array_equal(
        np.asarray(out["w"], np.float32), [1.5, -2.25, 3.0]
    )
    # no tmp objects: atomic PUTs need no rename dance (the .json
    # sidecar is the per-process commit record, not a tmp file)
    keys_ = gcs_emulator.list_prefix("gs://ckpts/job1/")
    assert sorted(keys_) == ["job1/step_7/metadata.json",
                             "job1/step_7/process_0.json",
                             "job1/step_7/process_0.npz"]


def test_gs_commit_marker_gates_completeness(gcs_emulator):
    p0 = CheckpointManager("gs://ckpts/j", process_id=0, num_processes=2)
    p1 = CheckpointManager("gs://ckpts/j", process_id=1, num_processes=2)
    p0.save(1, _state(1.0), blocking=True)
    assert p0.latest_step() is None  # marker present, shard 1 missing
    p1.save(1, _state(1.5), blocking=True)
    assert p0.latest_step() == 1
    assert float(p1.restore(_state(0.0))["params"]["w"][0, 0]) == 1.5


def test_gs_gc_reclaims_torn_prefixes(gcs_emulator):
    mgr = CheckpointManager("gs://ckpts/g", max_to_keep=2,
                            torn_gc_grace_s=0.0)
    mgr.save(1, _state(1.0), blocking=True)
    # a crash leftover: shard object without its commit marker
    gcs_emulator.put_bytes("gs://ckpts/g/step_0/process_0.npz", b"torn")
    time.sleep(0.01)
    for s in (2, 3):
        mgr.save(s, _state(float(s)), blocking=True)
    assert mgr._complete_steps() == [2, 3]
    assert not gcs_emulator.exists("gs://ckpts/g/step_0/process_0.npz")
    # max_to_keep pruned step 1's objects too
    assert not gcs_emulator.exists("gs://ckpts/g/step_1/metadata.json")


def test_gs_recent_torn_prefix_survives_gc(gcs_emulator):
    mgr = CheckpointManager("gs://ckpts/r", max_to_keep=2,
                            torn_gc_grace_s=3600.0)
    mgr.save(1, _state(1.0), blocking=True)
    gcs_emulator.put_bytes("gs://ckpts/r/step_0/process_0.npz", b"inflight")
    for s in (2, 3):
        mgr.save(s, _state(float(s)), blocking=True)
    assert gcs_emulator.exists("gs://ckpts/r/step_0/process_0.npz")


def test_gs_cross_topology_restore(gcs_emulator):
    """The topology-portable reassembly path over the OBJECT store: a
    2-process slab checkpoint under gs:// restores into a 1-process full
    template — donor shard files fetched as objects, values exact."""
    w = np.arange(16.0, dtype=np.float32).reshape(8, 2)
    from tony_tpu.checkpoint import _ObjectCheckpointStore

    _write_slab_checkpoint(
        None, 2,
        [{"['w']": (w[:4], [[0, 4], [0, 2]], (8, 2))},
         {"['w']": (w[4:], [[4, 8], [0, 2]], (8, 2))}],
        store=_ObjectCheckpointStore("gs://ckpts/xtopo"),
    )
    out = CheckpointManager("gs://ckpts/xtopo").restore(
        {"w": jnp.zeros((8, 2))}
    )
    np.testing.assert_array_equal(np.asarray(out["w"]), w)


def test_gs_restore_on_session_retry_e2e(tmp_path):
    """Resume-on-retry against the object store: session 1 checkpoints to
    gs:// and crashes at step 5; the retried session restores from the
    bucket and finishes — no filesystem anywhere in the checkpoint path."""
    cluster = MiniTonyCluster(tmp_path / "cluster")
    conf = cluster.base_conf()
    conf.set(keys.K_FRAMEWORK, "jax")
    conf.set(keys.K_EXECUTES, str(FIXTURES / "ckpt_train.py"))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 1)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_AM_RETRY_COUNT, 1)
    conf.set(
        keys.K_SHELL_ENV,
        "CKPT_DIR=gs://ckpts/retry,"
        f"TONY_GCS_EMULATOR_DIR={tmp_path / 'objects'}",
    )
    status, coord = cluster.run_job(conf, timeout_s=180)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    assert coord.session.session_id == 2
    import os

    os.environ["TONY_GCS_EMULATOR_DIR"] = str(tmp_path / "objects")
    try:
        from tony_tpu.cloud import set_default_storage

        set_default_storage(None)  # rebuild from the env var
        assert CheckpointManager("gs://ckpts/retry").latest_step() == 10
    finally:
        del os.environ["TONY_GCS_EMULATOR_DIR"]
        set_default_storage(None)


def test_restore_on_session_retry_e2e(tmp_path):
    """Full-stack resume: session 1 checkpoints every step and crashes at
    step 5; the retried session restores from step 5 and finishes — the
    orchestrator-restart + checkpoint contract of SURVEY §5.4."""
    cluster = MiniTonyCluster(tmp_path / "cluster")
    conf = cluster.base_conf()
    conf.set(keys.K_FRAMEWORK, "jax")
    conf.set(keys.K_EXECUTES, str(FIXTURES / "ckpt_train.py"))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 1)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_AM_RETRY_COUNT, 1)
    conf.set(keys.K_SHELL_ENV, f"CKPT_DIR={tmp_path}/ckpt")
    status, coord = cluster.run_job(conf, timeout_s=180)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    assert coord.session.session_id == 2  # second session finished the job
    # checkpoints survive: step 10 is the newest complete one
    assert CheckpointManager(tmp_path / "ckpt").latest_step() == 10


# ---------------------------------------------------------------------------
# Staged pipeline, differential saves, commit sidecars, live migration
# (checkpoint/ package). The fallback contract under test everywhere: a
# torn/corrupt/chain-broken step costs one interval of progress, never
# the job.
# ---------------------------------------------------------------------------
import json
import os
import signal
import subprocess
import threading

from tony_tpu import constants
from tony_tpu.checkpoint import FlushSignal
from tony_tpu.resilience import latest_complete_step


def _diff_state(val: float, static: float = 1.0):
    """A tree with a large STATIC leaf (the differential win) plus small
    hot leaves that change every save."""
    return {
        "hot": jnp.full((16, 4), float(val)),
        "frozen": jnp.full((512, 8), float(static)),
        "step": jnp.asarray(int(val), jnp.int32),
    }


def _arm_fault_plan(monkeypatch, plan: dict) -> None:
    """Point the user-process fault singletons at a fresh TONY_FAULT_PLAN."""
    from tony_tpu.resilience import faults as faults_mod

    monkeypatch.setenv(constants.TONY_FAULT_PLAN, json.dumps(plan))
    monkeypatch.setattr(faults_mod, "_env_plan", None)
    monkeypatch.setattr(faults_mod, "_ckpt_faults", False)


class _GatedStore:
    """Store wrapper that parks shard uploads on an Event — the
    controllable slow store for pipeline-overlap tests."""

    def __init__(self, inner, gate: threading.Event) -> None:
        self._inner = inner
        self._gate = gate
        self.shard_puts = 0

    def put_file(self, step, name, data):
        if name.endswith(".npz"):
            self.shard_puts += 1
            assert self._gate.wait(timeout=30.0), "gate never opened"
        return self._inner.put_file(step, name, data)

    def __getattr__(self, attr):
        return getattr(self._inner, attr)


def test_pipeline_overlaps_saves_and_save_call_does_not_block(tmp_path):
    """With depth 2, two saves ride the pipeline concurrently while the
    store is wedged, and the save() calls themselves return immediately
    — the persist wall is off the step path."""
    gate = threading.Event()
    mgr = CheckpointManager(tmp_path, pipeline_depth=2)
    mgr._store = _GatedStore(mgr._store, gate)
    t0 = time.monotonic()
    mgr.save(1, _state(1.0))
    mgr.save(2, _state(2.0))
    call_wall = time.monotonic() - t0
    assert call_wall < 5.0  # snapshot only; the store is parked
    assert mgr._pipeline.inflight() == 2
    assert mgr.latest_step() is None  # nothing committed yet
    gate.set()
    mgr.wait()
    assert mgr._pipeline.inflight() == 0
    assert mgr.latest_step() == 2
    assert mgr.last_committed_step == 2


def test_pipeline_depth_backpressures_the_caller(tmp_path):
    """Depth 1 + a wedged store: the second save must BLOCK (bounded
    host memory beats an unbounded snapshot queue) until the first
    commits."""
    gate = threading.Event()
    mgr = CheckpointManager(tmp_path, pipeline_depth=1)
    mgr._store = _GatedStore(mgr._store, gate)
    mgr.save(1, _state(1.0))
    entered = threading.Event()
    done = threading.Event()

    def second():
        entered.set()
        mgr.save(2, _state(2.0))
        done.set()

    t = threading.Thread(target=second, daemon=True)
    t.start()
    assert entered.wait(5.0)
    assert not done.wait(0.3), "save #2 should block at depth 1"
    gate.set()
    assert done.wait(30.0), "save #2 never unblocked"
    mgr.wait()
    assert mgr.latest_step() == 2


def test_differential_save_skips_unchanged_leaves_and_restores(tmp_path):
    """Steps 2..3 reference the frozen leaf's bytes in step 1 instead of
    rewriting them: measurably fewer bytes on disk, exact values on
    restore (newest AND an explicit mid-chain step)."""
    mgr = CheckpointManager(tmp_path, full_every=100)
    for s in (1, 2, 3):
        mgr.save(s, _diff_state(s), blocking=True)
    sc1 = json.loads((tmp_path / "step_1/process_0.json").read_text())
    sc3 = json.loads((tmp_path / "step_3/process_0.json").read_text())
    assert sc1["kind"] == "full" and sc1["base_steps"] == []
    assert sc3["kind"] == "diff" and sc3["base_steps"] == [1]
    full_bytes = (tmp_path / "step_1/process_0.npz").stat().st_size
    diff_bytes = (tmp_path / "step_3/process_0.npz").stat().st_size
    assert diff_bytes < full_bytes * 0.5, (full_bytes, diff_bytes)
    out = mgr.restore(_diff_state(0))
    assert int(out["step"]) == 3
    assert float(out["hot"][0, 0]) == 3.0
    assert float(out["frozen"][0, 0]) == 1.0  # resolved from step 1
    out2 = mgr.restore(_diff_state(0), step=2)
    assert int(out2["step"]) == 2 and float(out2["hot"][0, 0]) == 2.0
    # A fresh manager (no in-memory hash state) restores too.
    out3 = CheckpointManager(tmp_path).restore(_diff_state(0))
    assert int(out3["step"]) == 3


def test_full_every_compaction_and_donor_gc(tmp_path):
    """Every full_every-th save rewrites everything; GC keeps a donor
    step alive exactly as long as a kept diff references it."""
    mgr = CheckpointManager(tmp_path, max_to_keep=2, full_every=3)
    for s in range(1, 8):
        mgr.save(s, _diff_state(s), blocking=True)
    # Pattern: 1 full, 2-3 diff(base 1), 4 full, 5-6 diff(base 4), 7 full.
    kinds = {
        s: json.loads((tmp_path / f"step_{s}/process_0.json").read_text())
        for s in (4, 6, 7)
        if (tmp_path / f"step_{s}/process_0.json").exists()
    }
    assert kinds[4]["kind"] == "full"
    assert kinds[6]["kind"] == "diff" and kinds[6]["base_steps"] == [4]
    assert kinds[7]["kind"] == "full"
    present = {
        int(p.name.split("_")[1])
        for p in tmp_path.iterdir() if p.name.startswith("step_")
    }
    # kept {6, 7} + donor {4}; everything else pruned.
    assert present == {4, 6, 7}
    out = mgr.restore(_diff_state(0), step=6)
    assert int(out["step"]) == 6 and float(out["frozen"][0, 0]) == 1.0


def test_torn_differential_chain_falls_back(tmp_path):
    """A diff step whose base bytes vanished is invisible to BOTH the
    manager and the jax-free probe; readers fall back to the newest
    intact step instead of raising."""
    mgr = CheckpointManager(tmp_path, max_to_keep=10, full_every=3)
    for s in (1, 2, 3, 4):  # 1 full, 2-3 diff(base 1), 4 full
        mgr.save(s, _diff_state(s), blocking=True)
    (tmp_path / "step_1" / "process_0.npz").unlink()
    assert mgr._complete_steps() == [4]
    assert mgr.latest_step() == 4
    assert latest_complete_step(tmp_path) == 4  # probe agrees
    assert mgr.restore(_diff_state(0), step=3) is None
    out = mgr.restore(_diff_state(0))
    assert int(out["step"]) == 4


def test_corrupt_shard_checksum_falls_back(tmp_path):
    """Bit rot the listing cannot see: the newest step's shard fails its
    commit-sidecar sha256 at decode time — restore falls back to the
    previous complete step; the explicit step returns None."""
    mgr = CheckpointManager(tmp_path)
    for s in (1, 2):
        mgr.save(s, _state(float(s)), blocking=True)
    shard = tmp_path / "step_2" / "process_0.npz"
    raw = bytearray(shard.read_bytes())
    raw[-1] ^= 0xFF
    shard.write_bytes(bytes(raw))
    assert mgr.latest_step() == 2  # completeness listing can't see rot
    assert mgr.restore(_state(0.0), step=2) is None
    out = mgr.restore(_state(0.0))
    assert int(out["step"]) == 1
    # restore_resumable pinned at the rotten step falls back too.
    os.environ["TONY_RESUME_STEP"] = "2"
    try:
        assert int(mgr.restore_resumable(_state(0.0))["step"]) == 1
    finally:
        del os.environ["TONY_RESUME_STEP"]


def test_partial_write_fault_withholds_commit(tmp_path, monkeypatch):
    """fail_checkpoint_write mode=partial: the shard lands, the commit
    sidecar + marker are withheld — no reader (manager or probe) ever
    surfaces the torn step."""
    _arm_fault_plan(monkeypatch, {"faults": [
        {"action": "fail_checkpoint_write", "step": 2, "mode": "partial"},
    ]})
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0), blocking=True)
    mgr.save(2, _state(2.0), blocking=True)  # no error raised
    assert (tmp_path / "step_2" / "process_0.npz").exists()
    assert not (tmp_path / "step_2" / "process_0.json").exists()
    assert not (tmp_path / "step_2" / "metadata.json").exists()
    assert mgr.latest_step() == 1
    assert latest_complete_step(tmp_path) == 1
    assert int(mgr.restore(_state(0.0))["step"]) == 1


def test_delay_checkpoint_write_stays_off_step_path(tmp_path, monkeypatch):
    """delay_checkpoint_write slows the PERSIST stage only: the save()
    call returns fast while wait() pays the injected delay — the
    off-step-path proof in miniature."""
    _arm_fault_plan(monkeypatch, {"faults": [
        {"action": "delay_checkpoint_write", "ms": 500, "count": 1},
    ]})
    mgr = CheckpointManager(tmp_path)
    t0 = time.monotonic()
    mgr.save(1, _state(1.0))
    call_s = time.monotonic() - t0
    t1 = time.monotonic()
    mgr.wait()
    drain_s = time.monotonic() - t1
    assert call_s < 0.4, call_s
    assert call_s + drain_s >= 0.5
    assert mgr.latest_step() == 1


def test_flush_signal_fires_once_per_order_at_target(tmp_path, monkeypatch):
    f = tmp_path / "flush.json"
    monkeypatch.setenv(constants.TONY_CKPT_FLUSH_FILE, str(f))
    sig = FlushSignal()
    assert not sig.requested(5)  # no order yet
    f.write_text(json.dumps({"req_id": "r1", "step": 7}))
    assert not sig.requested(6)  # before the target step
    assert sig.requested(7)
    assert not sig.requested(8)  # once per order
    f.write_text(json.dumps({"req_id": "r2"}))  # targetless re-order
    assert sig.requested(1)
    assert not sig.requested(2)
    # Garbage never fires (a torn write is retried by the executor).
    f.write_text("{not json")
    assert not sig.requested(3)


def test_manager_without_flush_env_never_flushes(tmp_path, monkeypatch):
    monkeypatch.delenv(constants.TONY_CKPT_FLUSH_FILE, raising=False)
    mgr = CheckpointManager(tmp_path)
    assert not mgr.flush_requested(1)


@pytest.mark.parametrize("stage", ["shard", "sidecar", "marker"])
def test_sigkill_mid_persist_never_surfaces_torn_step(tmp_path, stage):
    """The satellite's kill-during-persist contract: SIGKILL the saving
    process at each commit boundary of the pipeline; readers only ever
    see complete steps and resume lands on the last committed one."""
    ckpt = tmp_path / "ckpt"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parent.parent))
    proc = subprocess.run(
        [sys.executable, str(FIXTURES / "ckpt_kill_stage.py"),
         str(ckpt), stage],
        capture_output=True, timeout=240, env=env,
    )
    assert proc.returncode == -signal.SIGKILL, (
        proc.returncode, proc.stderr.decode()[-500:],
    )
    mgr = CheckpointManager(ckpt)
    assert mgr.latest_step() == 3
    assert latest_complete_step(ckpt) == 3
    template = {"step": jnp.zeros((), jnp.int32), "w": jnp.zeros(8)}
    # The coordinator would seed the victim's last REPORTED step (4);
    # the reader must fall back to the last COMMITTED one (3).
    os.environ["TONY_RESUME_STEP"] = "4"
    try:
        out = mgr.restore_resumable(template)
    finally:
        del os.environ["TONY_RESUME_STEP"]
    assert int(out["step"]) == 3
    assert float(out["w"][0]) == 3.0


@pytest.mark.slow
def test_preemption_live_migration_e2e(tmp_path):
    """The tentpole acceptance: scheduler preemption of a running,
    checkpointing job becomes live migration — the coordinator orders a
    gang-wide flush over the heartbeat replies, waits for the commit
    marker, and the relaunch resumes within ~one step-interval of the
    victim's last executed step (vs one whole checkpoint interval for
    the non-migrating baseline), with wasted_by_failure bounded
    accordingly in the fleet ledger."""
    from tony_tpu.scheduler.queue import JobState

    with MiniTonyCluster(tmp_path / "cluster") as cluster:
        sched_conf = cluster.base_conf()
        sched_conf.set(keys.K_SCHED_TICK_MS, 50)
        sched_conf.set(keys.K_SCHED_MAX_SLICES, 1)
        daemon = cluster.start_scheduler(sched_conf, serve_http=False)
        ckpt = tmp_path / "ckpt"
        last_step = tmp_path / "last_step.txt"
        conf = cluster.base_conf()
        conf.set(keys.K_EXECUTES, str(FIXTURES / "migrate_train.py"))
        conf.set(keys.K_PYTHON_BINARY, sys.executable)
        conf.set(keys.instances_key("worker"), 1)
        conf.set(keys.instances_key("ps"), 0)
        conf.set(keys.K_CHECKPOINT_LOCATION, str(ckpt))
        conf.set(keys.K_SCHED_PRIORITY, 0)
        conf.set(keys.K_SHELL_ENV,
                 f"LAST_STEP_OUT={last_step},TARGET_STEPS=500,"
                 f"CKPT_EVERY=10,STEP_S=0.15,JAX_PLATFORMS=cpu")
        low = daemon.submit(conf)
        # Let it train past the first periodic checkpoint and INTO the
        # next interval, so migration has something to win.
        deadline = time.monotonic() + 120
        while latest_complete_step(ckpt) is None:
            assert time.monotonic() < deadline, "no first checkpoint"
            time.sleep(0.2)
        while (not last_step.exists()
               or int(last_step.read_text() or 0) < 13):
            assert time.monotonic() < deadline, "job made no progress"
            time.sleep(0.2)
        hi_conf = cluster.base_conf()
        hi_conf.set(keys.K_EXECUTES, str(FIXTURES / "exit_0.py"))
        hi_conf.set(keys.K_PYTHON_BINARY, sys.executable)
        hi_conf.set(keys.instances_key("worker"), 1)
        hi_conf.set(keys.instances_key("ps"), 0)
        hi_conf.set(keys.K_SCHED_PRIORITY, 10)
        hi = daemon.submit(hi_conf)
        assert daemon.wait_job(hi, 180) is JobState.SUCCEEDED
        assert daemon.wait_job(low, 180) is JobState.SUCCEEDED
        job = daemon.job(low)
        assert job.preemptions == 1
        # The flush order must actually have fired (a broken command
        # channel + the 20s migrate-timeout fallback could otherwise
        # land close enough by luck): attempt 1's coordinator stamped
        # it into the job's events.jsonl.
        events_log = Path(job.app_dir) / "events.jsonl"
        kinds = [
            json.loads(line).get("kind")
            for line in events_log.read_text().splitlines() if line
        ]
        assert "checkpoint_flush_requested" in kinds
        assert "checkpoint_progress" in kinds  # the live commit mark
        victim_last = int(last_step.read_text())
        resume = job.resume_step
        assert resume is not None
        # THE migration claim (ISSUE 14 acceptance): the relaunch's
        # resume step is within one SAVE interval (CKPT_EVERY=10) of
        # the victim's last executed step — the flush targets one past
        # the furthest reported step (heartbeat-lagged by up to one
        # ping) and the victim executes a few more while the order
        # lands and teardown drains.
        assert victim_last - resume <= 10, (victim_last, resume)
        # And never worse than the periodic-save baseline; with the
        # flush committed (events asserted above) it is the flushed
        # step, not the last multiple of 10.
        baseline_resume = (victim_last // 10) * 10
        assert resume >= baseline_resume, (resume, baseline_resume)
        # Ledger: the migrated job's recomputation debt is bounded by
        # the resume gap (~seconds), not the whole interval since the
        # last periodic save.
        fleet = daemon.goodput.to_json()["fleet_chip_seconds"]
        assert fleet["productive"] > 0.0
        assert fleet["wasted_by_failure"] <= 10.0, fleet


def test_resave_of_same_step_never_self_references(tmp_path):
    """Regression (found by a live lm_train run): the train loop's
    in-loop save and the final blocking save can hit the SAME step —
    the second save's unchanged leaves must be rewritten, not
    referenced to their own step (a self-ref diff overwrites the very
    shard file its bytes live in, and the step becomes unreadable)."""
    mgr = CheckpointManager(tmp_path, full_every=100)
    mgr.save(1, _diff_state(1), blocking=True)
    mgr.save(2, _diff_state(2), blocking=True)
    mgr.save(2, _diff_state(2), blocking=True)  # the re-save
    sc = json.loads((tmp_path / "step_2/process_0.json").read_text())
    assert 2 not in sc["base_steps"]
    out = CheckpointManager(tmp_path).restore(_diff_state(0))
    assert int(out["step"]) == 2
    assert float(out["hot"][0, 0]) == 2.0
    assert float(out["frozen"][0, 0]) == 1.0


def test_committed_gauge_is_global_not_per_process(tmp_path):
    """Review finding: the tony_ckpt_committed_step gauge feeds the
    goodput checkpoint mark, so it must reflect READER-SIDE (global)
    completeness — process 0 publishes it from the completeness rule;
    a peer's local commit publishes nothing, and process 0's own commit
    of a step whose peer shard is missing must not advance it."""
    from tony_tpu.checkpoint import CKPT_COMMITTED_GAUGE
    from tony_tpu.observability.metrics import default_registry

    def gauge():
        return default_registry().snapshot()["gauges"].get(
            CKPT_COMMITTED_GAUGE
        )

    p0 = CheckpointManager(tmp_path, process_id=0, num_processes=2)
    p1 = CheckpointManager(tmp_path, process_id=1, num_processes=2)
    before = gauge()
    p1.save(41, _state(1.5), blocking=True)  # peer commits FIRST
    assert gauge() == before  # non-marker processes publish nothing
    p0.save(41, _state(1.0), blocking=True)  # completes step 41
    assert gauge() == 41.0
    p0.save(42, _state(2.0), blocking=True)  # p1's shard still missing
    assert gauge() == 41.0  # own commit of an incomplete step: no move
    p1.save(42, _state(2.5), blocking=True)
    assert gauge() == 41.0  # conservative: advances at p0's next save
    p0.save(43, _state(3.0), blocking=True)
    assert gauge() == 42.0
