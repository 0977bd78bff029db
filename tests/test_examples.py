"""User-facing example scripts submitted through the real CLI — the
analogue of the reference shipping runnable tony-examples and exercising
them through its e2e harness (TestTonyE2E.java:27-253). These run
``python -m tony_tpu.client.cli local`` as a genuine subprocess, exactly as
a user would: the MNIST examples in all three frameworks, the LM
train → generate chain and the serving task."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"


def _submit(example: str, framework: str, workers: int, extra=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    return subprocess.run(
        [
            sys.executable, "-m", "tony_tpu.client.cli", "local",
            "--executes", str(EXAMPLES / example),
            "--framework", framework,
            "--python_binary_path", sys.executable,
            "--conf", f"tony.worker.instances={workers}",
            "--task_params", "--steps 10",
            *extra,
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_jax_example_single_worker():
    """BASELINE config 1: mini-cluster single-worker MNIST."""
    proc = _submit("mnist_distributed.py", "jax", workers=1)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_jax_example_two_workers_dp():
    """BASELINE config 4 analogue: synchronous DP allreduce over the XLA
    collective path (gloo on CPU, ICI on a slice)."""
    proc = _submit("mnist_distributed.py", "jax", workers=2)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
def test_lm_example_trains_and_checkpoints():
    """The flagship-framework showcase: transformer LM (GQA) through
    runtime.initialize + build_job_mesh + make_train_step +
    CheckpointManager, submitted exactly as a user would."""
    proc = _submit(
        "lm_train.py", "jax", workers=1,
        extra=["--task_params",
               "--steps 8 --d-model 32 --n-layers 2 --n-heads 2 "
               "--n-kv-heads 1 --batch 4 --seq 32 --checkpoint-every 4"],
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
def test_lm_generate_serves_trained_checkpoint(tmp_path):
    """The inference half: lm_train checkpoints to a shared dir, then
    lm_generate restores the TrainState through a second CLI job, builds
    a DecodeSession, and decodes — train-to-serve through the framework
    end to end (lm_generate exits 2 when no checkpoint is restorable, so
    rc 0 proves the restore happened)."""
    model_flags = ("--d-model 32 --n-layers 2 --n-heads 2 --n-kv-heads 1")
    ckpt = tmp_path / "lm-ckpt"
    train = _submit(
        "lm_train.py", "jax", workers=1,
        extra=["--task_params",
               f"--steps 8 {model_flags} --batch 4 --seq 32 "
               f"--checkpoint-every 4 --ckpt-dir {ckpt}"],
    )
    assert train.returncode == 0, train.stderr[-2000:]
    gen = _submit(
        "lm_generate.py", "jax", workers=1,
        extra=["--task_params",
               f"--ckpt {ckpt} {model_flags} --max-new 8 "
               f"--prompt 1,5,9:7,2"],
    )
    assert gen.returncode == 0, gen.stderr[-2000:]


@pytest.mark.slow
def test_lm_generate_across_topology_change(tmp_path):
    """The normal TPU lifecycle: train on MORE processes than serve. Two
    dp workers checkpoint a sharded TrainState; a ONE-process serving job
    reassembles the global params from both shard files and decodes
    (cross-topology restore — the reference's TF full-tensor checkpoints
    gave it this for free, mnist-tensorflow/mnist_distributed.py:46-48)."""
    model_flags = "--d-model 32 --n-layers 2 --n-heads 2 --n-kv-heads 1"
    ckpt = tmp_path / "lm-ckpt"
    train = _submit(
        "lm_train.py", "jax", workers=2,
        extra=["--conf", "tony.ps.instances=0",
               "--task_params",
               f"--steps 8 {model_flags} --batch 4 --seq 32 "
               f"--checkpoint-every 4 --ckpt-dir {ckpt}"],
    )
    assert train.returncode == 0, train.stderr[-2000:]
    gen = _submit(
        "lm_generate.py", "jax", workers=1,
        extra=["--conf", "tony.ps.instances=0",
               "--task_params",
               f"--ckpt {ckpt} {model_flags} --max-new 8 "
               f"--prompt 1,5,9:7,2"],
    )
    # rc 0 is the proof: lm_generate exits 2 when no checkpoint is
    # restorable, and a shape-mismatched restore raises (task stdout goes
    # to the per-task log files, not the CLI's stdout).
    assert gen.returncode == 0, gen.stderr[-2000:]


@pytest.mark.slow
def test_lm_train_streams_tokens_corpus_two_workers(tmp_path):
    """--data with a fixed-width token corpus on TWO workers: the
    flagship example trains from the framework data plane — each process
    reads its exactly-once byte-range shard and the step owns device
    placement (host batches; a pre-committed per-process device_put is
    the documented multihost trap)."""
    import numpy as np

    seq, vocab = 32, 512
    rows = np.random.default_rng(0).integers(
        1, vocab, (64, seq + 1)
    ).astype(np.uint16)
    path = tmp_path / "corpus.tokens"
    rows.tofile(path)
    proc = _submit(
        "lm_train.py", "jax", workers=2,
        extra=["--conf", "tony.ps.instances=0",
               "--task_params",
               f"--steps 8 --d-model 32 --n-layers 2 --n-heads 2 "
               f"--n-kv-heads 1 --batch 4 --seq {seq} --data {path}"],
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_lm_train_streams_jsonl_blocks_corpus(tmp_path):
    """--data with a block-compressed jsonl container: the compressed
    corpus format feeds the flagship training example end to end."""
    import numpy as np

    from tony_tpu.io import write_jsonl_blocks

    seq, vocab = 32, 512
    rng = np.random.default_rng(1)
    path = tmp_path / "corpus.jblk"
    try:
        import zstandard  # noqa: F401
        codec = "zstd"
    except ImportError:  # optional dependency; gzip is always available
        codec = "gzip"
    write_jsonl_blocks(
        str(path),
        ({"tokens": rng.integers(1, vocab, seq + 1).tolist()}
         for _ in range(64)),
        codec=codec, block_records=16,
        schema={"tokens": f"int[{seq + 1}]"},
    )
    proc = _submit(
        "lm_train.py", "jax", workers=1,
        extra=["--conf", "tony.ps.instances=0",
               "--task_params",
               f"--steps 8 --d-model 32 --n-layers 2 --n-heads 2 "
               f"--n-kv-heads 1 --batch 4 --seq {seq} --data {path}"],
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
def test_jax_example_with_ps():
    """BASELINE config 2 shape: 1 ps + 2 workers through the gang barrier
    (all three run the user script, like the reference's shared-script ps
    convention; the ps process joins the collective and is untracked in
    completion accounting)."""
    proc = _submit(
        "mnist_distributed.py", "jax", workers=2,
        extra=["--conf", "tony.ps.instances=1"],
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
def test_pytorch_example_ddp():
    """BASELINE config 3: PyTorch DDP-style MNIST, 2 workers over gloo."""
    proc = _submit("mnist_pytorch.py", "pytorch", workers=2)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
def test_tensorflow_example_multiworker():
    """BASELINE configs 2/4 TF shape: 2 MWMS workers + the default ps task
    serving tf.distribute.Server until the chief finishes, all wired from
    the injected TF_CONFIG. Skips (not vacuously passes) without TF."""
    import pytest

    pytest.importorskip("tensorflow")
    proc = _submit("mnist_tensorflow.py", "tensorflow", workers=2)
    assert proc.returncode == 0, proc.stderr[-2000:]


class TestCorpusBatchesUnit:
    """Direct unit coverage of lm_train's corpus_batches guards (the e2e
    tests cover the happy paths; these pin the refusal/empty-shard
    behavior without a cluster)."""

    def _args(self, tmp_path, data, batch=4, seq=8):
        import argparse
        sys.path.insert(0, str(EXAMPLES))
        try:
            import lm_train
        finally:
            sys.path.pop(0)
        ns = argparse.Namespace(
            data=data, batch=batch, seq=seq, vocab=64, steps=1
        )
        return lm_train, ns

    class _Ctx:
        process_id = 0
        num_processes = 1

    def test_mixed_suffixes_refused(self, tmp_path):
        lm_train, args = self._args(tmp_path, "a.jblk,b.tokens")
        import pytest as _pytest

        with _pytest.raises(ValueError, match="mixes"):
            next(lm_train.corpus_batches(args, self._Ctx()))

    def test_empty_path_list_refused(self, tmp_path):
        lm_train, args = self._args(tmp_path, ",")
        import pytest as _pytest

        with _pytest.raises(ValueError, match="no paths"):
            next(lm_train.corpus_batches(args, self._Ctx()))

    def test_undersized_shard_raises_not_hangs(self, tmp_path):
        import numpy as np

        rows = np.zeros((2, 9), np.uint16)  # 2 records < batch of 4
        p = tmp_path / "tiny.tokens"
        rows.tofile(p)
        lm_train, args = self._args(tmp_path, str(p))
        import pytest as _pytest

        with _pytest.raises(RuntimeError, match="no full batch"):
            next(lm_train.corpus_batches(args, self._Ctx()))

    def test_jblk_missing_tokens_field_refused(self, tmp_path):
        """A jsonl-blocks corpus whose records lack 'tokens' must fail
        with a named-field ValueError, not an opaque numpy/XLA error."""
        from tony_tpu.io import write_jsonl_blocks

        p = tmp_path / "c.jblk"
        write_jsonl_blocks(str(p), [{"text": "x"} for _ in range(8)])
        lm_train, args = self._args(tmp_path, str(p))
        import pytest as _pytest

        with _pytest.raises(ValueError, match="'tokens'"):
            next(lm_train.corpus_batches(args, self._Ctx()))

    def test_jblk_wrong_token_width_refused(self, tmp_path):
        """Records whose 'tokens' length != seq+1 must name the expected
        width up front instead of failing downstream at stacking."""
        from tony_tpu.io import write_jsonl_blocks

        p = tmp_path / "c.jblk"
        write_jsonl_blocks(
            str(p), [{"tokens": list(range(5))} for _ in range(8)]
        )
        lm_train, args = self._args(tmp_path, str(p))  # seq=8 -> wants 9
        import pytest as _pytest

        with _pytest.raises(ValueError, match="seq"):
            next(lm_train.corpus_batches(args, self._Ctx()))

    def test_epoch_wrap_yields_endlessly(self, tmp_path):
        import numpy as np

        rows = np.arange(8 * 9, dtype=np.uint16).reshape(8, 9)
        p = tmp_path / "c.tokens"
        rows.tofile(p)
        lm_train, args = self._args(tmp_path, str(p))
        src = lm_train.corpus_batches(args, self._Ctx())
        got = [np.asarray(next(src)) for _ in range(5)]  # > 1 epoch (2/epoch)
        assert all(b.shape == (4, 9) for b in got)
        np.testing.assert_array_equal(got[0], got[2])  # epoch determinism
