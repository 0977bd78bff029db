"""Ops-layer tests: the Pallas kernels run in interpret mode on CPU so
kernel math is validated without TPU hardware; the blockwise-JAX paths are
checked against naive references and through grad."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import (
    apply_rope,
    cache_decode_attention,
    flash_attention,
    rms_norm,
    rope_frequencies,
    softmax_cross_entropy,
)
from tony_tpu.ops.attention import _blockwise_attention_jax, _flash_attention_pallas


def naive_attention(q, k, v, causal=True):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = np.arange(tq)[:, None] >= np.arange(tk)[None, :]
        s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    b, t, h, d = 2, 64, 2, 16
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_jax_path_matches_naive(self, qkv, causal):
        q, k, v = qkv
        out = flash_attention(q, k, v, causal=causal, block_k=16, force_jax=True)
        ref = naive_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_pallas_kernel_interpret_matches_naive(self, qkv, causal):
        q, k, v = qkv
        b, t, h, d = q.shape
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        out = _flash_attention_pallas(
            qf, kf, vf, causal=causal, scale=d**-0.5,
            block_q=16, block_k=16, interpret=True,
        )
        out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
        ref = naive_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_uneven_block_sizes(self, qkv):
        q, k, v = qkv
        out = flash_attention(q, k, v, block_q=48, block_k=48, force_jax=True)
        ref = naive_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_uneven_blocks_pallas_interpret(self, qkv):
        q, k, v = qkv
        b, t, h, d = q.shape
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        out = _flash_attention_pallas(
            qf, kf, vf, causal=True, scale=d**-0.5,
            block_q=48, block_k=48, interpret=True,
        )
        out = out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
        ref = naive_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_cross_attention_lengths(self):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 8, 2, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 32, 2, 8)), dtype=jnp.float32)
        out = flash_attention(q, k, v, causal=False, block_k=8, force_jax=True)
        ref = naive_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def naive_decode_attention(self, q, k, v):
        """Causal with the query block at the END of the key range."""
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        tq, tk = q.shape[1], k.shape[1]
        q_pos = (tk - tq) + np.arange(tq)
        mask = q_pos[:, None] >= np.arange(tk)[None, :]
        s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def test_causal_decode_attends_full_prefix(self):
        """t_q=1 against a t_k=8 cache must attend to ALL 8 keys (decode
        convention), not just key 0."""
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(size=(1, 1, 2, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 8, 2, 8)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 8, 2, 8)), dtype=jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_k=4, force_jax=True)
        ref = self.naive_decode_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_causal_decode_pallas_interpret(self):
        rng = np.random.default_rng(8)
        tq, tk, d = 4, 32, 8
        q = jnp.asarray(rng.normal(size=(2, tq, d)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, tk, d)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, tk, d)), dtype=jnp.float32)
        out = _flash_attention_pallas(
            q, k, v, causal=True, scale=d**-0.5,
            block_q=4, block_k=8, interpret=True,
        )
        ref = self.naive_decode_attention(
            q.reshape(2, tq, 1, d),
            k.reshape(2, tk, 1, d),
            v.reshape(2, tk, 1, d),
        ).reshape(2, tq, d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_grad_matches_naive(self, qkv):
        q, k, v = qkv

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, block_k=16, force_jax=True).sum()

        def loss_naive(q, k, v):
            return naive_attention(q, k, v).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    def test_bf16_runs(self, qkv):
        q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
        out = flash_attention(q, k, v, force_jax=True)
        assert out.dtype == jnp.bfloat16


class TestCacheDecodeAttention:
    """The serving engine's decode read: one query per slot against one
    layer of the stacked cache. The kernel (interpret mode) is pinned to
    the plain-JAX path, which slices the layer out first."""

    @staticmethod
    def _case(dtype, h_kv=2, group=4, d=16, n_l=3, t=64):
        """A stacked cache of ``n_l`` layers x 7 slots x ``t`` positions
        under key blocks of 16 positions: slots at the first key, the
        last of block 0, the first of block 1, mid-block, the last key,
        an inactive lane whose position ran past the cache, and a lane
        that decodes nothing."""
        keys = jax.random.split(jax.random.key(3), 3)
        pos = jnp.asarray([0, 15, 16, 21, t - 1, t + 5, -1], jnp.int32)
        n_s = pos.shape[0]
        q = jax.random.normal(keys[0], (n_s, h_kv * group, d), dtype)
        k_all = jax.random.normal(keys[1], (n_l, n_s, t, h_kv, d), dtype)
        v_all = jax.random.normal(keys[2], (n_l, n_s, t, h_kv, d), dtype)
        return q, k_all, v_all, pos, 16 * h_kv

    @pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                            (jnp.bfloat16, 2e-2)])
    def test_kernel_interpret_matches_jax(self, dtype, atol):
        q, k_all, v_all, pos, block_rows = self._case(dtype)
        for layer in (0, 2):
            got, want = (
                cache_decode_attention(q, k_all, v_all, jnp.int32(layer),
                                       pos, block_rows=block_rows, mode=mode)
                for mode in ("interpret", "jax")
            )
            assert got.dtype == dtype and got.shape == q.shape
            # the lane that decodes nothing: zeros from the kernel, some
            # finite row from the plain path
            assert not np.asarray(got[-1], np.float32).any()
            assert np.isfinite(np.asarray(want[-1], np.float32)).all()
            np.testing.assert_allclose(
                np.asarray(got[:-1], np.float32),
                np.asarray(want[:-1], np.float32), atol=atol,
            )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("h_kv", [8, 4])
    def test_blocks_past_the_last_live_one_are_never_read(self, dtype, h_kv):
        """NaN in every row of every key block past a slot's last live
        one (in all of the slot that decodes nothing): the result stays
        finite and bit for bit, so those blocks are neither computed nor
        left to the mask. And it is, bit for bit, what the same kernel
        gives on a cache CUT to the live blocks of the longest slot."""
        from tony_tpu.ops.attention import (decode_key_block,
                                            decode_last_block)

        q, k_all, v_all, pos, block_rows = self._case(dtype, h_kv=h_kv,
                                                      group=2, n_l=2)
        t = k_all.shape[2]
        block = decode_key_block(t, h_kv, block_rows)
        assert block == 16
        last = np.asarray(decode_last_block(pos, t, block))
        assert last.tolist() == [0, 0, 1, 1, 3, 3, 0]
        dead = (np.arange(t)[None, :] // block > last[:, None]) \
            | (np.asarray(pos) < 0)[:, None]
        dead = jnp.asarray(dead)[None, :, :, None, None]
        run = functools.partial(cache_decode_attention, layer=jnp.int32(1),
                                block_rows=block_rows, mode="interpret")
        clean = run(q, k_all, v_all, pos=pos)
        got = run(q, jnp.where(dead, jnp.nan, k_all),
                  jnp.where(dead, jnp.nan, v_all), pos=pos)
        assert np.isfinite(np.asarray(got, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(clean, np.float32))
        # slots 0..3 live inside the first two blocks
        cut = run(q[:4], k_all[:, :4, :2 * block], v_all[:, :4, :2 * block],
                  pos=pos[:4])
        np.testing.assert_array_equal(np.asarray(cut, np.float32),
                                      np.asarray(clean[:4], np.float32))

    def test_under_a_mesh_each_shard_bounds_by_its_own_slots(self):
        """Slots split over dp 4 and KV heads over tp 2: the kernel runs
        per shard, a shard's parked lanes name blocks of the shard's own
        slots, and the result is the single-device kernel's (a shard's
        block holds other positions, so the sums differ in their order)."""
        from tony_tpu.parallel import MeshSpec, build_mesh

        q, k_all, v_all, pos, block_rows = self._case(jnp.float32, n_l=2)
        # eight slots: the odd lane out and a second lane that reads
        # nothing, first in its shard
        q, k_all, v_all = (jnp.concatenate([x, x[..., :1, :, :]
                                            if x.ndim == 3 else x[:, :1]],
                                           axis=0 if x.ndim == 3 else 1)
                           for x in (q, k_all, v_all))
        pos = jnp.concatenate([pos[4:5] * 0 - 1, pos])   # [-1, 0, 15, ...]
        run = functools.partial(cache_decode_attention, layer=jnp.int32(1),
                                block_rows=block_rows // 2, mode="interpret")
        want = run(q, k_all, v_all, pos=pos)
        mesh = build_mesh(MeshSpec(dp=4, tp=2))
        with jax.sharding.set_mesh(mesh):
            got = jax.jit(functools.partial(run, mesh=mesh))(
                q, k_all, v_all, pos=pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        assert not np.asarray(got)[[0, 7]].any()     # the lanes at -1

    def test_jax_path_is_grouped_softmax_over_the_prefix(self):
        rng = np.random.default_rng(5)
        n_s, t, h_kv, group, d = 2, 8, 2, 2, 4
        q = jnp.asarray(rng.normal(size=(n_s, h_kv * group, d)), jnp.float32)
        k_all = jnp.asarray(rng.normal(size=(2, n_s, t, h_kv, d)), jnp.float32)
        v_all = jnp.asarray(rng.normal(size=(2, n_s, t, h_kv, d)), jnp.float32)
        pos = jnp.asarray([3, 7], jnp.int32)
        out = cache_decode_attention(q, k_all, v_all, jnp.int32(1), pos,
                                     mode="jax")
        for s in range(n_s):
            n = int(pos[s]) + 1
            for h in range(h_kv * group):
                k = np.asarray(k_all[1, s, :n, h // group])
                v = np.asarray(v_all[1, s, :n, h // group])
                w = np.exp(k @ np.asarray(q[s, h]) * d ** -0.5)
                np.testing.assert_allclose(
                    np.asarray(out[s, h]), (w / w.sum()) @ v, atol=2e-5
                )


class TestRmsNorm:
    def test_matches_reference(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(4, 32)), dtype=jnp.float32)
        w = jnp.asarray(rng.normal(size=(32,)), dtype=jnp.float32)
        out = rms_norm(x, w, force_jax=True)
        ref = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_pallas_kernel_interpret_matches_jax(self):
        from tony_tpu.ops.norms import _rms_norm_jax, _rms_norm_pallas

        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=(300, 32)), dtype=jnp.float32)
        w = jnp.asarray(rng.normal(size=(32,)), dtype=jnp.float32)
        out = _rms_norm_pallas(x, w, 1e-6, block_rows=128, interpret=True)
        ref = _rms_norm_jax(x, w, 1e-6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_grad_finite(self):
        x = jnp.ones((2, 8))
        w = jnp.ones((8,))
        g = jax.grad(lambda x: rms_norm(x, w, force_jax=True).sum())(x)
        assert np.isfinite(np.asarray(g)).all()


class TestRope:
    def test_rotation_preserves_norm(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(1, 16, 2, 8)), dtype=jnp.float32)
        cos, sin = rope_frequencies(8, 32)
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(y), axis=-1),
            atol=1e-4,
        )

    def test_position_offset_matches_slicing(self):
        """Sharded application with explicit positions == slicing the full
        result (the sequence-parallel contract)."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(1, 16, 2, 8)), dtype=jnp.float32)
        cos, sin = rope_frequencies(8, 32)
        full = apply_rope(x, cos, sin)
        half = apply_rope(x[:, 8:], cos, sin, positions=jnp.arange(8, 16))
        np.testing.assert_allclose(
            np.asarray(full[:, 8:]), np.asarray(half), atol=1e-6
        )

    def test_position_zero_is_identity(self):
        x = jnp.ones((1, 1, 1, 8))
        cos, sin = rope_frequencies(8, 4)
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)


class TestCrossEntropy:
    def test_matches_naive(self):
        rng = np.random.default_rng(5)
        logits = jnp.asarray(rng.normal(size=(4, 10)), dtype=jnp.float32)
        labels = jnp.asarray(rng.integers(0, 10, size=(4,)))
        out = softmax_cross_entropy(logits, labels)
        p = jax.nn.log_softmax(logits)
        ref = -p[jnp.arange(4), labels].mean()
        np.testing.assert_allclose(float(out), float(ref), atol=1e-6)

    def test_mask_excludes_entries(self):
        logits = jnp.zeros((4, 10))
        labels = jnp.zeros((4,), dtype=jnp.int32)
        where = jnp.asarray([True, True, False, False])
        out = softmax_cross_entropy(logits, labels, where=where)
        full = softmax_cross_entropy(logits[:2], labels[:2])
        np.testing.assert_allclose(float(out), float(full), atol=1e-6)

    def test_extreme_logits_stable(self):
        logits = jnp.asarray([[1e4, -1e4, 0.0]])
        labels = jnp.asarray([0])
        out = softmax_cross_entropy(logits, labels)
        assert np.isfinite(float(out))


class TestFlashBackwardKernels:
    """Pallas backward (dq + dkv kernels) in interpret mode, pinned to the
    blockwise-JAX vjp — the path the TPU takes for training."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("t", [64, 40])  # exact and partial final blocks
    def test_bwd_kernels_match_blockwise_vjp(self, causal, t):
        from tony_tpu.ops.attention import (
            _blockwise_attention_jax,
            _flash_attention_pallas,
            _flash_attention_pallas_bwd,
        )

        rng = np.random.default_rng(0)
        bh, d = 4, 16
        q, k, v = (
            jnp.asarray(rng.normal(size=(bh, t, d)), jnp.float32)
            for _ in range(3)
        )
        g = jnp.asarray(rng.normal(size=(bh, t, d)), jnp.float32)
        scale = d ** -0.5

        out, lse = _flash_attention_pallas(
            q, k, v, causal=causal, scale=scale, block_q=16, block_k=16,
            interpret=True, return_lse=True,
        )
        dq, dk, dv = _flash_attention_pallas_bwd(
            q, k, v, out, lse, g, causal=causal, scale=scale,
            block_q=16, block_k=16, interpret=True,
        )
        ref_out, ref_vjp = jax.vjp(
            lambda q, k, v: _blockwise_attention_jax(
                q, k, v, causal=causal, scale=scale, block_k=16
            ),
            q, k, v,
        )
        rq, rk, rv = ref_vjp(g)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=3e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=3e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=3e-4)

    def test_bwd_cross_attention_lengths(self):
        from tony_tpu.ops.attention import (
            _blockwise_attention_jax,
            _flash_attention_pallas,
            _flash_attention_pallas_bwd,
        )

        rng = np.random.default_rng(1)
        bh, d, t_q, t_k = 2, 16, 16, 48  # decode convention
        q = jnp.asarray(rng.normal(size=(bh, t_q, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(bh, t_k, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(bh, t_k, d)), jnp.float32)
        g = jnp.asarray(rng.normal(size=(bh, t_q, d)), jnp.float32)
        scale = d ** -0.5
        out, lse = _flash_attention_pallas(
            q, k, v, causal=True, scale=scale, block_q=16, block_k=16,
            interpret=True, return_lse=True,
        )
        dq, dk, dv = _flash_attention_pallas_bwd(
            q, k, v, out, lse, g, causal=True, scale=scale,
            block_q=16, block_k=16, interpret=True,
        )
        _, ref_vjp = jax.vjp(
            lambda q, k, v: _blockwise_attention_jax(
                q, k, v, causal=True, scale=scale, block_k=16
            ),
            q, k, v,
        )
        for got, want in zip((dq, dk, dv), ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=3e-4)
