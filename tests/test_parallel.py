"""Parallel-layer tests on the virtual 8-device CPU mesh (conftest.py sets
--xla_force_host_platform_device_count=8 — the mini-cluster idea applied to
devices, per SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.parallel import (
    MeshSpec,
    all_gather_tp,
    all_to_all_ep,
    build_mesh,
    logical_sharding,
    logical_spec,
    pipeline_apply,
    pmean_gradients,
    reduce_scatter_tp,
    ring_attention,
    ring_halo_exchange,
)
from tony_tpu.parallel.mesh import round_up_to_slice


def reference_attention(q, k, v, causal=True):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), dtype=bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class TestMeshSpec:
    def test_auto_factors_all_devices(self):
        spec = MeshSpec.auto(8)
        assert spec.num_devices == 8

    def test_auto_respects_fixed_axes(self):
        spec = MeshSpec.auto(8, tp=4)
        assert spec.tp == 4 and spec.num_devices == 8

    def test_auto_with_fixed_dp_absorbs_leftover(self):
        # Leftover factor must land on an unset axis, not be dropped.
        spec = MeshSpec.auto(16, dp=1)
        assert spec.dp == 1 and spec.num_devices == 16
        spec = MeshSpec.auto(16, dp=2)
        assert spec.dp == 2 and spec.num_devices == 16

    def test_auto_all_axes_fixed_wrong_product(self):
        with pytest.raises(ValueError):
            MeshSpec.auto(16, dp=1, pp=1, ep=1, sp=2, tp=2)

    def test_auto_rejects_non_dividing(self):
        with pytest.raises(ValueError):
            MeshSpec.auto(8, tp=3)

    def test_validate_rejects_wrong_count(self):
        with pytest.raises(ValueError):
            MeshSpec(dp=4).validate(8)

    def test_build_mesh_has_five_axes(self):
        mesh = build_mesh()
        assert set(mesh.axis_names) == {"dp", "pp", "ep", "sp", "tp"}
        assert mesh.devices.size == 8

    def test_round_up_to_slice(self):
        assert round_up_to_slice(3) == 4
        assert round_up_to_slice(8) == 8
        assert round_up_to_slice(9) == 16
        with pytest.raises(ValueError):
            round_up_to_slice(10_000)


class TestLogicalSharding:
    def test_spec_mapping(self):
        assert logical_spec("batch", "seq", "embed") == P(("dp", "ep"), "sp", None)

    def test_unknown_role_raises(self):
        with pytest.raises(KeyError):
            logical_spec("batch", "head")  # typo for "heads"

    def test_sharding_places_array(self):
        mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        x = jnp.zeros((8, 16, 4))
        sh = logical_sharding(mesh, "batch", "seq", None)
        y = jax.device_put(x, sh)
        assert y.sharding.spec == P(("dp", "ep"), "sp", None)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        mesh = build_mesh(MeshSpec(sp=4, tp=2))
        rng = np.random.default_rng(0)
        b, t, h, d = 2, 32, 4, 8
        q = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
        out = ring_attention(q, k, v, mesh, causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_blockwise_inner_loop_matches_at_odd_block(self):
        """block_k smaller than (and not dividing) the shard: the inner
        flash accumulation + padding must stay exact."""
        mesh = build_mesh(MeshSpec(sp=4, dp=2))
        rng = np.random.default_rng(2)
        b, t, h, d = 2, 48, 2, 8  # t_local = 12, block_k 5 -> pad 3
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
            for _ in range(3)
        )
        out = ring_attention(q, k, v, mesh, causal=True, block_k=5)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_long_sequence_bounded_memory(self):
        """t_local >= 1k: the per-shard kv scan runs
        block_k keys at a time, so the [Tlocal, Tlocal] score matrix is
        never materialized; correctness is cross-checked against dense
        attention at seq 2048 over sp=2."""
        mesh = build_mesh(MeshSpec(sp=2, dp=2, tp=2))
        rng = np.random.default_rng(3)
        b, t, h, d = 2, 2048, 2, 16  # t_local = 1024
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
            for _ in range(3)
        )
        out = ring_attention(q, k, v, mesh, causal=True, block_k=256)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-5
        )

    def test_matches_flash_attention_path(self):
        """Ring and the ops-layer flash fallback implement the same math in
        different decompositions; pinning them to each other catches a fix
        applied to one but not the other (the two share no code)."""
        from tony_tpu.ops import flash_attention

        mesh = build_mesh(MeshSpec(sp=4, dp=2))
        rng = np.random.default_rng(5)
        b, t, h, d = 2, 64, 2, 8
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
            for _ in range(3)
        )
        ring = ring_attention(q, k, v, mesh, causal=True, block_k=7)
        flash = flash_attention(q, k, v, causal=True, block_k=16,
                                force_jax=True)
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(flash), atol=2e-5
        )

    def test_grad_flows_long_sequence(self):
        """Backward at t_local=1k: the remat'd double scan must train, not
        OOM on stacked score residuals."""
        mesh = build_mesh(MeshSpec(sp=2, dp=2, tp=2))
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.normal(size=(2, 2048, 2, 8)), dtype=jnp.float32)

        def loss(q):
            return ring_attention(q, q, q, mesh, block_k=256).sum()

        g = jax.grad(loss)(q)
        assert np.isfinite(np.asarray(g)).all()

    def test_grad_flows(self):
        mesh = build_mesh(MeshSpec(sp=2, dp=2, tp=2))
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(2, 8, 2, 4)), dtype=jnp.float32)

        def loss(q):
            return ring_attention(q, q, q, mesh).sum()

        g = jax.grad(loss)(q)
        assert np.isfinite(np.asarray(g)).all()

    @pytest.mark.parametrize("causal", [True, False])
    def test_kernel_path_matches_jax_path(self, causal):
        """The Pallas-in-ring path (kernel="interpret" on CPU) must match
        the independent blockwise-JAX ring — forward AND gradients. This is
        the cross-check that lets "auto" pick the kernel on TPU."""
        mesh = build_mesh(MeshSpec(sp=4, dp=2))
        rng = np.random.default_rng(7)
        b, t, h, d = 2, 64, 2, 8
        q, k, v = (
            jnp.asarray(rng.normal(size=(b, t, h, d)), dtype=jnp.float32)
            for _ in range(3)
        )
        out_k = ring_attention(q, k, v, mesh, causal=causal,
                               kernel="interpret")
        out_j = ring_attention(q, k, v, mesh, causal=causal, kernel="jax")
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_j), atol=2e-5
        )
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(ref), atol=2e-5
        )

        def loss(fn_kernel):
            def inner(q, k, v):
                w = ring_attention(q, k, v, mesh, causal=causal,
                                   kernel=fn_kernel)
                # Non-uniform weighting so lse gradients matter.
                return (w * jnp.arange(1, d + 1, dtype=w.dtype)).sum()
            return inner

        gk = jax.grad(loss("interpret"), argnums=(0, 1, 2))(q, k, v)
        gj = jax.grad(loss("jax"), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gk, gj):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4
            )


class TestMultiSlice:
    """Multi-slice (DCN-spanning) mesh: dp rows tile slice-by-slice so
    inner-axis collectives never cross the slice boundary."""

    def test_dp_outermost_tiles_slices(self):
        devices = jax.devices()[:8]
        mesh = build_mesh(
            MeshSpec(dp=2, sp=2, tp=2), devices=devices, num_slices=2
        )
        arr = mesh.devices  # [dp, pp, ep, sp, tp]
        # dp row 0 == slice 0 (devices 0..3), row 1 == slice 1 (4..7).
        assert {d.id for d in arr[0].flat} == {d.id for d in devices[:4]}
        assert {d.id for d in arr[1].flat} == {d.id for d in devices[4:]}

    def test_auto_spec_pins_dp_to_slices(self):
        mesh = build_mesh(devices=jax.devices()[:8], num_slices=2)
        assert mesh.shape["dp"] == 2

    def test_inner_axis_across_slices_rejected(self):
        with pytest.raises(ValueError, match="dp.*divisible by"):
            build_mesh(MeshSpec(dp=1, tp=8), devices=jax.devices()[:8],
                       num_slices=2)
        with pytest.raises(ValueError, match="equal slices"):
            build_mesh(MeshSpec(dp=3, tp=2), devices=jax.devices()[:6],
                       num_slices=4)

    def test_two_slice_training_dp_across_dcn(self):
        """The dryrun-style 2-slice case: 2 x 4-device groups, full train
        step with dp crossing the "DCN" boundary and tp/sp inside each
        slice — finite, descending loss."""
        import numpy as np

        from tony_tpu.models import TransformerConfig, make_train_step

        cfg = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2, head_dim=16,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
        )
        mesh = build_mesh(
            MeshSpec(dp=2, sp=2, tp=2), devices=jax.devices()[:8],
            num_slices=2,
        )
        init_fn, step_fn = make_train_step(cfg, mesh, learning_rate=1e-2)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 128, (4, 33)), jnp.int32
        )
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(0))
            losses = []
            for _ in range(3):
                state, metrics = step_fn(state, tokens)
                losses.append(float(metrics["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_build_job_mesh_reads_topology_env(self, monkeypatch):
        import json as _json

        import tony_tpu.runtime as rt
        from tony_tpu import constants

        monkeypatch.setenv(
            constants.TONY_SLICE_TOPOLOGY,
            _json.dumps({
                "accelerator_type": "v5litepod-4", "num_slices": 2,
                "hosts_per_slice": 1, "chips_per_slice": 4,
            }),
        )
        mesh = rt.build_job_mesh(devices=jax.devices()[:8])
        assert mesh.shape["dp"] == 2


class TestCollectives:
    def _run(self, mesh, fn, in_specs, out_specs, *args):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )(*args)

    def test_pmean_gradients(self):
        mesh = build_mesh(MeshSpec(dp=4, ep=2))
        x = jnp.arange(8.0).reshape(8, 1)

        def body(g):
            return pmean_gradients({"g": g})["g"]

        out = self._run(mesh, body, (P(("dp", "ep")),), P(("dp", "ep")), x)
        np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.5))

    def test_all_gather_then_reduce_scatter_roundtrip(self):
        mesh = build_mesh(MeshSpec(tp=8))
        x = jnp.arange(16.0).reshape(16, 1)

        def body(x):
            g = all_gather_tp(x, axis=0)          # [16,1] per shard
            return reduce_scatter_tp(g, axis=0)   # back to [2,1], ×8

        out = self._run(mesh, body, (P("tp"),), P("tp"), x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 8)

    def test_all_to_all_ep(self):
        mesh = build_mesh(MeshSpec(ep=4, dp=2))
        # [tokens=4, experts=4]: shard tokens, transpose to shard experts.
        x = jnp.arange(16.0).reshape(4, 4)

        def body(x):
            return all_to_all_ep(x, split_axis=1, concat_axis=0)

        out = self._run(mesh, body, (P("ep"),), P(None, "ep"), x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))

    def test_ring_halo_exchange(self):
        mesh = build_mesh(MeshSpec(sp=4, dp=2))
        x = jnp.arange(16.0).reshape(16, 1)

        def body(x):
            prev, nxt = ring_halo_exchange(x, "sp", halo=1)
            return jnp.concatenate([prev, nxt], axis=0)

        out = self._run(mesh, body, (P("sp"),), P("sp"), x)
        out = np.asarray(out).reshape(4, 2)
        # shard i holds rows [4i..4i+3]; prev-halo = last row of shard i-1,
        # next-halo = first row of shard i+1 (ring wrap).
        for i in range(4):
            assert out[i, 0] == (4 * ((i - 1) % 4) + 3)
            assert out[i, 1] == (4 * ((i + 1) % 4))


class TestPipeline:
    def test_matches_sequential(self):
        n_stages = 4
        mesh = build_mesh(MeshSpec(pp=n_stages, dp=2))
        rng = np.random.default_rng(2)
        dim = 8
        w = jnp.asarray(rng.normal(size=(n_stages, dim, dim)) * 0.3)
        b = jnp.asarray(rng.normal(size=(n_stages, dim)) * 0.1)
        params = {"w": w, "b": b}
        x = jnp.asarray(rng.normal(size=(16, dim)))

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        out = pipeline_apply(
            stage_fn, params, x, mesh=mesh, num_microbatches=4
        )
        expected = x
        for i in range(n_stages):
            expected = jnp.tanh(expected @ w[i] + b[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)

    def test_grad_through_pipeline(self):
        n_stages = 2
        mesh = build_mesh(MeshSpec(pp=n_stages, dp=2, tp=2))
        rng = np.random.default_rng(3)
        dim = 4
        params = {"w": jnp.asarray(rng.normal(size=(n_stages, dim, dim)) * 0.3)}
        x = jnp.asarray(rng.normal(size=(8, dim)))

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        def loss(params):
            return pipeline_apply(
                stage_fn, params, x, mesh=mesh, num_microbatches=2
            ).sum()

        g = jax.grad(loss)(params)

        def ref_loss(params):
            h = x
            for i in range(n_stages):
                h = jnp.tanh(h @ params["w"][i])
            return h.sum()

        g_ref = jax.grad(ref_loss)(params)
        np.testing.assert_allclose(
            np.asarray(g["w"]), np.asarray(g_ref["w"]), atol=1e-5
        )

    def test_bubble_tick_nan_aux_masked(self):
        """Bubble ticks run stage_fn on garbage (zero-initialized)
        activations; an aux that is non-finite there (log 0 → -inf) must
        not poison the accumulator — multiplicative masking would turn
        0 * -inf into NaN, selection masking must not."""
        n_stages = 2
        num_micro = 2
        mesh = build_mesh(MeshSpec(pp=n_stages, dp=4))
        rng = np.random.default_rng(5)
        dim = 4
        w = jnp.asarray(rng.normal(size=(n_stages, dim, dim)) * 0.3)
        # Inputs bounded away from zero so every VALID tick's aux is
        # finite; only garbage ticks see all-zero activations.
        x = jnp.asarray(np.abs(rng.normal(size=(8, dim))) + 1.0)

        def stage_fn(p, xin):
            y = jnp.tanh(xin @ p["w"]) + 2.0  # activations stay positive
            return y, {"logsum": jnp.log(jnp.abs(xin).sum())}

        out, aux = pipeline_apply(
            stage_fn, {"w": w}, x, mesh=mesh,
            num_microbatches=num_micro, stage_aux=True,
        )
        got = float(aux["logsum"])
        assert np.isfinite(got), "bubble-tick -inf leaked into the aux sum"
        # Sequential reference: Σ over (stage, microbatch) of the aux on
        # that stage's true input.
        x_mb = np.asarray(x).reshape(num_micro, -1, dim)
        expect = 0.0
        for u in range(num_micro):
            h = x_mb[u]
            for s in range(n_stages):
                expect += np.log(np.abs(h).sum())
                h = np.tanh(h @ np.asarray(w[s])) + 2.0
        np.testing.assert_allclose(got, expect, rtol=1e-5)

    def test_bubble_tick_nan_aux_masked_interleaved(self):
        """Same NaN-in-bubble regression for the interleaved (virtual
        stage) schedule, whose aux path masks by the chunk-tick window."""
        pp, virtual, num_micro = 2, 2, 2
        mesh = build_mesh(MeshSpec(pp=pp, dp=4))
        rng = np.random.default_rng(6)
        dim = 4
        # leaves [pp, virtual, ...]: element [d, c] = global stage c*pp+d
        w = jnp.asarray(rng.normal(size=(pp, virtual, dim, dim)) * 0.3)
        x = jnp.asarray(np.abs(rng.normal(size=(4, dim))) + 1.0)

        def stage_fn(p, xin):
            y = jnp.tanh(xin @ p["w"]) + 2.0
            return y, {"logsum": jnp.log(jnp.abs(xin).sum())}

        out, aux = pipeline_apply(
            stage_fn, {"w": w}, x, mesh=mesh, num_microbatches=num_micro,
            schedule="interleaved", virtual=virtual, stage_aux=True,
        )
        got = float(aux["logsum"])
        assert np.isfinite(got), "bubble-tick -inf leaked into the aux sum"
        x_mb = np.asarray(x).reshape(num_micro, -1, dim)
        expect = 0.0
        for u in range(num_micro):
            h = x_mb[u]
            for g in range(virtual * pp):  # global virtual stage order
                expect += np.log(np.abs(h).sum())
                h = np.tanh(h @ np.asarray(w[g % pp, g // pp])) + 2.0
        np.testing.assert_allclose(got, expect, rtol=1e-5)

    def test_rejects_bad_microbatch(self):
        mesh = build_mesh(MeshSpec(pp=2, dp=4))
        with pytest.raises(ValueError):
            pipeline_apply(
                lambda p, x: x, {"w": jnp.zeros((2, 1))},
                jnp.zeros((7, 4)), mesh=mesh, num_microbatches=2,
            )


def test_ring_cross_length_causal_skip_exact():
    """The causal ring-step skip must compare GLOBAL positions: with
    t_q != t_k a 'future' kv owner can still hold visible keys."""
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(sp=2, dp=2, tp=2))
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 8, 2, 8)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 8, 2, 8)), dtype=jnp.float32)
    out = ring_attention(q, k, v, mesh, causal=True, block_k=4)
    # dense reference with plain global positions (ring convention)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (8 ** -0.5)
    q_pos = jnp.arange(16)[:, None]
    k_pos = jnp.arange(8)[None, :]
    s = jnp.where((q_pos >= k_pos)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas kernels under a mesh: per_shard (parallel/sharding.py)
# ---------------------------------------------------------------------------
# On a TPU every op that lowers to a Mosaic kernel must run inside a
# shard_map (XLA cannot partition the call). These tests take exactly that
# path on the virtual CPU mesh: `_on_tpu` is steered true from here and
# the kernels run in Pallas interpret mode, so what is compared with the
# single-device result is the kernel path itself, not the blockwise one.


@pytest.fixture
def interpreted_kernels(monkeypatch):
    from tony_tpu.ops import attention, norms

    def interpreted(fn):
        def call(*args, **kwargs):
            kwargs["interpret"] = True
            return fn(*args, **kwargs)
        return call

    monkeypatch.setattr(attention, "_on_tpu", lambda mesh=None: True)
    monkeypatch.setattr(norms, "_on_tpu", lambda mesh=None: True)
    for mod, name in (
        (attention, "_flash_attention_pallas"),
        (attention, "_flash_attention_pallas_bwd"),
        (norms, "_rms_norm_pallas"),
    ):
        monkeypatch.setattr(mod, name, interpreted(getattr(mod, name)))


def _max_err(got, want):
    """Largest error over the leaves, relative to each leaf's scale."""
    got, want = jax.device_get((got, want))
    return max(
        float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1.0))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))
    )


class TestKernelsPerShard:
    @pytest.mark.parametrize("case", ["gqa", "kv_heads_not_divisible",
                                      "batch_not_divisible"])
    def test_flash_attention_matches_single_device(
        self, interpreted_kernels, case
    ):
        from tony_tpu.ops import flash_attention

        b, h_kv = {"gqa": (4, 2), "kv_heads_not_divisible": (4, 1),
                   "batch_not_divisible": (3, 2)}[case]
        mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
        keys = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(keys[0], (b, 32, 4, 16))
        k = jax.random.normal(keys[1], (b, 32, h_kv, 16))
        v = jax.random.normal(keys[2], (b, 32, h_kv, 16))

        def loss(q, k, v, mesh):
            out = flash_attention(q, k, v, block_q=16, block_k=16, mesh=mesh)
            return (out ** 2).sum()

        want = jax.jit(jax.value_and_grad(
            lambda *a: loss(*a, None), argnums=(0, 1, 2)))(q, k, v)
        got = jax.jit(jax.value_and_grad(
            lambda *a: loss(*a, mesh), argnums=(0, 1, 2)))(q, k, v)
        assert _max_err(got, want) < 1e-5

    def test_rms_norm_matches_single_device(self, interpreted_kernels):
        from tony_tpu.ops import rms_norm

        mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        x = jax.random.normal(jax.random.key(0), (4, 32, 64))
        w = jax.random.normal(jax.random.key(1), (64,))

        def loss(x, w, mesh):
            return (rms_norm(x, w, mesh=mesh) ** 2 * jnp.arange(64.0)).sum()

        want = jax.jit(jax.value_and_grad(
            lambda *a: loss(*a, None), argnums=(0, 1)))(x, w)
        step = jax.jit(jax.value_and_grad(
            lambda *a: loss(*a, mesh), argnums=(0, 1)))
        assert _max_err(step(x, w), want) < 1e-5
        # x is replicated over tp: the loss and dw are reduced, dx never —
        # a backward that transposed the shard_map would all-reduce dx
        # (a [2, 16, 64] block per device) over tp as well.
        reduced = [
            line for line in step.lower(x, w).compile().as_text().splitlines()
            if " all-reduce(" in line
        ]
        assert reduced and not any("[2,16,64]" in line for line in reduced)

    def test_called_directly_where_every_axis_is_manual(
        self, interpreted_kernels
    ):
        """Inside a shard_map over the whole mesh (pipeline stages, the
        ring) there is nothing left to partition: the op must not wrap
        itself a second time over axes that are already manual."""
        from tony_tpu.ops import rms_norm
        from tony_tpu.parallel.sharding import auto_axes

        mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
        x = jax.random.normal(jax.random.key(0), (4, 8, 64))
        w = jnp.ones((64,))
        seen = []

        def body(x, w):
            seen.append(auto_axes())
            return rms_norm(x, w)

        got = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("dp"), P()), out_specs=P("dp"),
            check_vma=False,
        ))(x, w)
        assert seen == [{}]
        assert _max_err(got, rms_norm(x, w, force_jax=True)) < 1e-5

    @pytest.mark.parametrize("layout", ["dp2_tp2", "dp2_sp2", "pp2_tp2"])
    def test_lm_loss_and_grads_match_single_device(
        self, interpreted_kernels, layout
    ):
        """Forward and gradients of the whole LM loss: GSPMD trunk with
        heads over tp, GSPMD trunk with the sp ring, and the manual
        pipeline trunk (whose embedding-side norm is the one call outside
        its shard_map)."""
        from tony_tpu.models import TransformerConfig, init_params, lm_loss

        axes, kwargs = {
            "dp2_tp2": (dict(dp=2, tp=2), {}),
            "dp2_sp2": (dict(dp=2, sp=2), {}),
            "pp2_tp2": (dict(pp=2, tp=2), dict(pipeline_microbatches=2)),
        }[layout]
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=32, n_kv_heads=2, dtype="float32", remat=False,
        )
        params = init_params(jax.random.key(0), cfg)
        tokens = jax.random.randint(jax.random.key(1), (4, 33), 0, 64)
        one = build_mesh(MeshSpec(), devices=jax.devices()[:1])
        four = build_mesh(MeshSpec(**axes), devices=jax.devices()[:4])

        def run(mesh, **kw):
            return jax.jit(jax.value_and_grad(
                lambda p, t: lm_loss(p, t, cfg, mesh, **kw)
            ))(params, tokens)

        assert _max_err(run(four, **kwargs), run(one)) < 1e-4
