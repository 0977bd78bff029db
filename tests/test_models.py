"""Model-layer tests on the virtual 8-device CPU mesh: every parallelism
axis is exercised by a real train step, and the sharded result is checked
against a single-device reference run (the strongest correctness statement a
sharding test can make)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tony_tpu.models import (
    MnistConfig,
    TransformerConfig,
    forward,
    init_params,
    lm_loss,
    make_train_step,
)
from tony_tpu.models.train import make_classifier_step
from tony_tpu.parallel.mesh import MeshSpec, build_mesh

CFG = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=4,
    n_heads=4,
    head_dim=16,
    d_ff=128,
    max_seq=64,
    dtype="float32",  # CPU tests compare across meshes; bf16 noise would mask bugs
    remat=False,
)


def _tokens(b=8, t=33, seed=0, vocab=None):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, vocab or CFG.vocab_size, (b, t)), jnp.int32
    )


def _single_device_loss(cfg, tokens, key):
    mesh1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
                 ("dp", "pp", "ep", "sp", "tp"))
    params = jax.jit(lambda k: init_params(k, cfg))(key)
    with jax.sharding.set_mesh(mesh1):
        return float(jax.jit(
            lambda p, t: lm_loss(p, t, cfg, mesh1)
        )(params, tokens))


class TestForward:
    def test_logits_shape_and_finite(self):
        mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        tokens = _tokens()[:, :-1]
        params = jax.jit(lambda k: init_params(k, CFG))(jax.random.key(0))
        with jax.sharding.set_mesh(mesh):
            logits = jax.jit(lambda p, t: forward(p, t, CFG, mesh))(
                params, tokens
            )
        assert logits.shape == (8, 32, CFG.vocab_size)
        assert bool(jnp.isfinite(logits).all())

    def test_sharded_loss_matches_single_device(self):
        tokens = _tokens()
        key = jax.random.key(1)
        want = _single_device_loss(CFG, tokens, key)
        mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        params = jax.jit(lambda k: init_params(k, CFG))(key)
        with jax.sharding.set_mesh(mesh):
            got = float(jax.jit(
                lambda p, t: lm_loss(p, t, CFG, mesh)
            )(params, tokens))
        np.testing.assert_allclose(got, want, rtol=2e-4)


class TestTrainStep:
    def test_gspmd_step_all_axes(self):
        mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        init_fn, step_fn = make_train_step(CFG, mesh, learning_rate=1e-3)
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(0))
            tokens = _tokens()
            losses = []
            for i in range(3):
                state, metrics = step_fn(state, tokens)
                losses.append(float(metrics["loss"]))
        assert int(state.step) == 3
        assert losses[2] < losses[0]  # adamw on a fixed batch must descend

    def test_moe_step_with_expert_parallel(self):
        cfg = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2, head_dim=16,
            d_ff=64, max_seq=64, n_experts=4, expert_top_k=2,
            dtype="float32", remat=False,
        )
        mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
        init_fn, step_fn = make_train_step(cfg, mesh, learning_rate=1e-3)
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(0))
            tokens = _tokens(b=4, t=17, vocab=cfg.vocab_size)
            losses = []
            for _ in range(3):
                state, metrics = step_fn(state, tokens)
                losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        assert losses[2] < losses[0]
        # Router metrics ride the step output on the MoE path.
        for k in ("moe_balance", "moe_zloss", "moe_drop_rate", "moe_entropy"):
            assert np.isfinite(float(metrics[k])), k

    def test_moe_balance_loss_recovers_biased_router(self):
        """Start from a router collapsed onto expert 0 (shrunk weights plus
        an expert-0 column aligned with the batch's activation directions):
        with the Switch balance loss the assignment re-spreads (entropy
        rises to ~ln E, drop rate goes to 0); with the coefficient at 0 the
        collapse persists. This is the failure mode the aux loss exists
        for — dropped tokens silently pass through the residual."""

        def run(balance_coef, steps=40):
            cfg = TransformerConfig(
                vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                head_dim=16, d_ff=64, max_seq=64, n_experts=4,
                expert_top_k=1, dtype="float32", remat=False,
                moe_balance_coef=balance_coef,
            )
            mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
            init_fn, step_fn = make_train_step(cfg, mesh, learning_rate=1e-2)
            rng = np.random.default_rng(1)
            tokens = jnp.asarray(rng.integers(0, 128, (4, 17)), jnp.int32)
            with jax.sharding.set_mesh(mesh):
                state = init_fn(jax.random.key(0))
                embed = state.params["embed"]
                used = jnp.unique(tokens)
                direction = embed[used]
                direction = (
                    direction
                    / jnp.linalg.norm(direction, axis=-1, keepdims=True)
                ).sum(0)
                router = state.params["layers"]["router"] * 0.05
                router = router.at[:, :, 0].add(0.1 * direction)
                state = state._replace(
                    params={**state.params,
                            "layers": {**state.params["layers"],
                                       "router": router}},
                )
                hist = []
                for _ in range(steps):
                    state, metrics = step_fn(state, tokens)
                    hist.append({k: float(v) for k, v in metrics.items()})
            return hist

        with_aux = run(0.05)
        without = run(0.0)
        ln_e = float(np.log(4))
        # Both start collapsed: entropy well below uniform, heavy overflow.
        assert with_aux[0]["moe_entropy"] < 0.65 * ln_e
        assert with_aux[0]["moe_drop_rate"] > 0.3
        # The balance loss re-spreads routing; CE alone does not (top-1
        # combine weights are constant 1, so CE gives the router no signal).
        assert with_aux[-1]["moe_entropy"] > 0.9 * ln_e
        assert with_aux[-1]["moe_drop_rate"] < 0.05
        assert without[-1]["moe_entropy"] < 0.7 * ln_e
        assert without[-1]["moe_drop_rate"] > 0.3

    def test_pipeline_step_pp_tp_dp(self):
        mesh = build_mesh(MeshSpec(dp=2, pp=2, tp=2))
        init_fn, step_fn = make_train_step(
            CFG, mesh, learning_rate=1e-3, pipeline_microbatches=4
        )
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(0))
            tokens = _tokens()
            losses = []
            for _ in range(3):
                state, metrics = step_fn(state, tokens)
                losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        assert losses[2] < losses[0]

    def test_unrolled_layer_loop_matches_scan(self):
        """layer_scan_unroll >= n_layers takes the static Python-loop
        path (grads avoid scan's stacked-grad DUS); it must be the same
        math as the rolled scan — loss AND grads."""
        import dataclasses

        tokens = _tokens()
        params = jax.jit(lambda k: init_params(k, CFG))(jax.random.key(3))
        mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        out = {}
        for unroll in (1, CFG.n_layers):
            cfg = dataclasses.replace(CFG, layer_scan_unroll=unroll)
            with jax.sharding.set_mesh(mesh):
                loss, grads = jax.jit(jax.value_and_grad(
                    lambda p, t, c=cfg: lm_loss(p, t, c, mesh)
                ))(params, tokens)
            out[unroll] = (float(loss), grads)
        np.testing.assert_allclose(out[1][0], out[CFG.n_layers][0],
                                   rtol=1e-6)
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(out[1][1])[0],
            jax.tree_util.tree_flatten_with_path(out[CFG.n_layers][1])[0],
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
                err_msg=str(path),
            )

    def test_pipeline_loss_matches_gspmd(self):
        """Same params, same batch: the pp=2 manual trunk and the GSPMD
        trunk are the same math."""
        tokens = _tokens()
        key = jax.random.key(3)
        params = jax.jit(lambda k: init_params(k, CFG))(key)

        gmesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        with jax.sharding.set_mesh(gmesh):
            want = float(jax.jit(
                lambda p, t: lm_loss(p, t, CFG, gmesh)
            )(params, tokens))

        pmesh = build_mesh(MeshSpec(dp=2, pp=2, sp=2))
        with jax.sharding.set_mesh(pmesh):
            got = float(jax.jit(
                lambda p, t: lm_loss(p, t, CFG, pmesh, pipeline_microbatches=4)
            )(params, tokens))
        np.testing.assert_allclose(got, want, rtol=2e-4)

    def test_interleaved_schedule_matches_gpipe_loss_and_grads(self):
        """Megatron-style virtual stages (v=2) vs GPipe on the same pp=2
        mesh: identical loss AND identical gradients — the round-robin
        chunk placement and wrap-around output collection must be a pure
        re-scheduling of the same math."""
        tokens = _tokens()
        params = jax.jit(lambda k: init_params(k, CFG))(jax.random.key(3))
        pmesh = build_mesh(MeshSpec(dp=2, pp=2, tp=2))

        def loss_fn(schedule, virtual):
            def f(p, t):
                return lm_loss(p, t, CFG, pmesh, pipeline_microbatches=4,
                               pipeline_schedule=schedule,
                               pipeline_virtual=virtual)
            return f

        with jax.sharding.set_mesh(pmesh):
            lg, gg = jax.jit(jax.value_and_grad(loss_fn("gpipe", 1)))(
                params, tokens)
            li, gi = jax.jit(
                jax.value_and_grad(loss_fn("interleaved", 2))
            )(params, tokens)
        np.testing.assert_allclose(float(li), float(lg), rtol=2e-5)
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gg)[0],
            jax.tree_util.tree_flatten_with_path(gi)[0],
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5,
                err_msg=str(path),
            )

    def test_interleaved_pp4_v4_matches_gpipe_loss_and_grads(self):
        """pp=4, virtual=4 (16 virtual stages over a 16-layer trunk): the
        index algebra in _pipeline_interleaved_local is exactly the kind
        that can pass at 2/2 and break at 4/4, so pin
        loss AND grads against GPipe on the same mesh at depth."""
        cfg16 = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=16, n_heads=2, head_dim=16,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
        )
        tokens = _tokens(b=8, t=17, vocab=128)
        params = jax.jit(lambda k: init_params(k, cfg16))(jax.random.key(5))
        pmesh = build_mesh(MeshSpec(pp=4, tp=2))

        def loss_fn(schedule, virtual):
            def f(p, t):
                return lm_loss(p, t, cfg16, pmesh, pipeline_microbatches=8,
                               pipeline_schedule=schedule,
                               pipeline_virtual=virtual)
            return f

        with jax.sharding.set_mesh(pmesh):
            lg, gg = jax.jit(jax.value_and_grad(loss_fn("gpipe", 1)))(
                params, tokens)
            l4, g4 = jax.jit(
                jax.value_and_grad(loss_fn("interleaved", 4))
            )(params, tokens)
            l2, _ = jax.jit(
                jax.value_and_grad(loss_fn("interleaved", 2))
            )(params, tokens)
        np.testing.assert_allclose(float(l4), float(lg), rtol=2e-5)
        np.testing.assert_allclose(float(l2), float(lg), rtol=2e-5)
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gg)[0],
            jax.tree_util.tree_flatten_with_path(g4)[0],
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5,
                err_msg=str(path),
            )

    def test_interleaved_schedule_shrinks_bubble(self):
        """Tick accounting: at v virtual stages the idle bubble per device
        drops from (pp-1) full-stage ticks to (pp-1) chunk ticks — a ~v
        fold reduction of idle time (the schedule implementations derive
        their scan lengths from this same function)."""
        from tony_tpu.parallel.pipeline import schedule_info

        m, pp, layers = 8, 4, 16
        v = 2
        gp = schedule_info("gpipe", m, pp, layers)
        il = schedule_info("interleaved", m, pp, layers, virtual=v)
        # Idle time per device, in units of layer executions: GPipe idles
        # (pp-1) full ticks, interleaved pp chunk-ticks of 1/v the work —
        # a ((pp-1)/pp)*v-fold shrink (1.5x here).
        gp_idle = gp.bubble_fraction * gp.ticks * gp.tick_layers
        il_idle = il.bubble_fraction * il.ticks * il.tick_layers
        assert gp_idle == pytest.approx((pp - 1) * layers / pp)
        assert il_idle == pytest.approx(layers / v)
        assert il_idle < gp_idle / (((pp - 1) / pp) * v * 0.99)
        # Same useful work either way: m microbatches x all layers / pp —
        # exact in both schedules (the accounting must conserve work).
        assert gp.ticks * gp.tick_layers * (1 - gp.bubble_fraction) == (
            pytest.approx(m * layers / pp)
        )
        assert il.ticks * il.tick_layers * (1 - il.bubble_fraction) == (
            pytest.approx(m * layers / pp)
        )

    def test_moe_pipeline_matches_gspmd_loss_and_grads(self):
        """MoE through the pipeline trunk: pp=2×ep=2
        ×tp=2 manual-collective experts (resident E/ep slabs, all_to_all
        token exchange) produce the same total loss AND gradients as the
        GSPMD MoE trunk on a dp=2×ep=2×tp=2 mesh. Capacity factor = E so
        nothing drops — the two trunks then compute identical math."""
        cfg = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=2, n_heads=2,
            head_dim=16, d_ff=64, max_seq=64, dtype="float32",
            remat=False, n_experts=4, expert_top_k=2, capacity_factor=4.0,
        )
        tokens = _tokens(b=8, t=17, vocab=128)
        params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(7))
        gmesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
        pmesh = build_mesh(MeshSpec(pp=2, ep=2, tp=2))

        with jax.sharding.set_mesh(gmesh):
            lg, gg = jax.jit(jax.value_and_grad(
                lambda p, t: lm_loss(p, t, cfg, gmesh)
            ))(params, tokens)
        with jax.sharding.set_mesh(pmesh):
            lp_, gp_ = jax.jit(jax.value_and_grad(
                lambda p, t: lm_loss(p, t, cfg, pmesh,
                                     pipeline_microbatches=1)
            ))(params, tokens)
        np.testing.assert_allclose(float(lp_), float(lg), rtol=2e-5)
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gg)[0],
            jax.tree_util.tree_flatten_with_path(gp_)[0],
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5,
                err_msg=str(path),
            )

    def test_moe_pipeline_microbatched_aux_metrics(self):
        """Microbatched (m=2) MoE pipeline: aux losses accumulate across
        microbatches and average — the train step surfaces finite router
        metrics with zero drops at generous capacity, and the interleaved
        schedule's loss AND grads match GPipe's (same math, different
        scheduling — including the per-schedule aux accumulation)."""
        from tony_tpu.models import make_train_step

        cfg = TransformerConfig(
            vocab_size=128, d_model=32, n_layers=4, n_heads=2,
            head_dim=16, d_ff=64, max_seq=64, dtype="float32",
            remat=False, n_experts=4, expert_top_k=2, capacity_factor=4.0,
        )
        tokens = _tokens(b=8, t=17, vocab=128)
        pmesh = build_mesh(MeshSpec(pp=2, ep=2, tp=2))
        with jax.sharding.set_mesh(pmesh):
            init_fn, step_fn = make_train_step(
                cfg, pmesh, pipeline_microbatches=2
            )
            state = init_fn(jax.random.key(0))
            state, metrics = step_fn(state, tokens)
            lg, gg = jax.jit(jax.value_and_grad(
                lambda p, t: lm_loss(p, t, cfg, pmesh,
                                     pipeline_microbatches=2)
            ))(state.params, tokens)
            li, gi = jax.jit(jax.value_and_grad(
                lambda p, t: lm_loss(p, t, cfg, pmesh,
                                     pipeline_microbatches=2,
                                     pipeline_schedule="interleaved",
                                     pipeline_virtual=2)
            ))(state.params, tokens)
        for k in ("moe_balance", "moe_zloss", "moe_drop_rate",
                  "moe_entropy"):
            assert np.isfinite(float(metrics[k])), k
        assert float(metrics["moe_drop_rate"]) == 0.0
        assert float(metrics["moe_balance"]) >= 1.0 - 1e-5  # Switch minimum
        np.testing.assert_allclose(float(li), float(lg), rtol=2e-5)
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(gg)[0],
            jax.tree_util.tree_flatten_with_path(gi)[0],
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5,
                err_msg=str(path),
            )

    def test_moe_pipeline_rejects_indivisible_experts(self):
        cfg = TransformerConfig(n_experts=3, n_layers=2)
        mesh = build_mesh(MeshSpec(pp=2, ep=2, tp=2))
        params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
        with pytest.raises(ValueError, match="divisible by ep"):
            from tony_tpu.models.transformer import forward_pipeline
            forward_pipeline(
                params, jnp.zeros((4, 8), jnp.int32), cfg, mesh,
                num_microbatches=2,
            )


class TestMnist:
    def test_mnist_cnn_learns(self):
        mesh = build_mesh(MeshSpec(dp=8))
        cfg = MnistConfig(arch="cnn", dtype="float32")
        init_fn, step_fn = make_classifier_step(cfg, mesh, learning_rate=2e-3)
        rng = np.random.default_rng(0)
        # Separable synthetic task: class = brightest quadrant band
        images = jnp.asarray(rng.normal(size=(64, 28, 28, 1)), jnp.float32)
        labels = jnp.asarray(
            (np.asarray(images).reshape(64, -1).mean(-1) > 0).astype(np.int32)
        )
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(0))
            losses = []
            for _ in range(5):
                state, m = step_fn(state, images, labels)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_steps_per_call_matches_sequential(self):
        """steps_per_call=3 (one on-device scan) must produce the same
        final params and metrics as 3 sequential single-step calls over
        the same batches — the fused loop is dispatch batching, not a
        different optimizer."""
        from tony_tpu.models import MnistConfig
        from tony_tpu.models.train import make_classifier_step
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(dp=8))
        cfg = MnistConfig(arch="mlp", dtype="float32")
        rng = np.random.default_rng(2)
        images = jnp.asarray(
            rng.normal(size=(3, 16, 28, 28, 1)), jnp.float32
        )
        labels = jnp.asarray(rng.integers(0, 10, (3, 16)), jnp.int32)

        init1, step1 = make_classifier_step(cfg, mesh, learning_rate=1e-3)
        init3, step3 = make_classifier_step(
            cfg, mesh, learning_rate=1e-3, steps_per_call=3
        )
        with jax.sharding.set_mesh(mesh):
            s1 = init1(jax.random.key(4))
            for i in range(3):
                s1, m1 = step1(s1, images[i], labels[i])
            s3 = init3(jax.random.key(4))
            s3, m3 = step3(s3, images, labels)
        assert int(s1.step) == int(s3.step) == 3
        np.testing.assert_allclose(
            float(m1["loss"]), float(m3["loss"]), rtol=1e-6
        )
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s3.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            )

    def test_mnist_mlp_shapes(self):
        from tony_tpu.models import mnist_apply, mnist_init
        cfg = MnistConfig(arch="mlp", dtype="float32")
        params = mnist_init(jax.random.key(0), cfg)
        logits = mnist_apply(params, jnp.zeros((4, 784)), cfg)
        assert logits.shape == (4, 10)


class TestResNet:
    def _tiny(self):
        from tony_tpu.models import ResNetConfig

        return ResNetConfig(depth=18, width=8, n_classes=10, dtype="float32")

    def test_forward_shapes_and_dtype(self):
        from tony_tpu.models import resnet_apply, resnet_init

        cfg = self._tiny()
        params = resnet_init(jax.random.key(0), cfg)
        x = jnp.ones((2, 32, 32, 3))
        logits = resnet_apply(params, x, cfg)
        assert logits.shape == (2, 10) and logits.dtype == jnp.float32
        assert np.isfinite(np.asarray(logits)).all()

    def test_resnet50_param_count(self):
        from tony_tpu.models import ResNetConfig, resnet_init

        cfg = ResNetConfig(depth=50, width=64, n_classes=1000)
        params = resnet_init(jax.random.key(0), cfg)
        n = sum(x.size for x in jax.tree.leaves(params))
        # canonical ResNet-50 is ~25.6M; GroupNorm keeps the same
        # scale/bias counts as BN's affine params
        assert 24e6 < n < 27e6, n

    def test_group_norm_matches_two_pass_reference(self):
        """The single-accumulation GroupNorm (E[x²]−E[x]² with fp32
        accumulation — the 2.7× ResNet step win) must match the textbook
        two-pass mean/var formulation."""
        from tony_tpu.models.resnet import _group_norm

        rng = np.random.default_rng(0)
        x = jnp.asarray(
            rng.normal(size=(2, 8, 8, 32)) * 3 + 1.5, jnp.float32
        )
        gn = {"scale": jnp.asarray(rng.normal(size=(32,)), jnp.float32),
              "bias": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}

        def reference(x, gn, groups, eps=1e-5):
            b, h, w, c = x.shape
            g = min(groups, c)
            xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
            mean = xf.mean(axis=(1, 2, 4), keepdims=True)
            var = xf.var(axis=(1, 2, 4), keepdims=True)
            xf = (xf - mean) * jax.lax.rsqrt(var + eps)
            return (xf.reshape(b, h, w, c) * gn["scale"] + gn["bias"])

        np.testing.assert_allclose(
            np.asarray(_group_norm(x, gn, 8)),
            np.asarray(reference(x, gn, 8)),
            atol=2e-5, rtol=2e-5,
        )
        # bf16 inputs: fp32 accumulation keeps stats sane
        xb = x.astype(jnp.bfloat16)
        out = _group_norm(xb, gn, 8)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out).astype(np.float32),
            np.asarray(reference(x, gn, 8)),
            atol=0.15,  # bf16 quantization of in/out, not the stats
        )

    def test_unsupported_depth_rejected(self):
        from tony_tpu.models import ResNetConfig

        with pytest.raises(ValueError, match="unsupported depth"):
            ResNetConfig(depth=42).plan

    def test_loss_descends_data_parallel(self):
        from tony_tpu.models import make_image_classifier_step, resnet_apply, resnet_init
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = self._tiny()
        mesh = build_mesh(MeshSpec(dp=8))
        init_fn, step_fn = make_image_classifier_step(
            lambda key: resnet_init(key, cfg),
            lambda params, images: resnet_apply(params, images, cfg),
            mesh,
            learning_rate=5e-3,
        )
        rng = np.random.default_rng(0)
        labels = jnp.asarray(rng.integers(0, 10, (16,)), jnp.int32)
        images = jnp.asarray(
            rng.normal(size=(16, 32, 32, 3))
            + np.asarray(labels)[:, None, None, None] * 0.3,
            jnp.float32,
        )
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(1))
            first = None
            for _ in range(8):
                state, metrics = step_fn(state, images, labels)
                first = first if first is not None else float(metrics["loss"])
            last = float(metrics["loss"])
        assert np.isfinite(last) and last < first


class TestDecode:
    """KV-cache decoding pinned to the training forward — the cached path
    must produce the same distribution the trunk was trained with."""

    def _setup(self):
        from tony_tpu.models import TransformerConfig, init_params

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
        )
        params = init_params(jax.random.key(0), cfg)
        return cfg, params

    @pytest.mark.parametrize("prefill", [False, True])
    def test_prefill_matches_training_forward(self, prefill):
        """Both the dense-scan path and the flash prefill fast path (what
        generate() actually runs) must match the training forward at the
        logits level, not just post-argmax."""
        from tony_tpu.models import advance, forward, init_cache

        cfg, params = self._setup()
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (2, 12)), jnp.int32
        )
        cache = init_cache(cfg, 2, 32)
        logits, cache = advance(params, cache, tokens, cfg, prefill=prefill)
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
        with jax.sharding.set_mesh(mesh):
            full = forward(params, tokens, cfg, mesh)[:, -1].astype(
                jnp.float32
            )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full), atol=2e-4
        )
        assert int(cache["length"]) == 12

    def test_stepwise_decode_matches_full_recompute(self):
        """Greedy generation with the cache must emit the same tokens as
        re-running the full forward on the growing context each step."""
        from tony_tpu.models import forward, generate

        cfg, params = self._setup()
        prompt = jnp.asarray(
            np.random.default_rng(1).integers(0, 64, (2, 6)), jnp.int32
        )
        got = generate(params, prompt, cfg, max_new_tokens=5)
        # reference: uncached greedy loop
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
        ctx = prompt
        want = []
        with jax.sharding.set_mesh(mesh):
            for _ in range(5):
                logits = forward(params, ctx, cfg, mesh)[:, -1]
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                want.append(tok)
                ctx = jnp.concatenate([ctx, tok[:, None]], axis=1)
        want = jnp.stack(want, axis=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_temperature_sampling_varies_with_key(self):
        from tony_tpu.models import generate

        cfg, params = self._setup()
        prompt = jnp.ones((1, 4), jnp.int32)
        a = generate(params, prompt, cfg, 8, temperature=1.0,
                     key=jax.random.key(1))
        b = generate(params, prompt, cfg, 8, temperature=1.0,
                     key=jax.random.key(2))
        assert a.shape == b.shape == (1, 8)
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_moe_decode_matches_training_forward(self):
        """MoE trunk (with GQA): cached greedy decode emits the same tokens
        as full-recompute argmax. capacity_factor is sized so training's
        dispatch drops nothing — decode's grouped expert evaluation never
        drops (inference serves whatever the router picks), so parity
        requires a non-dropping training config."""
        from tony_tpu.models import (
            TransformerConfig, forward, generate, init_params,
        )
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
            n_experts=4, expert_top_k=2, capacity_factor=4.0,
            n_kv_heads=2,
        )
        params = init_params(jax.random.key(7), cfg)
        prompt = jnp.asarray(
            np.random.default_rng(3).integers(0, 64, (2, 6)), jnp.int32
        )
        got = generate(params, prompt, cfg, max_new_tokens=5)
        mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
        ctx = prompt
        want = []
        with jax.sharding.set_mesh(mesh):
            for _ in range(5):
                logits = forward(params, ctx, cfg, mesh)[:, -1]
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                want.append(nxt)
                ctx = jnp.concatenate([ctx, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(
            np.asarray(got), np.stack(want, axis=1)
        )

    @pytest.mark.parametrize("n_experts", [4, 16])
    def test_grouped_expert_layer_equals_the_dense_mixture(self, n_experts):
        """Decode's expert layer (pairs grouped by expert through
        ``lax.ragged_dot``, no capacity) against the mixture written out
        in plain jnp: every expert on every token, weighed by the
        router's normalised top-k weights, at E=4 and E=16. Float32:
        1e-5 is summation order."""
        from tony_tpu.models import TransformerConfig, decode_weights, init_params
        from tony_tpu.models.decode import _moe_mlp_decode
        from tony_tpu.models.transformer import _route_tokens
        from tony_tpu.ops import rms_norm

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
            n_experts=n_experts, expert_top_k=2, capacity_factor=4.0,
        )
        fused = decode_weights(init_params(jax.random.key(11), cfg), cfg)
        lp = jax.tree.map(lambda w: w[1], fused["layers"])
        x = jax.random.normal(jax.random.key(5), (3, 7, 32))
        got, counts = jax.jit(lambda x: _moe_mlp_decode(x, lp, cfg))(x)
        assert int(counts["pairs"].sum()) == 3 * 7 * 2
        hn = rms_norm(x, lp["ln2"], eps=cfg.rms_eps)
        _, _, gvals, gidx = _route_tokens(hn, lp["router"], 2)
        weight = (jax.nn.one_hot(gidx, n_experts) * gvals[..., None]).sum(2)
        gu = jnp.einsum("btd,edf->btef", hn, lp["gate_up"])
        act = jax.nn.silu(gu[..., :64]) * gu[..., 64:]
        want = jnp.einsum("btef,efd,bte->btd", act, lp["w_down"], weight)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=0)

    def test_decode_session_matches_generate_and_refreshes(self):
        from tony_tpu.models import DecodeSession, generate

        cfg, params = self._setup()
        prompt = jnp.asarray(
            np.random.default_rng(9).integers(0, cfg.vocab_size, (2, 5)),
            jnp.int32,
        )
        session = DecodeSession(params, cfg)
        want = generate(params, prompt, cfg, max_new_tokens=6)
        got = session.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # fusion happened once: the session holds the fused layout
        assert "qkv" in session.params["layers"]
        # refresh picks up new weights
        params2 = jax.tree.map(lambda p: p * 1.5, params)
        session.refresh(params2)
        want2 = generate(params2, prompt, cfg, max_new_tokens=6)
        got2 = session.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))

    def test_overflow_and_key_guards(self):
        from tony_tpu.models import generate
        import pytest

        cfg, params = self._setup()
        prompt = jnp.ones((1, 60), jnp.int32)
        with pytest.raises(ValueError, match="max_seq"):
            generate(params, prompt, cfg, max_new_tokens=10)  # 70 > 64
        with pytest.raises(ValueError, match="PRNG key"):
            generate(params, jnp.ones((1, 4), jnp.int32), cfg, 4,
                     temperature=1.0)

    def test_prefill_on_nonempty_cache_rejected(self):
        from tony_tpu.models import advance, init_cache

        cfg, params = self._setup()
        cache = init_cache(cfg, 1, 32)
        _, cache = advance(params, cache, jnp.ones((1, 4), jnp.int32), cfg,
                           prefill=True)
        with pytest.raises(ValueError, match="empty cache"):
            advance(params, cache, jnp.ones((1, 4), jnp.int32), cfg,
                    prefill=True)

    def test_cumulative_cache_overflow_rejected_eagerly(self):
        from tony_tpu.models import advance, init_cache
        import pytest

        cfg, params = self._setup()
        cache = init_cache(cfg, 1, 16)
        _, cache = advance(params, cache,
                           jnp.ones((1, 10), jnp.int32), cfg)
        with pytest.raises(ValueError, match="cannot take"):
            advance(params, cache, jnp.ones((1, 10), jnp.int32), cfg)

    def test_gqa_trains_and_decodes_token_exact(self):
        """GQA config (4 q heads, 2 kv heads): the train step descends and
        cached greedy decode matches full-recompute argmax token-for-token
        — same pin as the MHA parity tests, over the shrunken cache."""
        from tony_tpu.models import (
            TransformerConfig, forward, generate, make_train_step,
        )
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, n_kv_heads=2, dtype="float32", remat=False,
        )
        mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2))
        init_fn, step_fn = make_train_step(cfg, mesh, learning_rate=1e-2)
        rng = np.random.default_rng(5)
        tokens = jnp.asarray(rng.integers(0, 64, (4, 33)), jnp.int32)
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(2))
            losses = []
            for _ in range(5):
                state, metrics = step_fn(state, tokens)
                losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]

        params = jax.device_get(state.params)
        prompt = tokens[:2, :8]
        got = generate(params, prompt, cfg, max_new_tokens=6)
        # Reference: argmax over the full training forward, re-fed greedily.
        ctx = prompt
        want = []
        # Trivial 1-device mesh for the reference loop: its growing seq
        # lengths and batch 2 divide neither the training mesh's sp nor dp.
        dmesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
        with jax.sharding.set_mesh(dmesh):
            for _ in range(6):
                logits = forward(params, ctx, cfg, dmesh)[:, -1]
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                want.append(nxt)
                ctx = jnp.concatenate([ctx, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(
            np.asarray(got), np.stack(want, axis=1)
        )

    def test_gqa_cache_is_smaller(self):
        from tony_tpu.models import TransformerConfig, init_cache

        mha = TransformerConfig(n_heads=8, head_dim=16, d_model=128)
        gqa = TransformerConfig(
            n_heads=8, head_dim=16, d_model=128, n_kv_heads=2
        )
        c_mha = init_cache(mha, 2, 32)
        c_gqa = init_cache(gqa, 2, 32)
        assert c_gqa["k"].size * 4 == c_mha["k"].size

    def test_top_k_and_top_p_sampling(self):
        """top_k=1 must equal greedy argmax regardless of temperature; a
        tight top_p keeps samples inside the nucleus; invalid combos are
        rejected eagerly."""
        from tony_tpu.models import generate

        cfg, params = self._setup()
        prompt = jnp.asarray(
            np.random.default_rng(4).integers(0, 64, (2, 6)), jnp.int32
        )
        greedy = generate(params, prompt, cfg, 6)
        k1 = generate(params, prompt, cfg, 6, temperature=1.0, top_k=1,
                      key=jax.random.key(9))
        np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))

        # Tiny top_p: only the argmax survives the nucleus at any step
        # where one token dominates; with p→0 the threshold keeps exactly
        # the top token, so this must also equal greedy.
        p_small = generate(params, prompt, cfg, 6, temperature=1.0,
                           top_p=1e-6, key=jax.random.key(11))
        np.testing.assert_array_equal(
            np.asarray(greedy), np.asarray(p_small)
        )

        # A permissive nucleus still varies with the key (real sampling).
        a = generate(params, prompt, cfg, 8, temperature=1.0, top_p=0.95,
                     key=jax.random.key(1))
        b = generate(params, prompt, cfg, 8, temperature=1.0, top_p=0.95,
                     key=jax.random.key(2))
        assert not np.array_equal(np.asarray(a), np.asarray(b))

        with pytest.raises(ValueError, match="set a temperature"):
            generate(params, prompt, cfg, 4, top_k=5)
        with pytest.raises(ValueError, match="top_p"):
            generate(params, prompt, cfg, 4, temperature=1.0, top_p=0.0,
                     key=jax.random.key(0))

    def test_tensor_parallel_decode_matches_single_device(self):
        """generate under a tp×dp mesh with sharded params produces the
        same tokens as the single-device path — multi-chip inference
        (megatron head/vocab splits) falls out of GSPMD."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tony_tpu.models import (
            TransformerConfig, decode_weights, generate, init_params,
            param_roles,
        )
        from tony_tpu.models.train import _sharding_for_tree
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
            n_kv_heads=2,
        )
        params = init_params(jax.random.key(5), cfg)
        prompt = jnp.asarray(
            np.random.default_rng(6).integers(0, 64, (2, 6)), jnp.int32
        )
        want = generate(params, prompt, cfg, max_new_tokens=6)

        mesh = build_mesh(MeshSpec(dp=2, tp=4))
        shardings = _sharding_for_tree(params, param_roles(cfg), mesh)
        sharded = jax.device_put(params, shardings)
        # The point of the test: weights really are tp-sharded.
        wq_spec = sharded["layers"]["wq"].sharding.spec
        assert wq_spec[2] == "tp", wq_spec  # heads axis megatron-split
        with jax.sharding.set_mesh(mesh):
            got = generate(sharded, prompt, cfg, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_decode_session_sharded_serving_parity(self):
        """DecodeSession(mesh=...) is the serve-in-place API (r4's
        GSPMD TP-decode parity test promoted to surface): fused weights
        land tp-sharded, the KV cache shards batch-over-dp and
        kv-heads-over-tp, and the generated tokens exactly match the
        single-device session."""
        from tony_tpu.models import (
            DecodeSession, TransformerConfig, init_params,
        )
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
            n_kv_heads=2,
        )
        params = init_params(jax.random.key(5), cfg)
        prompt = jnp.asarray(
            np.random.default_rng(6).integers(0, 64, (4, 6)), jnp.int32
        )
        want = DecodeSession(params, cfg).generate(prompt, max_new_tokens=6)

        mesh = build_mesh(MeshSpec(dp=4, tp=2))
        session = DecodeSession(params, cfg, mesh=mesh)
        spec = session.params["layers"]["qkv"].sharding.spec
        assert spec[2] == "tp", spec          # packed head axis split
        spec = session.params["layers"]["w_down"].sharding.spec
        assert spec[1] == "tp", spec          # ff axis split
        got = session.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # refresh() keeps the serving shardings
        session.refresh(params)
        assert session.params["layers"]["qkv"].sharding.spec[2] == "tp"

    def test_decode_session_sharded_moe_parity(self):
        """Sharded serving of an MoE model: expert weights split over ep,
        ff over tp (decode_param_specs' expert branch) — tokens identical
        to the single-device session."""
        from tony_tpu.models import (
            DecodeSession, TransformerConfig, init_params,
        )
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
            n_kv_heads=2, n_experts=4, expert_top_k=2,
        )
        params = init_params(jax.random.key(3), cfg)
        prompt = jnp.asarray(
            np.random.default_rng(2).integers(0, 64, (4, 5)), jnp.int32
        )
        want = DecodeSession(params, cfg).generate(prompt, max_new_tokens=5)
        mesh = build_mesh(MeshSpec(dp=2, ep=2, tp=2))
        session = DecodeSession(params, cfg, mesh=mesh)
        spec = session.params["layers"]["gate_up"].sharding.spec
        assert tuple(spec)[:2] == (None, "ep"), spec
        got = session.generate(prompt, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_init_cache_sharded_under_mesh(self):
        """Inside a mesh context the KV cache is born sharded (batch over
        dp, kv heads over tp) — not left to GSPMD propagation; outside a
        mesh it is unconstrained. Non-divisible dims fall back to
        replicated."""
        from tony_tpu.models import TransformerConfig, init_cache
        from tony_tpu.parallel.mesh import MeshSpec, build_mesh

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
            n_kv_heads=2,
        )
        mesh = build_mesh(MeshSpec(dp=4, tp=2))
        with jax.sharding.set_mesh(mesh):
            cache = jax.jit(
                lambda: init_cache(cfg, batch=8, max_len=32)
            )()
            assert tuple(cache["k"].sharding.spec)[:4] == (
                None, "dp", None, "tp"
            ), cache["k"].sharding.spec
            # batch=3: dp (4) doesn't divide -> replicated batch axis,
            # heads still sharded
            cache3 = jax.jit(
                lambda: init_cache(cfg, batch=3, max_len=32)
            )()
            assert tuple(cache3["k"].sharding.spec)[:4] == (
                None, None, None, "tp"
            ), cache3["k"].sharding.spec
        plain = init_cache(cfg, batch=8, max_len=32)
        assert plain["k"].sharding.is_fully_replicated or isinstance(
            plain["k"].sharding, jax.sharding.SingleDeviceSharding
        )

    def test_eos_masks_continuation(self):
        """Tokens after a sequence's first EOS come back as pad; the EOS
        itself survives; sequences that never emit EOS are untouched."""
        import numpy as _np

        from tony_tpu.models import generate

        cfg, params = self._setup()
        prompt = jnp.asarray(
            _np.random.default_rng(8).integers(0, 64, (2, 6)), jnp.int32
        )
        plain = _np.asarray(generate(params, prompt, cfg, 8))
        # Pick row 0's second token as the "EOS" so masking must trigger.
        eos = int(plain[0, 1])
        masked = _np.asarray(generate(
            params, prompt, cfg, 8, eos_token=eos, pad_token=63
        ))
        # Expected under the documented rule, derived row-by-row so both
        # the has-EOS and no-EOS properties are always exercised.
        def expect(row):
            row = row.copy()
            hits = _np.flatnonzero(row == eos)
            if hits.size:
                row[hits[0] + 1:] = 63
            return row

        for r in range(plain.shape[0]):
            _np.testing.assert_array_equal(masked[r], expect(plain[r]))

    def test_checked_overflow_caught_under_jit(self):
        """checked=True + checkify turns a traced-length cache overflow into
        a runtime error instead of a clamped, silently-corrupting update."""
        from jax.experimental import checkify

        from tony_tpu.models import advance, init_cache

        cfg, params = self._setup()

        @jax.jit
        def two_steps(params, tokens):
            cache = init_cache(cfg, 1, 16)
            err1, (_, cache) = checkify.checkify(
                lambda: advance(params, cache, tokens, cfg, checked=True)
            )()
            err2, _ = checkify.checkify(
                lambda: advance(params, cache, tokens, cfg, checked=True)
            )()
            return err1, err2

        err1, err2 = two_steps(params, jnp.ones((1, 10), jnp.int32))
        err1.throw()  # 10 <= 16: fine
        import pytest

        with pytest.raises(Exception, match="KV cache overflow"):
            err2.throw()  # 20 > 16


class TestEosIdGeneration:
    """generate(..., eos_id=): done-mask early exit + effective lengths
    (the serving-era EOS contract, distinct from legacy eos_token's
    post-hoc pad masking)."""

    def _setup(self):
        from tony_tpu.models import TransformerConfig, init_params

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
        )
        return cfg, init_params(jax.random.key(0), cfg)

    def test_lengths_and_forced_tail_match_plain_greedy(self):
        from tony_tpu.models import generate

        cfg, params = self._setup()
        prompt = jnp.asarray(
            np.random.default_rng(8).integers(0, 64, (3, 6)), jnp.int32
        )
        plain = np.asarray(generate(params, prompt, cfg, 8))
        eos = int(plain[0, 1])  # row 0 stops at its 2nd token
        res = generate(params, prompt, cfg, 8, eos_id=eos)
        toks, lens = np.asarray(res.tokens), np.asarray(res.lengths)
        for b in range(3):
            hits = np.flatnonzero(plain[b] == eos)
            want_len = hits[0] + 1 if hits.size else 8
            assert lens[b] == want_len
            # Unfinished prefix matches the plain trajectory exactly
            # (positional key schedule), tail is forced to eos_id.
            np.testing.assert_array_equal(toks[b, :want_len],
                                          plain[b, :want_len])
            assert (toks[b, want_len:] == eos).all()

    def test_effective_length_one_when_first_token_is_eos(self):
        from tony_tpu.models import generate

        cfg, params = self._setup()
        prompt = jnp.asarray(
            np.random.default_rng(8).integers(0, 64, (2, 5)), jnp.int32
        )
        plain = np.asarray(generate(params, prompt, cfg, 4))
        res = generate(params, prompt, cfg, 4, eos_id=int(plain[1, 0]))
        assert int(np.asarray(res.lengths)[1]) == 1

    def test_eos_id_and_eos_token_mutually_exclusive(self):
        from tony_tpu.models import generate

        cfg, params = self._setup()
        with pytest.raises(ValueError, match="different contracts"):
            generate(params, jnp.ones((1, 4), jnp.int32), cfg, 4,
                     eos_id=3, eos_token=3)

    def test_temperature_rows_match_plain_path_until_eos(self):
        """The while_loop's positional key schedule: a sampling row that
        has NOT hit EOS draws exactly what the plain scan path draws at
        that step, even while other rows sit done."""
        from tony_tpu.models import generate

        cfg, params = self._setup()
        prompt = jnp.asarray(
            np.random.default_rng(5).integers(0, 64, (3, 6)), jnp.int32
        )
        key = jax.random.key(11)
        plain = np.asarray(generate(
            params, prompt, cfg, 8, temperature=0.9, key=key
        ))
        eos = int(plain[0, 2])
        res = generate(params, prompt, cfg, 8, temperature=0.9, key=key,
                       eos_id=eos)
        toks, lens = np.asarray(res.tokens), np.asarray(res.lengths)
        for b in range(3):
            hits = np.flatnonzero(plain[b] == eos)
            want_len = hits[0] + 1 if hits.size else 8
            assert lens[b] == want_len
            np.testing.assert_array_equal(toks[b, :want_len],
                                          plain[b, :want_len])


class TestDecodeSessionRefresh:
    """Satellite: DecodeSession.refresh + repeated generate — fused
    weights are reused (never re-fused), and the compile-cache
    instrumentation neither double-counts reused executables nor misses
    new signatures across a refresh."""

    def _setup(self):
        from tony_tpu.models import TransformerConfig, init_params

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
            d_ff=64, max_seq=64, dtype="float32", remat=False,
        )
        return cfg, init_params(jax.random.key(0), cfg)

    def test_refresh_with_fused_layout_is_identity(self):
        from tony_tpu.models import DecodeSession

        cfg, params = self._setup()
        session = DecodeSession(params, cfg)
        fused = session.params
        assert "qkv" in fused["layers"]
        session.refresh(fused)  # already fused: adopted as-is, no re-fuse
        assert session.params is fused

    def test_repeated_generate_and_refresh_instrumentation(self):
        from tony_tpu.models import DecodeSession, generate
        from tony_tpu.observability.metrics import default_registry

        cfg, params = self._setup()
        reg = default_registry()

        def totals():
            snap = reg.snapshot()["counters"]
            return (snap.get("tony_compile_cache_hits_total", 0)
                    + snap.get("tony_compile_cache_misses_total", 0))

        session = DecodeSession(params, cfg)
        prompt = jnp.asarray(
            np.random.default_rng(1).integers(0, 64, (2, 5)), jnp.int32
        )
        base = totals()
        session.generate(prompt, max_new_tokens=4)
        assert totals() == base + 1  # first signature instruments once
        session.generate(prompt, max_new_tokens=4)
        assert totals() == base + 1  # cached executable: not re-counted

        # refresh() swaps weights only — same avals, same executable —
        # so the signature must stay marked compiled...
        params2 = jax.tree.map(lambda p: p * 1.5, params)
        session.refresh(params2)
        got = session.generate(prompt, max_new_tokens=4)
        assert totals() == base + 1
        # ...while still producing the refreshed weights' output.
        want = generate(params2, prompt, cfg, max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        # A genuinely new signature (different horizon) counts again.
        session.generate(prompt, max_new_tokens=6)
        assert totals() == base + 2
