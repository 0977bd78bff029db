"""Sharded train-step builders over the 5-axis mesh.

The reference delegates all training to the user script and only injects the
distributed env (TaskExecutor.java:126-153); here training is in-framework:
one jitted step — forward, loss, grad, adamw update — with every array's
placement derived from the logical-role tables, so XLA SPMD emits the dp
gradient psum, tp all-gathers and ep all-to-alls without any hand-written
communication.
"""

from __future__ import annotations

import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu import observability
from tony_tpu.observability import stepstats as stepstats_mod

from tony_tpu.models.mnist import MnistConfig, mnist_apply, mnist_init
from tony_tpu.models.transformer import (
    TransformerConfig,
    forward,
    forward_pipeline,
    init_params,
    param_roles,
)
from tony_tpu.ops import softmax_cross_entropy
from tony_tpu.parallel import plan as plan_lib
from tony_tpu.parallel.sharding import logical_sharding


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def _instrumented(step_fn, stats: "stepstats_mod.StepStats | None" = None):
    """Count dispatches + host-side dispatch time into the process
    registry (telemetry plane). Deliberately measures only the DISPATCH
    (async under jit — no sync is forced here): the loss readback the
    caller already does is where step wall time gets reported.

    ``stats`` (observability/stepstats.py) turns the same hook into the
    per-step anatomy feed: the interval between consecutive dispatches
    is the completed step's wall (donation-safe — nothing re-reads the
    donated state), the first batch argument's shape sizes the MFU /
    collective model, and the dispatch time is the ``host`` phase. The
    recorder rides the returned step as ``step.stepstats`` so train
    loops can wire their batch iterator in (``stats.wrap_batches``)."""
    registry = observability.default_registry()
    dispatches = registry.counter("train_step_dispatches_total")
    dispatch_s = registry.histogram("train_step_dispatch_seconds")

    def step(*args, **kwargs):
        if stats is not None:
            stats.step_begin(
                getattr(args[1], "shape", None) if len(args) > 1 else None
            )
        t0 = time.perf_counter()
        out = step_fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        dispatches.inc()
        dispatch_s.observe(dt)
        if stats is not None:
            stats.step_end(dt)
        return out

    step.stepstats = stats
    return step


def _sharding_for_tree(abstract_tree, roles: dict, mesh: Mesh):
    """NamedShardings for any pytree whose dict-keyed subtrees mirror the
    params tree (TrainState.params itself, optax mu/nu copies). A leaf's
    dict-key path is looked up in the nested ``roles`` table; leaves with no
    matching role path (optimizer scalars like adam's count) replicate.
    """

    def axis_size(entry) -> int:
        names = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        return size

    def leaf_sharding(path, leaf):
        node = roles
        for entry in path:
            if isinstance(entry, jax.tree_util.DictKey):
                if isinstance(node, dict) and entry.key in node:
                    node = node[entry.key]
                else:
                    return NamedSharding(mesh, P())
        if isinstance(node, tuple):
            spec = logical_sharding(mesh, *node).spec
            # A dim whose size the mesh axes don't divide replicates instead
            # of erroring (e.g. d_model=64 with dp=3 fsdp): sharding is a
            # placement optimization, never a correctness requirement.
            fixed = [
                e if e is None or dim % axis_size(e) == 0 else None
                for e, dim in zip(spec, leaf.shape)
            ]
            return NamedSharding(mesh, P(*fixed))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf_sharding, abstract_tree)


def _to_global_batch(batch, sharding):
    """Place a host batch for the jitted step. Single-process meshes take
    the plain device_put re-shard; on a multi-process mesh each process
    holds only ITS shard of the global batch, and device_put of differing
    per-process values is wrong API usage (jax's cross-process consistency
    check rejects it — nondeterministically, depending on which collective
    notices first). make_array_from_process_local_data assembles the
    global array from the per-process shards instead; note the jitted
    step then sees the GLOBAL batch shape (num_processes x local).

    A batch that is ALREADY a device array with an equivalent sharding
    (the device_prefetch pipeline places batches with the step's exact
    spec) passes through untouched — re-putting it would queue a second
    device round-trip per batch."""
    if sharding.is_fully_addressable:
        current = getattr(batch, "sharding", None)
        if current is not None:
            try:
                if current.is_equivalent_to(sharding, batch.ndim):
                    return batch
            except (AttributeError, TypeError):
                if current == sharding:
                    return batch
        return jax.device_put(batch, sharding)
    import numpy as np

    return jax.make_array_from_process_local_data(
        sharding, np.asarray(batch)
    )


def lm_loss(
    params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh | None = None,
    *,
    pipeline_microbatches: int | None = None,
    pipeline_schedule: str = "gpipe",
    pipeline_virtual: int = 1,
    return_metrics: bool = False,
):
    """Next-token cross-entropy, plus the MoE router auxiliary losses when
    the config has experts (balance keeps routing uniform, z-loss keeps
    router logits bounded — without them the router can collapse onto few
    experts and dropped tokens silently stop training). tokens: [B, T+1]
    int32. With ``return_metrics`` returns ``(total, metrics)`` where
    metrics includes the raw cross-entropy and per-component router stats.
    """
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    if pipeline_microbatches is not None:
        logits, aux = forward_pipeline(
            params, inputs, cfg, mesh, num_microbatches=pipeline_microbatches,
            schedule=pipeline_schedule, virtual_stages=pipeline_virtual,
            return_aux=True,
        )
    else:
        logits, aux = forward(params, inputs, cfg, mesh, return_aux=True)
    ce = softmax_cross_entropy(logits, labels)
    total = ce
    if aux:
        total = (
            total
            + cfg.moe_balance_coef * aux["moe_balance"]
            + cfg.moe_zloss_coef * aux["moe_zloss"]
        )
    if not return_metrics:
        return total
    metrics = {"cross_entropy": ce, **aux}
    return total, metrics


def make_train_step(
    cfg: TransformerConfig,
    mesh: Mesh | None = None,
    *,
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
    pipeline_microbatches: int | None = None,
    pipeline_schedule: str = "gpipe",
    pipeline_virtual: int = 1,
    optimizer: optax.GradientTransformation | None = None,
    plan: plan_lib.Plan | None = None,
):
    """Returns (init_fn, step_fn), both jitted over ``mesh``.

    init_fn(key) -> TrainState, every leaf placed by its logical roles.
    step_fn(state, tokens[B, T+1]) -> (state', {"loss": f32}); donates the
    old state so params update in place in HBM. ``step_fn.lower`` is the
    jitted step's own ``.lower``.

    ``plan`` (parallel/plan.py) is the declarative alternative to the
    mesh + pipeline kwargs: it supplies the mesh (built from its spec
    when ``mesh`` is None) and the trunk/microbatching knobs in one
    object — the planner's output plugs in directly. Explicit pipeline
    kwargs win over the plan's. Both jitted functions are compile-
    instrumented: their first call lands in ``tony_compile_ms`` and
    counts a persistent-cache hit or miss against the plan-key index.
    """
    if plan is not None:
        if mesh is None:
            mesh = plan.build_mesh()
        if pipeline_microbatches is None:
            pipeline_microbatches = plan.microbatches
            # Explicit schedule/virtual kwargs still win over the plan's:
            # only defaults are replaced.
            if pipeline_schedule == "gpipe" and pipeline_virtual == 1:
                pipeline_schedule = plan.pipeline_schedule
                pipeline_virtual = plan.pipeline_virtual
    if mesh is None:
        raise ValueError("make_train_step needs a mesh or a plan")
    # Measured-autotuner consumption: a persisted record for this exact
    # (model config, mesh topology, jax version) fills whatever the
    # caller (and the plan) left at defaults — never overrides an
    # explicit kwarg. lookup() is a no-op mid-search and one small JSON
    # read otherwise; every miss path returns None.
    from tony_tpu.parallel import autotune as autotune_lib

    tuned = autotune_lib.lookup("lm_train_step", config=cfg, mesh=mesh)
    if tuned is not None:
        if pipeline_microbatches is None and tuned.microbatches is not None:
            pipeline_microbatches = tuned.microbatches
            if pipeline_schedule == "gpipe" and tuned.pipeline_schedule:
                pipeline_schedule = tuned.pipeline_schedule
        cfg = autotune_lib.apply_knobs_to_config(cfg, tuned)
        if tuned.block_q or tuned.block_k:
            from tony_tpu.ops import attention as attention_lib

            attention_lib.set_tuned_blocks(tuned.block_q, tuned.block_k)
    opt = optimizer or optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, weight_decay=weight_decay),
    )
    roles = param_roles(cfg)

    def init_fn(key):
        params = init_params(key, cfg)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt.init(params),
        )

    abstract = jax.eval_shape(init_fn, jax.random.key(0))
    state_sh = _sharding_for_tree(abstract, roles, mesh)
    # Tokens shard over batch only: [B, T+1] has the odd "+1" length that the
    # sp axis can't divide; the shift inside lm_loss re-shards activations
    # onto sp via the constraints in forward().
    batch_sh = logical_sharding(mesh, "batch", None)
    repl = NamedSharding(mesh, P())

    # Everything whose change must invalidate a cached executable rides
    # the plan cache key (argument shapes join at the first call). An
    # EXPLICIT optimizer is a pile of closures with no stable identity
    # (every optax factory returns a 'GradientTransformation'), so its
    # opt-state TREEDEF stands in: adamw/adafactor/sgd/chain arities all
    # differ there. Residual gap: hyperparameters buried inside a custom
    # optimizer (adafactor(1e-3) vs (1e-4)) share a treedef and may
    # read as a hit while XLA, keying on real HLO, recompiles — a
    # metric mislabel only, never a wrong executable.
    fingerprint = {
        "learning_rate": learning_rate,
        "weight_decay": weight_decay,
        "grad_clip": grad_clip,
        "microbatches": pipeline_microbatches,
        "schedule": pipeline_schedule,
        "virtual": pipeline_virtual,
        "optimizer": "default-adamw" if optimizer is None else str(
            jax.tree_util.tree_structure(abstract.opt_state)
        ),
    }
    jit_init = plan_lib.instrument_jit(
        jax.jit(init_fn, out_shardings=state_sh),
        plan_lib.plan_cache_key(
            "lm_train_init", config=cfg, mesh=mesh, plan=plan,
            extra=fingerprint,
        ),
    )

    def step_fn(state: TrainState, tokens: jax.Array):
        (loss, metrics), grads = jax.value_and_grad(lm_loss, has_aux=True)(
            state.params, tokens, cfg, mesh,
            pipeline_microbatches=pipeline_microbatches,
            pipeline_schedule=pipeline_schedule,
            pipeline_virtual=pipeline_virtual,
            return_metrics=True,
        )
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        new_state = TrainState(state.step + 1, params, opt_state)
        return new_state, {"loss": loss, **metrics}

    # Metric structure is config-static: router stats exist for MoE
    # configs on both trunks (GSPMD and, since r5, the pipeline).
    metric_keys = ["loss", "cross_entropy"]
    if cfg.n_experts:
        metric_keys += [
            "moe_balance", "moe_zloss", "moe_drop_rate", "moe_entropy",
        ]
    metrics_sh = {k: repl for k in metric_keys}
    jit_step = plan_lib.instrument_jit(
        jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, metrics_sh),
            donate_argnums=(0,) if (plan is None or plan.donate_state)
            else (),
        ),
        plan_lib.plan_cache_key(
            "lm_train_step", config=cfg, mesh=mesh, plan=plan,
            extra=fingerprint,
        ),
    )

    # Step anatomy: every dispatch of this step feeds the phase/MFU/
    # calibration recorder. Workload sizing comes from the assembled
    # GLOBAL tokens below, not the dispatch-hook shape — on a
    # multi-process mesh the hook only sees this process's shard, which
    # would understate MFU and mis-bucket plan calibration by the
    # process count.
    stats = stepstats_mod.StepStats(
        cfg=cfg, plan=plan, mesh=mesh,
        microbatches=pipeline_microbatches, size_from_shapes=False,
    )

    def step(state, tokens):
        # Re-shard the host batch explicitly: jit rejects (rather than
        # reshards) committed args whose sharding differs from in_shardings
        # (and multi-process meshes need the local->global assembly).
        tokens = _to_global_batch(tokens, batch_sh)
        stats.set_workload(tokens.shape[0], max(tokens.shape[1] - 1, 1))
        return jit_step(state, tokens)

    step = _instrumented(step, stats)
    # The very program the step dispatches, ahead of time:
    # ``step.lower(state, tokens)`` (arrays or ShapeDtypeStructs) gives the
    # ``jax.stages.Lowered`` whose text / compile() show the kernels and
    # collectives — for any devices the mesh names, attached or described.
    step.lower = jit_step.__wrapped__.lower
    return jit_init, step


def make_classifier_step(
    cfg: MnistConfig,
    mesh: Mesh,
    *,
    learning_rate: float = 1e-3,
    steps_per_call: int = 1,
):
    """Data-parallel supervised step for the MNIST models (see
    make_image_classifier_step)."""
    return make_image_classifier_step(
        lambda key: mnist_init(key, cfg),
        lambda params, images: mnist_apply(params, images, cfg),
        mesh,
        learning_rate=learning_rate,
        steps_per_call=steps_per_call,
        config=cfg,
    )


def uint8_image_normalizer(mean: float = 0.0, std: float = 255.0):
    """On-device decode for byte-transferred images: uint8 → fp32
    ``(x - mean) / std`` INSIDE the jitted step. The data plane ships raw
    uint8 over H2D (4× fewer bytes than host-side float32 normalize
    would) and the chip does the cast — pass the result as
    ``make_image_classifier_step(preprocess=...)``."""
    scale = 1.0 / std

    def pre(images):
        return (images.astype(jnp.float32) - mean) * scale

    return pre


def make_image_classifier_step(
    init_params_fn,
    apply_fn,
    mesh: Mesh,
    *,
    learning_rate: float = 1e-3,
    steps_per_call: int = 1,
    preprocess=None,
    config=None,
):
    """Data-parallel supervised step for any image classifier
    ``(params, images) -> logits``: batch split over (dp, ep); params
    replicated (MB-scale at most — fsdp would be pure overhead; the
    transformer path owns the sharded-weights story). Returns
    (init_fn, step_fn).

    ``steps_per_call > 1`` runs that many optimizer steps per dispatch as
    one on-device ``lax.scan``: ``step_fn(state, images, labels)`` then
    takes STACKED batches with a leading [steps_per_call] axis and
    returns the last step's metrics. For small models the per-call
    dispatch (host round-trip) dominates a ~0.5 ms step — the fused loop
    measures (and delivers) actual chip throughput.

    ``preprocess`` runs on the images INSIDE the jitted step (before
    ``apply_fn``), which is the uint8-transfer contract: stream/transfer
    raw bytes, decode (cast + normalize) on device where it fuses into
    the first conv instead of quadrupling the H2D byte volume — see
    ``uint8_image_normalizer`` and docs/DEPLOY.md "Data-plane
    performance"."""
    opt = optax.adam(learning_rate)

    def init_fn(key):
        params = init_params_fn(key)
        return TrainState(jnp.zeros((), jnp.int32), params, opt.init(params))

    repl = NamedSharding(mesh, P())
    state_sh = jax.tree.map(
        lambda _: repl, jax.eval_shape(init_fn, jax.random.key(0))
    )
    n = steps_per_call
    batch_sh = NamedSharding(
        mesh, P(("dp", "ep")) if n == 1 else P(None, ("dp", "ep"))
    )

    def loss_fn(params, images, labels):
        logits = apply_fn(params, images)
        loss = softmax_cross_entropy(logits, labels)
        acc = (logits.argmax(-1) == labels).mean()
        return loss, acc

    def one_step(state, images, labels):
        if preprocess is not None:
            images = preprocess(images)
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, images, labels
        )
        updates, opt_state = opt.update(grads, state.opt_state)
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(state.step + 1, params, opt_state),
            {"loss": loss, "accuracy": acc},
        )

    if n == 1:
        step_fn = one_step
    else:
        def step_fn(state, images, labels):
            def body(carry, batch):
                return one_step(carry, *batch)

            state, metrics = jax.lax.scan(body, state, (images, labels))
            return state, jax.tree.map(lambda m: m[-1], metrics)

    # ``config`` rides the plan cache key when given (MnistConfig /
    # ResNetConfig from the named builders); without it the state's leaf
    # shapes — folded in at the first call — carry the model identity.
    fingerprint = {
        "learning_rate": learning_rate,
        "steps_per_call": steps_per_call,
        "preprocess": getattr(preprocess, "__name__", repr(preprocess))
        if preprocess is not None else None,
    }
    jit_init = plan_lib.instrument_jit(
        jax.jit(init_fn, out_shardings=state_sh),
        plan_lib.plan_cache_key(
            "classifier_init", config=config, mesh=mesh, extra=fingerprint,
        ),
    )
    jit_step = plan_lib.instrument_jit(
        jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh, batch_sh),
            out_shardings=(state_sh, {"loss": repl, "accuracy": repl}),
            donate_argnums=(0,),
        ),
        plan_lib.plan_cache_key(
            "classifier_step", config=config, mesh=mesh, extra=fingerprint,
        ),
    )

    def step(state, images, labels):
        return jit_step(
            state,
            _to_global_batch(images, batch_sh),
            _to_global_batch(labels, batch_sh),
        )

    # Step anatomy for classifiers: phases + calibration, no MFU (image
    # shapes don't carry a flops model the way token shapes do).
    stats = stepstats_mod.StepStats(
        cfg=config, mesh=mesh, steps_per_call=steps_per_call,
        tokens_workload=False,
    )
    return jit_init, _instrumented(step, stats)
