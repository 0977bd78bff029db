"""The training side's rope (``ops.rotate_rope``) against the one it took the
place of in ``models/transformer.py::_attention``: the same function
forward, bit for bit, and the same cotangents as autodiff of ``apply_rope``
without its scatter-adds; and the gradient of the loss through ``forward``
the same whatever ``jax.checkpoint`` keeps (``remat_policy``), GQA and the
pipeline branch (``sp`` positions) included."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import TransformerConfig, init_params, lm_loss
from tony_tpu.ops import apply_rope, rope_frequencies, rotate_rope
from tony_tpu.parallel.mesh import MeshSpec, build_mesh

B, T, H, D = 2, 16, 4, 32
POSITIONS = {
    "none": None,
    "T": np.arange(5, 5 + T),
    "BT": np.stack([np.arange(T), np.arange(9, 9 + T)]),
}
DTYPES = [jnp.float32, jnp.bfloat16]


def _inputs(dtype):
    kx, kg = jax.random.split(jax.random.key(7))
    return (jax.random.normal(kx, (B, T, H, D), dtype),
            jax.random.normal(kg, (B, T, H, D), dtype))


def _pair(positions):
    cos, sin = rope_frequencies(D, 64)
    pos = None if positions is None else jnp.asarray(positions)
    return (lambda x: apply_rope(x, cos, sin, positions=pos),
            lambda x: rotate_rope(x, cos, sin, positions=pos))


@pytest.mark.parametrize("positions", sorted(POSITIONS))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_forward_is_apply_rope_bit_for_bit(dtype, positions):
    """Primitive by primitive (no ``jit``) the two make the same products
    and the same one addition per lane. Under ``jit`` the CPU backend
    contracts a multiply into the addition, and picks another of the two
    products in the two forms on odd lanes: one unit in the last place,
    no more."""
    x, _ = _inputs(dtype)
    old, new = _pair(POSITIONS[positions])
    want, got = old(x), new(x)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    ulp = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(
        np.asarray(jax.jit(new)(x), np.float32),
        np.asarray(jax.jit(old)(x), np.float32), rtol=ulp, atol=4 * ulp)


@pytest.mark.parametrize("positions", sorted(POSITIONS))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_vjp_is_autodiff_of_apply_rope(dtype, positions):
    x, g = _inputs(dtype)
    old, new = _pair(POSITIONS[positions])
    (want,) = jax.vjp(old, x)[1](g)
    (got,) = jax.vjp(new, x)[1](g)
    assert got.dtype == want.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_vjp_keeps_nothing_shaped_like_its_input():
    """The cotangent of a rotation is the inverse rotation: the backward
    needs the tables and no copy of x or of its exchange."""
    x, _ = _inputs(jnp.float32)
    _, new = _pair(None)
    _, pullback = jax.vjp(new, x)
    kept = [leaf.shape for leaf in jax.tree.leaves(pullback)
            if hasattr(leaf, "shape")]
    assert kept and all(np.prod(s) < x.size for s in kept), kept


TOY = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, head_dim=16,
           d_ff=128, max_seq=32, dtype="float32")
BRANCHES = {
    # name -> (config extras, mesh axes, lm_loss extras)
    "mha": ({}, dict(dp=8), {}),
    "gqa": (dict(n_kv_heads=2), dict(dp=4, tp=2), {}),
    "gqa_ring": (dict(n_kv_heads=2), dict(dp=2, sp=2, tp=2), {}),
    "moe_gqa": (dict(n_kv_heads=2, n_experts=4), dict(dp=4, ep=2), {}),
    # forward_pipeline's manual branch: sp=2 offsets the rope's positions
    # by the shard's start and runs the ring, sp=1 the flash call.
    "pipeline_sp2": (dict(n_kv_heads=2), dict(pp=2, sp=2, dp=2),
                     dict(pipeline_microbatches=2)),
    "pipeline": ({}, dict(pp=2, dp=4), dict(pipeline_microbatches=2)),
}


def _loss_grads(cfg, axes, extras, tokens, params):
    mesh = build_mesh(MeshSpec(**axes))
    with jax.sharding.set_mesh(mesh):
        return jax.jit(jax.value_and_grad(
            lambda p: lm_loss(p, tokens, cfg, mesh, **extras)))(params)


@pytest.mark.parametrize("policy", ["full", "dots"])
@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_grad_of_loss_agrees_whatever_remat_keeps(branch, policy):
    extras_cfg, axes, extras = BRANCHES[branch]
    plain = TransformerConfig(**TOY, **extras_cfg, remat=False)
    remat = TransformerConfig(**TOY, **extras_cfg, remat=True,
                              remat_policy=policy)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, plain.vocab_size, (8, 17)), jnp.int32)
    params = jax.jit(lambda k: init_params(k, plain))(jax.random.key(5))
    want_loss, want = _loss_grads(plain, axes, extras, tokens, params)
    got_loss, got = _loss_grads(remat, axes, extras, tokens, params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    for (path, a), (_, b) in zip(jax.tree.leaves_with_path(got),
                                 jax.tree.leaves_with_path(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-7, err_msg=jax.tree_util.keystr(path))
