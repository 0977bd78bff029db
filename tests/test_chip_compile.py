"""Compiles for a described (not attached) TPU v5e, kept as tests.

The TPU compiler ships with the installed jax/libtpu and compiles for a
``v5e:2x2`` topology that is only described, so these run on the CPU-only
test box and cost no chip time. They catch what interpret mode and the
CPU backend cannot: a kernel the Mosaic compiler refuses (tiling, VMEM),
and — the reason the file exists — a Pallas call reached on sharded
operands outside a ``shard_map`` ("Mosaic kernels cannot be automatically
partitioned"), which is how every multi-device layout failed before the
ops learned to run per shard.

Nothing executes: a compile that passes says nothing about results or
times. ``_on_tpu`` is steered from here (the ambient backend is the CPU);
the program has no option for it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tony_tpu.models import decode as decode_lib
from tony_tpu.models.train import make_train_step
from tony_tpu.models.transformer import TransformerConfig, init_params
from tony_tpu.ops import attention, hybrid, norms
from tony_tpu.parallel.mesh import AXES, MeshSpec


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu, or one that cannot describe it
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")
    return list(topo.devices)


@pytest.fixture(autouse=True)
def _chip_compile_env(monkeypatch):
    """Take the TPU branch everywhere, and keep the persistent cache out
    of it: a compile for a described chip is written there but can never
    be read back, so the next run would warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(attention, "_on_tpu", lambda mesh=None: True)
    monkeypatch.setattr(norms, "_on_tpu", lambda mesh=None: True)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _flash_fwd(bh, t_q, t_k, d, block):
    fn = functools.partial(
        attention._flash_attention_pallas, causal=True, scale=d ** -0.5,
        block_q=block, block_k=block, return_lse=True,
    )
    shapes = [(bh, t_q, d), (bh, t_k, d), (bh, t_k, d)]
    return fn, [jnp.bfloat16] * 3, shapes


def _flash_bwd(bh, t, d, block):
    fn = functools.partial(
        attention._flash_attention_pallas_bwd, causal=True, scale=d ** -0.5,
        block_q=block, block_k=block,
    )
    shapes = [(bh, t, d)] * 4 + [(bh, t)] + [(bh, t, d)]
    dtypes = [jnp.bfloat16] * 4 + [jnp.float32, jnp.bfloat16]
    return fn, dtypes, shapes


def _cache_decode(layers, slots, t, h_kv, group, d, tiles=1, window=0):
    """K as ``tiles`` 128-lane tiles of a 192-wide row where ``tiles``
    is 2; ``window`` > 0: a ring of ``t - 1`` positions with a sink."""
    cache = (layers, slots, t, h_kv, d)
    d_k = 192 if tiles == 2 else d
    shapes = [(slots, h_kv * group, d_k)] + [cache] * (tiles + 1) + [
        (), (slots,)]
    dtypes = [jnp.bfloat16] * (tiles + 2) + [jnp.int32] * 2
    if window:
        shapes.append((h_kv * group,))
        dtypes.append(jnp.float32)

    def fn(q, *rest):
        k, (v, layer, pos, *sink) = rest[:tiles], rest[tiles:]
        return attention.cache_decode_attention(
            q, k if tiles > 1 else k[0], v, layer, pos, window=window,
            sink=sink[0] if sink else None)

    return fn, dtypes, shapes


def _cache_prefill(layers, slots, t, h_kv, group, tiles, p, c):
    """A round of ``p`` chunks of ``c`` tokens; K as ``tiles`` 128-lane
    tiles of a 192-wide row where ``tiles`` is 2."""
    cache = (layers, slots, t, h_kv, 128)
    d_k = 192 if tiles == 2 else 128
    shapes = [(p, c, h_kv * group, d_k)] + [cache] * (tiles + 1) + [
        (), (p,), (p,), (h_kv * group,)]
    dtypes = [jnp.bfloat16] * (tiles + 2) + [jnp.int32] * 3 + [jnp.float32]

    def fn(q, *rest):
        k, (v, layer, row_slots, ends, sink) = rest[:tiles], rest[tiles:]
        return attention.cache_prefill_attention(
            q, k if tiles > 1 else k[0], v, layer, row_slots, ends,
            sink=sink)

    return fn, dtypes, shapes


def _lightning(kind, rows, c, h, d):
    """The lightning kernels at the MiniCPM-SALA cell's size: a prefill
    round of ``rows`` chunks of ``c`` positions, a decode step of
    ``rows`` slots over the state buffer [rows + 1, H, D, D]."""
    if kind == "prefill":
        shapes = [(rows, c, h, d)] * 3 + [(rows, h, d, d), (h,), (rows,)]
        dtypes = [jnp.bfloat16] * 3 + [jnp.float32] * 2 + [jnp.int32]
        return functools.partial(hybrid.lightning_prefill,
                                 mode="pallas"), dtypes, shapes
    shapes = [(rows, h, d)] * 3 + [(rows + 1, h, d, d), (rows,), (h,)]
    dtypes = [jnp.bfloat16] * 3 + [jnp.float32, jnp.int32, jnp.float32]
    return functools.partial(hybrid.lightning_decode,
                             mode="pallas"), dtypes, shapes


def _sparse(kind, slots, t, h_kv, group, d, rows, c):
    """The sparse layers' attention kernels at the cell's size: decode
    over the selected blocks (a list of 128 a slot and KV head), a
    prefill round of ``rows`` chunks of ``c`` under its block mask."""
    cache = (slots, h_kv, t, d)
    if kind == "decode":
        shapes = [(slots, h_kv * group, d), cache, cache,
                  (slots, h_kv, 128), (slots, h_kv, 128), (slots,)]
        dtypes = [jnp.bfloat16] * 3 + [jnp.int32, jnp.bool_, jnp.int32]
        return functools.partial(hybrid.sparse_decode_attention,
                                 scale=d ** -0.5, block=64,
                                 mode="pallas"), dtypes, shapes
    shapes = [(rows, c, h_kv * group, d), cache, cache,
              (rows, h_kv, c, t // 64), (rows,), (rows,)]
    dtypes = [jnp.bfloat16] * 3 + [jnp.bool_, jnp.int32, jnp.int32]
    return functools.partial(hybrid.sparse_prefill_attention,
                             scale=d ** -0.5, block=64,
                             mode="pallas"), dtypes, shapes


def _rms(rows, d):
    fn = functools.partial(norms._rms_norm_pallas, eps=1e-6, block_rows=256)
    return fn, [jnp.bfloat16, jnp.float32], [(rows, d), (d,)]


# (B·H, T, D, block) as bench/chip_smoke run them: 200M 16x64 and 8x128 at
# 2k, the 1B 16x128 at 2k, both head dims at 8k; decode is one query row
# over a 2048-key cache; the engine's decode read at the serving cells'
# size (16 layers x 32 slots x 2,048 positions of 8 KV heads x 128, 4
# query heads a group) and with 32 KV heads (the block must shrink to
# fit VMEM); a prefill round's read at the MiMo cell's size (4 chunks of
# 128 tokens, 64 query heads over 4 KV heads, K in two lane tiles, 64
# slots x 8,192 positions) and at Mistral's widths over a long
# reservation; RMSNorm at a train (8x2048 rows) and a decode
# (8 rows) row count of the 1B width.
KERNELS = {
    "flash_fwd_2k_d128": (_flash_fwd, (64, 2048, 2048, 128, 512), 1),
    "flash_fwd_2k_d64": (_flash_fwd, (128, 2048, 2048, 64, 512), 1),
    "flash_fwd_8k_d128": (_flash_fwd, (16, 8192, 8192, 128, 1024), 1),
    "flash_fwd_8k_d64": (_flash_fwd, (16, 8192, 8192, 64, 1024), 1),
    "flash_bwd_2k_d128": (_flash_bwd, (64, 2048, 128, 512), 2),
    "flash_bwd_2k_d64": (_flash_bwd, (128, 2048, 64, 512), 2),
    "flash_bwd_8k_d128": (_flash_bwd, (16, 8192, 128, 1024), 2),
    "flash_bwd_8k_d64": (_flash_bwd, (16, 8192, 64, 1024), 2),
    "flash_decode_tq1": (_flash_fwd, (64, 1, 2048, 128, 512), 1),
    "cache_decode_mistral7b": (_cache_decode, (16, 32, 2048, 8, 4, 128), 1),
    "cache_decode_mha32": (_cache_decode, (2, 8, 2048, 32, 1, 128), 1),
    # MiMo's cell: the full layers' K in two lane tiles, and the rings
    "cache_decode_mimo_full": (
        _cache_decode, (2, 64, 8192, 4, 16, 128, 2), 1),
    "cache_decode_mimo_ring": (
        _cache_decode, (7, 64, 257, 8, 8, 128, 2, 128), 1),
    "cache_prefill_mimo": (_cache_prefill, (2, 64, 8192, 4, 16, 2, 4, 128), 1),
    "cache_prefill_gqa8": (_cache_prefill, (2, 8, 32768, 8, 4, 1, 4, 128), 1),
    # Dh 64 (the 200M flagship): the cache's rows do not merge, plain path
    "cache_decode_d64_plain": (_cache_decode, (8, 8, 512, 4, 4, 64), 0),
    # MiniCPM-SALA's cell: 32 slots x 32,768, 32 heads x 128, 2 KV heads,
    # rounds of 4 chunks of 256
    "lightning_prefill_sala": (_lightning, ("prefill", 4, 256, 32, 128), 1),
    "lightning_decode_sala": (_lightning, ("decode", 32, 0, 32, 128), 1),
    "sparse_decode_sala": (
        _sparse, ("decode", 32, 32768, 2, 16, 128, 0, 0), 1),
    "sparse_prefill_sala": (
        _sparse, ("prefill", 32, 32768, 2, 16, 128, 4, 256), 1),
    "rms_norm_train_rows": (_rms, (16384, 2048), 1),
    "rms_norm_decode_rows": (_rms, (8, 2048), 1),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(v5e, name):
    build, dims, n_calls = KERNELS[name]
    fn, dtypes, shapes = build(*dims)
    one_chip = SingleDeviceSharding(v5e[0])
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
        for s, dt in zip(shapes, dtypes)
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert _mosaic_calls(compiled) == n_calls


# Flagship 200M widths (bench_transformer: d_model 1024, 16 heads x 64,
# d_ff 4096, vocab 32000, bf16) with GQA 4 and depth cut to 1 per pipeline
# stage — every layer compiles the same kernels.
def _cfg(n_layers: int, seq: int) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=32_000, d_model=1024, n_layers=n_layers, n_heads=16,
        head_dim=64, d_ff=4096, max_seq=seq, n_kv_heads=4, dtype="bfloat16",
        remat=False,
    )


def _mesh(devices, **axes) -> Mesh:
    spec = MeshSpec(**axes).validate(len(devices))
    return Mesh(np.asarray(devices).reshape(spec.shape), AXES)


LAYOUTS = {
    "dp4": (dict(dp=4), {}),
    "dp2_tp2": (dict(dp=2, tp=2), {}),
    "dp2_sp2": (dict(dp=2, sp=2), {}),
    "pp2_tp2": (dict(pp=2, tp=2), dict(pipeline_microbatches=2)),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_train_step_compiles_on_four_chips(v5e, name):
    """``make_train_step``'s own program (forward, backward, adamw) over
    a 4-chip mesh: before the ops ran per shard, each of these raised
    ``Mosaic kernels cannot be automatically partitioned`` in under two
    seconds."""
    axes, pipeline = LAYOUTS[name]
    mesh = _mesh(v5e, **axes)
    batch, seq = 8, 2048
    cfg = _cfg(n_layers=axes.get("pp", 1), seq=seq)
    init_fn, step_fn = make_train_step(cfg, mesh, **pipeline)
    state = jax.eval_shape(init_fn.__wrapped__, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)
    compiled = step_fn.lower(state, tokens).compile()
    # Per layer: flash fwd + dq + dkv (the sp ring runs them per ring
    # step) and the RMSNorm forwards; the count only has to show that the
    # kernels, not the blockwise path, were lowered.
    assert _mosaic_calls(compiled) >= 5


def _computations(text: str) -> dict:
    """Computation name -> its instruction lines, from optimized HLO text."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def _loop_bodies(text: str) -> tuple:
    """(instruction lines directly in the ``while`` bodies, those lines
    and the lines of every computation they call, fusions included)."""
    comps = _computations(text)
    bodies = re.findall(r"body=%([\w.\-]+)", text)
    direct = [ln for b in bodies for ln in comps[b]]
    seen, todo = set(), list(bodies)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for ln in comps[name]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                               ln)
    return direct, [ln for name in seen for ln in comps[name]]


_RESULT = re.compile(r" = (.*?[\]})]) [a-z][\w\-]*\(")
_O = r"bf16\[\d+,\d+,\d+\]\S*"
_FLASH_RESULT = re.compile(     # forward (o, log-sum-exp); dq; (dk, dv)
    rf"^(?:\({_O}, f32\[\d+,1,\d+\]\S*\)|{_O}|\({_O}, {_O}\))$")


def _result(line: str) -> str:
    """An instruction's result shapes: what stands between ``=`` and the
    operation's name."""
    found = _RESULT.search(line)
    return found.group(1) if found else ""


def _elements(shape: str) -> int:
    return int(np.prod([int(n) for n in shape.split(",") if n]))


@pytest.mark.parametrize("policy", ["dots", "full"])
def test_train_step_moves_q_and_k_through_hbm_once(v5e, policy):
    """The train cell's own step (Mistral-7B widths, 2 layers, batch 4 x
    2,049, float32 state) on one chip of the described v5e, read from the
    optimized HLO of its two layer loops. What ``remat_policy`` keeps and
    what the rope lowers to are decided at compile time, so the compiled
    text is where they are counted:

    - ``dots`` keeps the flash forward's result, so the loops hold three
      flash calls (forward; dq and dkv) and the backward runs no forward;
      ``full`` keeps nothing and holds four.
    - Between the projections and the kernels nothing gathers or scatters
      an array of k's size or more (``apply_rope`` did, for q and k, in
      both loops), and no float32 copy of a q-sized array is written
      (its transpose under autodiff did)."""
    cfg = TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=2, n_heads=32,
        head_dim=128, d_ff=14336, max_seq=2048, n_kv_heads=8,
        dtype="bfloat16", remat=True, remat_policy=policy,
    )
    batch, seq = 4, 2048
    mesh = Mesh(np.asarray(v5e[:1]).reshape((1,) * len(AXES)), AXES)
    one_chip = SingleDeviceSharding(v5e[0])
    init_fn, step_fn = make_train_step(cfg, mesh)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(init_fn.__wrapped__, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                  sharding=one_chip)
    text = step_fn.lower(state, tokens).compile().as_text()
    direct, reachable = _loop_bodies(text)

    flash = [ln for ln in direct if "tpu_custom_call" in ln
             and _FLASH_RESULT.match(_result(ln))]
    assert len(flash) == (3 if policy == "dots" else 4), flash

    q_size = batch * seq * cfg.n_heads * cfg.head_dim
    k_size = batch * seq * cfg.kv_heads * cfg.head_dim
    # (a line names its operands without their shapes; the half of an
    # operand's lanes that ``apply_rope`` gathered is half of k's size)
    moved = [ln.strip()[:160] for ln in reachable
             if re.search(r" (?:gather|scatter)\(", ln)
             and max(_elements(dims) for dims in
                     re.findall(r"[a-z]+\d+\[([\d,]*)\]", ln)) >= k_size // 2]
    assert not moved, moved
    # an activation's copy: batch and sequence among its dimensions
    in_f32 = [ln.strip()[:160] for ln in direct
              for dims in re.findall(r"f32\[([\d,]*)\]", _result(ln))
              if _elements(dims) >= q_size
              and {str(batch), str(seq)} <= set(dims.split(","))]
    assert not in_f32, in_f32


def test_sharded_decode_step_compiles_on_four_chips(v5e):
    """``DecodeSession(mesh=)``'s layout — fused weights megatron-split
    over tp, KV cache batch-over-dp / kv-heads-over-tp — through one
    prefill and one single-token ``advance`` under the ambient mesh."""
    mesh = _mesh(v5e, dp=2, tp=2)
    cfg = _cfg(n_layers=1, seq=2048)
    batch, prompt = 8, 512
    fused = jax.eval_shape(
        lambda: decode_lib.decode_weights(
            init_params(jax.random.key(0), cfg), cfg
        )
    )
    specs = decode_lib.decode_param_specs(cfg)
    fused = jax.tree.map(
        lambda spec, p: jax.ShapeDtypeStruct(
            p.shape, p.dtype, sharding=NamedSharding(mesh, spec)
        ),
        specs, fused, is_leaf=lambda x: isinstance(x, P),
    )
    tokens = jax.ShapeDtypeStruct(
        (batch, prompt), jnp.int32, sharding=NamedSharding(mesh, P("dp"))
    )

    def prefill_then_step(params, tokens):
        cache = decode_lib.init_cache(cfg, batch, prompt + 1)
        logits, cache = decode_lib.advance(
            params, cache, tokens, cfg, prefill=True
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits, _ = decode_lib.advance(params, cache, nxt[:, None], cfg)
        return logits

    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(prefill_then_step).lower(fused, tokens).compile()
    # Prefill: flash + 2 layer norms; step: 2 layer norms; 2 final norms.
    assert _mosaic_calls(compiled) >= 5


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunks"])
def test_serving_programs_hold_no_layer_slab(v5e, program):
    """The engine's two programs at the serving cells' widths (Mistral-7B,
    32 slots x 2,048 positions, depth cut to 2): the compiler's temporaries
    stay under ONE layer's K or V slab. The jaxpr cannot show this side —
    XLA copies a layer out of the stacked cache even for a read-only slice
    that feeds the attention contraction, which is why decode reads
    through ``cache_decode_attention`` and prefill takes only its own
    slots' rows (the slab path held three slabs here)."""
    from tony_tpu.serving import engine

    cfg = TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=2, n_heads=32,
        head_dim=128, d_ff=14336, max_seq=2048, n_kv_heads=8,
        dtype="bfloat16", remat=False,
    )
    slots, t_max, p, c = 32, 2048, 4, 32
    one_chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree
        )

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fused = on_chip(jax.eval_shape(
        lambda: decode_lib.decode_weights(
            init_params(jax.random.key(0), cfg), cfg
        )
    ))
    kv = arr((cfg.n_layers, slots, t_max, cfg.kv_heads, cfg.head_dim),
             jnp.bfloat16)
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    if program == "decode_window":
        lowered = engine.decode_window.lower(
            fused, kv, kv, arr((slots,)), arr((slots,)), arr((slots,)),
            arr((slots,), jnp.float32), key, arr(()), arr((slots, 1)),
            cfg=cfg, steps=1,
        )
    else:
        lowered = engine.prefill_chunks.lower(
            fused, kv, kv, arr((p, c)), arr((p,)), arr((p,)), arr((p,)),
            arr((p,), jnp.float32), key, arr(()), cfg=cfg,
        )
    compiled = lowered.compile()
    slab_bytes = slots * t_max * cfg.kv_heads * cfg.head_dim * 2
    assert compiled.memory_analysis().temp_size_in_bytes < slab_bytes
    # decode: a layer's attention and 3 norms; prefill: the norms alone
    # (its scores are 33 MB: the batched path, no kernel of its own)
    assert _mosaic_calls(compiled) == (4 if program == "decode_window" else 3)


_LAYERED: dict = {}


def _layered_program(v5e, program):
    """``decode_window`` or ``prefill_chunks`` over a LAYERED model at
    published widths (q/k 192, v 128, 4 KV heads in the full layer and 8
    in the window layer, a sink, 16 of 256 experts held; 64 slots x
    8,192 positions, 4 chunks of 128 a round, depth cut to one layer of
    each attention kind, the second with experts), compiled once for
    the tests below."""
    from tony_tpu.serving import engine

    if program in _LAYERED:
        return _LAYERED[program]
    cfg = TransformerConfig(
        vocab_size=19_072, d_model=4096, n_layers=2, n_heads=64,
        head_dim=192, v_head_dim=128, rotary_dim=64, v_scale=0.707,
        rms_eps=1e-5, n_kv_heads=4, rope_theta=5e6, max_seq=8192,
        attn_kinds=("full", "window"), window=128, window_kv_heads=8,
        window_rope_theta=1e4, window_sink=True, n_dense_layers=1,
        dense_d_ff=16384, d_ff=2048, n_experts=256, expert_top_k=8,
        router_scoring="sigmoid", router_bias=True, experts_held=(0, 16),
        dtype="bfloat16", remat=False,
    )
    slots, t_max, p, c = 64, 8192, 4, 128
    one_chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree
        )

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fused = on_chip(jax.eval_shape(
        lambda: decode_lib.decode_weights(
            init_params(jax.random.key(0), cfg), cfg
        )
    ))
    k, v = on_chip(jax.eval_shape(
        lambda: engine.init_slot_cache(cfg, slots, t_max, prefill_chunk=c)
    ))
    assert [x.shape for x in k["full"]] == [(1, 64, 8192, 4, 128)] * 2
    assert v["window"].shape == (1, 64, 257, 8, 128)
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    if program == "decode_window":
        lowered = engine.decode_window.lower(
            fused, k, v, arr((slots,)), arr((slots,)), arr((slots,)),
            arr((slots,), jnp.float32), key, arr(()), arr((slots, 1)),
            cfg=cfg, steps=1,
        )
    else:
        lowered = engine.prefill_chunks.lower(
            fused, k, v, arr((p, c)), arr((p,)), arr((p,)), arr((p,)),
            arr((p,), jnp.float32), key, arr(()), cfg=cfg,
        )
    _LAYERED[program] = lowered.compile()
    return _LAYERED[program]


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunks"])
def test_layered_serving_programs_copy_no_cache(v5e, program):
    """The engine's two programs over a layered model at published
    widths (``_layered_program``): the decode kernel of both kinds, the
    grouped expert products (``ops.grouped_matmul``: the megablox kernel
    at weight-streaming tiles) and the norms compile, and decode's
    temporaries stay far under one K buffer of the full layers (1.07 GB)
    — a K row of 192 kept as ONE [Tmax, 4, 256] buffer does not merge to
    rows without a copy of the whole cache per dispatch (2.15 GB of
    temporaries here); as two 128-lane tiles it does."""
    compiled = _layered_program(v5e, program)
    if program == "decode_window":
        # 2 attention calls, 2 + 2 + 1 norms, the expert layer's 2 products
        assert _mosaic_calls(compiled) == 9
        assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    else:
        # 2 + 2 + 1 norms, the expert layer's 2 products and the full
        # layer's chunk attention (``cache_prefill_attention``: a round's
        # scores against 8,192 keys are 1 GiB)
        assert _mosaic_calls(compiled) == 8
        # no scores through HBM and no slot's rows read out: nothing of
        # 2^28 bytes in float32, and far under the 0.33 GB the plain
        # path held (one row's scores and the four slots' rows)
        sizes = [int(np.prod([int(n) for n in dims.split(",")])) * 4
                 for dims in re.findall(r"f32\[([\d,]+)\]",
                                        compiled.as_text())]
        assert max(sizes) < 2 ** 28
        assert compiled.memory_analysis().temp_size_in_bytes < 64e6


def _mosaic_results(lines) -> list:
    return [_result(ln) for ln in lines
            if 'custom_call_target="tpu_custom_call"' in ln]


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunks"])
def test_expert_products_keep_their_rows_and_follow_the_pairs(v5e, program):
    """An expert layer's two grouped products at the served widths. A
    decode iteration keeps its 64 x 8 = 512 pair rows: two Mosaic calls
    with ``bf16[512,.]`` results, in no loop (the benchmark's reader
    tells decode's expert products by that shape and counts two a
    layer). A prefill round's 4 x 128 x 8 = 4,096 pair rows go in passes
    of 1,024: its two products have ``bf16[1024,.]`` results — not the
    worst case's 4,096 rows, nor 512 (decode's name) — and stand inside
    a ``while`` loop. gate|up's tiles keep all of k, so a row tile is
    fetched once per n tile and not beside every weight tile."""
    from tony_tpu.ops.grouped import _tiling

    text = _layered_program(v5e, program).as_text()
    everywhere = _mosaic_results(text.splitlines())
    in_loops = _mosaic_results(_loop_bodies(text)[1])

    def with_rows(results, rows):
        return [r for r in results if re.match(rf"bf16\[{rows},\d+\]", r)]

    if program == "decode_window":
        assert len(with_rows(everywhere, 512)) == 2
        assert not with_rows(in_loops, 512)
        assert not with_rows(everywhere, 1024)
    else:
        assert len(with_rows(in_loops, 1024)) == 2
        assert len(with_rows(everywhere, 1024)) == 2
        assert not with_rows(everywhere, 4096)
        # what has 512 rows here are the round's bfloat16 norms
        # (4 x 128 tokens), which the reader takes out by their float32
        # twin: no product
        assert len(with_rows(everywhere, 512)) == 3
    for rows in (512, 1024):
        assert _tiling(rows, 4096, 4096, 2) == (128, 4096, 256)
        assert _tiling(rows, 2048, 4096, 2) == (128, 2048, 512)


_LFM2: dict = {}


def _lfm2_program(v5e, program):
    """``decode_window`` or ``prefill_chunks`` over an LFM2-shaped model at
    published widths (d 2,048; 32 heads of 64 over 8 KV heads, two to a
    row of the cache; a conv state of 2 rows a slot; ALL 32 experts of
    width 1,792 held, top-4; a tied head over 65,536 rows; 128 slots x
    4,096 positions, 2 chunks of 512 a round: the engine's default at
    that chunk), depth cut to ``conv``
    (dense), ``full`` and ``conv`` (experts), compiled once for the tests
    below."""
    from tony_tpu.serving import engine

    if program in _LFM2:
        return _LFM2[program]
    cfg = TransformerConfig(
        vocab_size=65_536, d_model=2048, n_layers=3, n_heads=32, head_dim=64,
        n_kv_heads=8, rope_theta=1e6, rms_eps=1e-5, max_seq=4096,
        attn_kinds=("conv", "full", "conv"), conv_kernel=3, qk_norm=True,
        n_dense_layers=1, dense_d_ff=7168, d_ff=1792, n_experts=32,
        expert_top_k=4, router_scoring="sigmoid", router_bias=True,
        tie_embeddings=True, dtype="bfloat16", remat=False,
    )
    slots, t_max, p, c = 128, 4096, 2, 512
    one_chip = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree
        )

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fused = on_chip(jax.eval_shape(
        lambda: decode_lib.decode_weights(
            init_params(jax.random.key(0), cfg), cfg
        )
    ))
    assert "unembed" not in fused
    k, v = on_chip(jax.eval_shape(
        lambda: engine.init_slot_cache(cfg, slots, t_max, prefill_chunk=c)
    ))
    # two 64-wide KV heads a row; a conv state a conv layer, on the K side
    assert k["full"].shape == v["full"].shape == (1, 128, 4096, 4, 128)
    assert [x.shape for x in k["conv"]] == [(128, 2, 2048)] * 2
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    if program == "decode_window":
        lowered = engine.decode_window.lower(
            fused, k, v, arr((slots,)), arr((slots,)), arr((slots,)),
            arr((slots,), jnp.float32), key, arr(()), arr((slots, 1)),
            cfg=cfg, steps=1,
        )
    else:
        lowered = engine.prefill_chunks.lower(
            fused, k, v, arr((p, c)), arr((p,)), arr((p,)), arr((p,)),
            arr((p,), jnp.float32), key, arr(()), cfg=cfg,
        )
    args = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((fused, k, v)))
    _LFM2[program] = lowered.compile(), args
    return _LFM2[program]


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunks"])
def test_lfm2_serving_programs_copy_no_cache(v5e, program):
    """The engine's two programs over a model of conv layers with a conv
    state beside attention at a head width of 64: the cache's rows of two
    KV heads cost 64 lanes a head (the buffers as compiled are the bytes
    budgeted: ``bf16[1,128,4096,4,128]`` in tiles of (4, 128), nothing
    padded; a [.., 8, 64] buffer did not merge to rows and fell to the plain
    path, which reads a slot's whole reservation), both programs read them
    through the KERNELS, and neither copies the cache nor holds a layer's
    slab (one K buffer of the attention layer is 537 MB)."""
    compiled, args = _lfm2_program(v5e, program)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "bf16[1,128,4096,4,128]{4,3,2,1,0:T(4,128)(2,1)}" in text
    assert "bf16[1,128,4096,8,64]" not in text
    assert abs(memory.argument_size_in_bytes - args) < 0.01 * args
    assert memory.temp_size_in_bytes < 96e6
    results = [r.split("{")[0] for r in _mosaic_results(text.splitlines())]
    if program == "decode_window":
        # the attention kernel returns both halves of its paired rows
        assert results.count("bf16[128,32,128]") == 1
    else:
        # the chunks' attention through ``cache_prefill_attention``: 4 rows
        # a position, 8 query heads a row: [P, rows, C * 8, 128]
        assert results.count("bf16[2,4,4096,128]") == 1


@pytest.mark.parametrize("program", ["decode_window", "prefill_chunks"])
def test_every_expert_held_keeps_the_pairs_rows(v5e, program):
    """With all 32 experts held every pair lands here. A decode iteration
    keeps its 128 x 4 = 512 pair rows over 32 groups: two Mosaic calls an
    expert layer with ``bf16[512,.]`` results, in no loop (the benchmark's
    reader tells decode's expert products by that shape). A prefill round's
    2 x 512 x 4 = 4,096 pair rows are what lands here at any routing, so
    they go in ONE pass (``_pass_rows``: four times the mean is the worst
    case itself), in no loop either."""
    from tony_tpu.models.decode import _pass_rows
    from tony_tpu.ops.grouped import _tiling

    text = _lfm2_program(v5e, program)[0].as_text()
    everywhere, in_loops = (
        [r.split("{")[0] for r in _mosaic_results(lines)]
        for lines in (text.splitlines(), _loop_bodies(text)[1]))
    rows = 512 if program == "decode_window" else 4096
    products = [r for r in everywhere
                if re.match(rf"bf16\[{rows},(3584|2048)\]", r)]
    # two expert layers, gate|up and down each
    assert sorted(products) == sorted(
        [f"bf16[{rows},3584]", f"bf16[{rows},2048]"] * 2)
    assert not [r for r in in_loops if r.startswith(f"bf16[{rows},")]
    weights = 32 * 3 * 2048 * 1792 * 2
    assert _pass_rows(rows, 2048, 32, 32, 2, weights) == rows
    assert _tiling(rows, 2048, 3584, 2) == (128, 2048, 512)
    assert _tiling(rows, 1792, 2048, 2) == (128, 1792, 512)
