"""Flagship decoder-only transformer LM, built TPU-first.

The reference framework contains no model code (SURVEY.md: "no kernels, no
autograd, no tensors"); this model is the compute payload the rebuild adds so
every parallelism axis of the 5-axis mesh is exercised by a real workload:

  dp/fsdp — batch split + weight sharding via logical rules (sharding.py)
  tp      — megatron split: heads / mlp-hidden / vocab columns
  sp      — ring attention over the sequence axis (parallel/ring.py)
  pp      — GPipe microbatch pipeline over stacked layers (parallel/pipeline.py)
  ep      — MoE experts with capacity-based dispatch/combine einsums

Two trunk modes, one layer implementation:

  * GSPMD mode (``forward``): everything under ``jit`` with sharding
    constraints; XLA SPMD inserts the collectives (all-gather for tp,
    psum for dp grads, all-to-all for ep dispatch). Use when pp == 1.
  * Manual mode (``forward_pipeline``): the trunk runs inside
    ``pipeline_apply``'s shard_map, so tp reductions are explicit
    ``lax.psum`` and sequence parallelism is the in-shard_map ring
    (``ring_attention_local``). Use when pp > 1. MoE is GSPMD-only.

Weights are fp32 (optimizer precision), compute is bfloat16 on the MXU with
fp32 accumulation inside the attention/norm kernels.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.ops import (
    flash_attention,
    rms_norm,
    rope_frequencies,
    rotate_rope,
)
from tony_tpu.ops.attention import FLASH_RESIDUALS
from tony_tpu.parallel.pipeline import pipeline_apply
from tony_tpu.parallel.ring import ring_attention, ring_attention_local
from tony_tpu.parallel.sharding import logical_spec, with_logical_constraint


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    head_dim: int = 64
    d_ff: int = 1408
    max_seq: int = 2048
    rope_theta: float = 10_000.0
    # GQA (grouped-query attention): number of K/V heads; 0 = n_heads
    # (MHA). Shrinks the KV cache by n_heads/n_kv_heads — *the* decode
    # bandwidth lever; training repeats K/V heads (compute-bound anyway).
    n_kv_heads: int = 0
    # MoE: 0 experts = dense SwiGLU mlp. When > 0, every layer is an MoE
    # layer with top-k routing and capacity_factor token capacity.
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    # Router auxiliary losses (Switch Transformer): the balance term keeps
    # expert assignment near-uniform (its minimum), the z term keeps router
    # logits small so the fp32 softmax stays well-conditioned. Both are
    # added to the LM loss by lm_loss(); 0 disables.
    moe_balance_coef: float = 0.01
    moe_zloss_coef: float = 1e-3
    dtype: str = "bfloat16"
    remat: bool = True
    # "full": recompute the whole layer in backward, keep nothing (min
    # memory);
    # "dots": keep what is expensive to recompute — matmul outputs (XLA's
    # dots_with_no_batch_dims_saveable) AND the flash forward's result (o
    # and the log-sum-exp, named in ops/attention.py: a Pallas call is no
    # dot to that policy, which alone re-ran the forward kernel in every
    # layer's backward) — and recompute the elementwise work. More memory
    # (at Mistral-7B widths and 4 x 2,048 tokens about 1.1 GB a layer, 68
    # MB of it the flash result), fewer recomputed flops: the better MFU
    # point when the model fits.
    remat_policy: str = "full"
    # Layer-loop scheduling. 1 = the rolled scan (default; dryruns/tests
    # compile fast). Values >= n_layers bypass scan for a static Python
    # loop over static layer slices, at ~L x the trunk's compile time;
    # intermediate values use scan's own unroll. No chip record favours
    # either form: on the v5e the rolled scan's dynamic-update-slice
    # fusions over the stacked gradients ARE the weight-gradient matmuls
    # writing into the stacked buffer, at 74-82% of the chip's peak
    # (PERF.md section 5, PR 33's traced step), so there is no copy to
    # win back by unrolling.
    layer_scan_unroll: int = 1
    # RMSNorm epsilon, every norm of the model.
    rms_eps: float = 1e-6
    # -- Facts of a model whose layers are not all alike. Any of them set
    # makes the configuration ``layered``: its parameters are groups of
    # stacks by layer kind (``layer_groups``), the serving engine runs it
    # through its one layer definition with a cache per attention kind,
    # and ``forward`` / ``generate`` refuse it (never a silently uniform
    # model). ``head_dim`` is the q/k width.
    v_head_dim: int = 0          # value width; 0 = head_dim
    rotary_dim: int = 0          # leading dims of q/k that rotate; 0 = all
    v_scale: float = 1.0         # v = v_scale * (h @ Wv)
    # Per layer "full" | "window" | "linear" | "sparse" | "conv"; () =
    # every layer full. A window layer sees the last ``window`` positions (its own
    # included), has its own KV head count and rope base, and with
    # ``window_sink`` a learnt per-head bias in the softmax denominator.
    # A linear layer (lightning attention) keeps no K/V rows: per head a
    # float32 state [head_dim, head_dim] that decays by the head's slope
    # and takes k^T v at every position (``ops/hybrid.py``); it has
    # ``n_heads`` K/V heads. A sparse layer (InfLLM-V2 block-sparse
    # attention) keeps K/V at ``n_kv_heads`` and, beside them, the means
    # of its keys over ``sparse_kernel`` positions every
    # ``sparse_stride``; a query at a position >= ``sparse_dense_len``
    # attends the first ``sparse_init_blocks`` blocks of ``sparse_block``
    # positions, the blocks that cover its last ``sparse_window``
    # positions and the ``sparse_topk`` blocks those means score highest
    # for its KV group, a query before it every key. A conv layer's token
    # mixer is no attention at all: a gated short convolution (LFM2),
    # ``[B | C | z] = h W_in`` ([d, 3d]), ``g = B * z``, a causal
    # depthwise convolution of ``conv_kernel`` taps over g, times C,
    # through ``W_out`` ([d, d]); it has no q/k/v/o and no rope, and keeps
    # per slot the last ``conv_kernel - 1`` rows of g. All three are
    # SERVED (``ServingEngine``) and not trained: no backward is written.
    attn_kinds: tuple = ()
    conv_kernel: int = 3
    window: int = 0
    window_kv_heads: int = 0     # 0 = n_kv_heads
    window_rope_theta: float = 0.0   # 0 = rope_theta
    window_sink: bool = False
    # Leading layers with a dense SwiGLU of width ``dense_d_ff`` before
    # the expert layers (``d_ff`` is then an expert's width).
    n_dense_layers: int = 0
    dense_d_ff: int = 0
    # Router: "softmax" scores as above, or "sigmoid" scores with the
    # chosen ones normalised; ``router_bias`` adds a learnt per-expert
    # bias to the scores for the CHOICE only (it never weighs).
    router_scoring: str = "softmax"
    router_bias: bool = False
    # The experts this device holds of each layer's ``n_experts``:
    # (first, count). The router stays ``n_experts`` wide and the layer
    # computes its own experts' part of the result; None = all.
    experts_held: tuple | None = None
    # Block-sparse selection of the "sparse" layers (above).
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    # RMSNorm with a learnt weight over each head's dims of q and of k,
    # before the rope.
    qk_norm: bool = False
    # Attention kinds that rotate nothing, that gate their attention
    # output by sigmoid(h Wg) before the output projection, and that
    # RMSNorm it over all heads' dims before the gate.
    no_rope_kinds: tuple = ()
    gated_kinds: tuple = ()
    out_norm_kinds: tuple = ()
    # muP-style scales: x0 = embed_scale * E[token]; every sub-block adds
    # residual_scale * f(norm(x)); the head reads norm(x) * logit_scale.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # The output head reads the embedding matrix (logits = norm(x) E^T):
    # the parameters hold no ``unembed``.
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.attn_kinds and len(self.attn_kinds) != self.n_layers:
            raise ValueError(
                f"attn_kinds names {len(self.attn_kinds)} layers, the "
                f"model has {self.n_layers}")
        if set(self.attn_kinds) - {"full", "window", "linear", "sparse",
                                   "conv"}:
            raise ValueError(f"unknown attention kind in {self.attn_kinds}")
        if "conv" in self.attn_kinds and self.conv_kernel < 2:
            raise ValueError("conv layers need conv_kernel >= 2")
        if "sparse" in self.attn_kinds:
            k, s, b = (self.sparse_kernel, self.sparse_stride,
                       self.sparse_block)
            if min(k, s, b, self.sparse_topk, self.sparse_init_blocks) < 1 \
                    or k % s or b % s or self.sparse_window % b \
                    or self.sparse_dense_len % b:
                raise ValueError(
                    "sparse layers need sparse_kernel and sparse_block in "
                    "whole sparse_stride, sparse_window and "
                    "sparse_dense_len in whole sparse_block, all >= 1")
        if "window" in self.attn_kinds and self.window < 1:
            raise ValueError("window layers need window >= 1")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown router_scoring {self.router_scoring!r}")
        if self.n_dense_layers and not (self.n_experts and self.dense_d_ff):
            raise ValueError(
                "n_dense_layers needs n_experts and dense_d_ff")
        first, count = self.held
        if not 0 <= first <= first + count <= max(self.n_experts, 0):
            raise ValueError(
                f"experts_held {self.experts_held} outside 0.."
                f"{self.n_experts}")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kv_heads(self) -> int:
        return self.kv_heads_of("full")

    def kv_heads_of(self, attn_kind: str) -> int:
        kv = self.n_kv_heads or self.n_heads
        if attn_kind == "window":
            kv = self.window_kv_heads or kv
        if attn_kind == "linear":
            kv = self.n_heads
        if self.n_heads % kv:
            raise ValueError(
                f"n_kv_heads {kv} must divide n_heads {self.n_heads}"
            )
        return kv

    def rope_theta_of(self, attn_kind: str) -> float:
        if attn_kind == "window" and self.window_rope_theta:
            return self.window_rope_theta
        return self.rope_theta

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def rot_dim(self) -> int:
        return self.rotary_dim or self.head_dim

    @property
    def held(self) -> tuple:
        """(first, count) of the experts held here."""
        return tuple(self.experts_held or (0, self.n_experts))

    @property
    def layered(self) -> bool:
        return bool(
            self.attn_kinds or self.v_head_dim or self.rotary_dim
            or self.v_scale != 1.0 or self.n_dense_layers
            or self.router_scoring != "softmax" or self.router_bias
            or self.experts_held or self.qk_norm or self.no_rope_kinds
            or self.gated_kinds or self.out_norm_kinds
            or self.tie_embeddings
            or (self.embed_scale, self.residual_scale,
                self.logit_scale) != (1.0, 1.0, 1.0))

    @property
    def layer_kinds(self) -> tuple:
        """(attention kind, MLP kind) of every layer."""
        attn = self.attn_kinds or ("full",) * self.n_layers
        return tuple(
            (a, "moe" if self.n_experts and i >= self.n_dense_layers
             else "dense")
            for i, a in enumerate(attn))

    @property
    def layer_groups(self) -> dict:
        """Kind name ("window_moe") -> its layers in order: the groups
        of stacks a layered configuration's parameters come in."""
        groups: dict = {}
        for i, (a, m) in enumerate(self.layer_kinds):
            groups.setdefault(f"{a}_{m}", []).append(i)
        return {k: tuple(v) for k, v in groups.items()}

    def refuse_layered(self, what: str) -> None:
        if self.layered:
            raise ValueError(
                f"{what} runs uniform layers only; this configuration "
                f"has layer kinds (linear, sparse and conv layers have "
                f"no backward: they are served, not trained), its own "
                f"v/rotary widths, a tied head, or a share of its experts "
                f"(TransformerConfig.layered): serve it through "
                f"ServingEngine")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: TransformerConfig) -> dict:
    """Params as a plain pytree; per-layer weights stacked on a leading
    ``layers`` axis so the trunk is one ``lax.scan`` (or, reshaped, one
    pipeline stage stack). fp32 master weights."""
    d, h, dh, f, l = (
        cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
    )
    hkv = cfg.kv_heads
    keys = jax.random.split(key, 10)

    def norm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale)

    if cfg.layered:
        groups = {
            name: _init_group(jax.random.fold_in(keys[1], i), cfg, name,
                              len(layers))
            for i, (name, layers) in enumerate(cfg.layer_groups.items())
        }
        top = {
            "embed": norm(keys[0], (cfg.vocab_size, d), 1.0),
            "layers": groups,
            "final_norm": jnp.ones((d,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            top["unembed"] = norm(keys[9], (d, cfg.vocab_size), d ** -0.5)
        return top

    layer = {
        "ln1": jnp.ones((l, d), jnp.float32),
        "wq": norm(keys[1], (l, d, h, dh), d ** -0.5),
        "wk": norm(keys[2], (l, d, hkv, dh), d ** -0.5),
        "wv": norm(keys[3], (l, d, hkv, dh), d ** -0.5),
        "wo": norm(keys[4], (l, h, dh, d), (h * dh) ** -0.5),
        "ln2": jnp.ones((l, d), jnp.float32),
    }
    if cfg.n_experts:
        e = cfg.n_experts
        layer["router"] = norm(keys[5], (l, d, e), d ** -0.5)
        layer["w_gate"] = norm(keys[6], (l, e, d, f), d ** -0.5)
        layer["w_up"] = norm(keys[7], (l, e, d, f), d ** -0.5)
        layer["w_down"] = norm(keys[8], (l, e, f, d), f ** -0.5)
    else:
        layer["w_gate"] = norm(keys[6], (l, d, f), d ** -0.5)
        layer["w_up"] = norm(keys[7], (l, d, f), d ** -0.5)
        layer["w_down"] = norm(keys[8], (l, f, d), f ** -0.5)

    return {
        "embed": norm(keys[0], (cfg.vocab_size, d), 1.0),
        "layers": layer,
        "final_norm": jnp.ones((d,), jnp.float32),
        "unembed": norm(keys[9], (d, cfg.vocab_size), d ** -0.5),
    }


def _init_group(key, cfg: TransformerConfig, name: str, n: int) -> dict:
    """One group of a layered configuration: ``n`` layers of one
    (attention kind, MLP kind), each leaf stacked [n, ...]."""
    attn, mlp = name.split("_")
    d, h, dk, dv = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_dim
    hkv = cfg.kv_heads_of(attn)
    keys = jax.random.split(key, 10)

    def norm(k, shape, scale):
        return jax.random.normal(k, (n,) + shape, jnp.float32) * scale

    if attn == "conv":
        group = {
            "ln1": jnp.ones((n, d), jnp.float32),
            "in_proj": norm(keys[0], (d, 3 * d), d ** -0.5),
            "conv_w": norm(keys[1], (d, cfg.conv_kernel),
                           cfg.conv_kernel ** -0.5),
            "out_proj": norm(keys[3], (d, d), d ** -0.5),
            "ln2": jnp.ones((n, d), jnp.float32),
        }
    else:
        group = {
            "ln1": jnp.ones((n, d), jnp.float32),
            "wq": norm(keys[0], (d, h, dk), d ** -0.5),
            "wk": norm(keys[1], (d, hkv, dk), d ** -0.5),
            "wv": norm(keys[2], (d, hkv, dv), d ** -0.5),
            "wo": norm(keys[3], (h, dv, d), (h * dv) ** -0.5),
            "ln2": jnp.ones((n, d), jnp.float32),
        }
    if attn == "window" and cfg.window_sink:
        group["sink"] = norm(keys[4], (h,), 1.0)
    if cfg.qk_norm and attn != "conv":
        group["q_norm"] = jnp.ones((n, dk), jnp.float32)
        group["k_norm"] = jnp.ones((n, dk), jnp.float32)
    if attn in cfg.gated_kinds:
        group["w_ogate"] = norm(jax.random.fold_in(keys[4], 1),
                                (d, h * dv), d ** -0.5)
    if attn in cfg.out_norm_kinds:
        group["o_norm"] = jnp.ones((n, h * dv), jnp.float32)
    if mlp == "moe":
        e, f = cfg.n_experts, cfg.d_ff
        held = cfg.held[1]
        group["router"] = norm(keys[5], (d, e), d ** -0.5)
        if cfg.router_bias:
            group["router_bias"] = norm(keys[9], (e,), 0.1)
        group["w_gate"] = norm(keys[6], (held, d, f), d ** -0.5)
        group["w_up"] = norm(keys[7], (held, d, f), d ** -0.5)
        group["w_down"] = norm(keys[8], (held, f, d), f ** -0.5)
    else:
        f = cfg.dense_d_ff if cfg.n_dense_layers else cfg.d_ff
        group["w_gate"] = norm(keys[6], (d, f), d ** -0.5)
        group["w_up"] = norm(keys[7], (d, f), d ** -0.5)
        group["w_down"] = norm(keys[8], (f, d), f ** -0.5)
    return group


def param_roles(cfg: TransformerConfig) -> dict:
    """Logical-axis roles per leaf (sharding.py LOGICAL_RULES maps roles to
    mesh axes): tp splits heads/mlp/vocab, fsdp splits the embed dim, pp
    stages the stacked layers axis, ep splits experts."""
    cfg.refuse_layered("param_roles (the training layout)")
    layer = {
        "ln1": ("layers", None),
        "wq": ("layers", "embed_fsdp", "heads", None),
        "wk": ("layers", "embed_fsdp", "heads", None),
        "wv": ("layers", "embed_fsdp", "heads", None),
        "wo": ("layers", "heads", None, "embed_fsdp"),
        "ln2": ("layers", None),
    }
    if cfg.n_experts:
        layer["router"] = ("layers", None, "expert")
        layer["w_gate"] = ("layers", "expert", "embed_fsdp", "mlp")
        layer["w_up"] = ("layers", "expert", "embed_fsdp", "mlp")
        layer["w_down"] = ("layers", "expert", "mlp", "embed_fsdp")
    else:
        layer["w_gate"] = ("layers", "embed_fsdp", "mlp")
        layer["w_up"] = ("layers", "embed_fsdp", "mlp")
        layer["w_down"] = ("layers", "mlp", "embed_fsdp")
    return {
        "embed": ("vocab", None),
        "layers": layer,
        "final_norm": (None,),
        "unembed": ("embed_fsdp", "vocab"),
    }


# ---------------------------------------------------------------------------
# Blocks (shared by both trunk modes)
# ---------------------------------------------------------------------------

def _attention(x, lp, cfg, cos, sin, *, manual: bool, mesh: Mesh | None):
    """Pre-norm attention block. x: [b, t, d] (local shard in manual mode).

    GSPMD: heads constrained onto tp, seq onto sp; ring attention when the
    mesh has sp > 1 (exact attention over the sharded sequence), else flash.
    Manual: params arrive pre-sliced over tp by shard_map in_specs; output
    projection psums over tp; sp > 1 runs the in-shard_map ring body with
    RoPE positions offset by the shard's global start.
    """
    dt = cfg.compute_dtype
    h = rms_norm(x, lp["ln1"], eps=cfg.rms_eps, mesh=mesh).astype(dt)
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dt))
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(dt))

    def expand_kv(arr):
        """GQA: repeat K/V heads up to q's head count for attention paths
        that expect matched heads (the repeat is a broadcast XLA folds into
        the consuming matmul; training is compute-bound regardless — the
        cache-size win happens in models/decode.py). Uses q's *local* head
        count so it stays correct under tp-sliced manual mode."""
        group = q.shape[2] // arr.shape[2]
        return jnp.repeat(arr, group, axis=2) if group > 1 else arr

    if manual:
        sp = lax.axis_size("sp")
        t_local = x.shape[1]
        positions = lax.axis_index("sp") * t_local + jnp.arange(t_local)
        q = rotate_rope(q, cos, sin, positions=positions)
        k = rotate_rope(k, cos, sin, positions=positions)
        if sp > 1:
            o = ring_attention_local(
                q, expand_kv(k), expand_kv(v), axis_name="sp", causal=True,
                scale=cfg.head_dim ** -0.5,
            )
        else:
            o = flash_attention(q, k, v, causal=True)
        out = jnp.einsum("bthk,hkd->btd", o.astype(dt), lp["wo"].astype(dt))
        return lax.psum(out, "tp")

    q = with_logical_constraint(q, "batch", "seq", "heads", None, mesh=mesh)
    k = with_logical_constraint(k, "batch", "seq", "heads", None, mesh=mesh)
    q = rotate_rope(q, cos, sin)
    k = rotate_rope(k, cos, sin)
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        o = ring_attention(q, expand_kv(k), expand_kv(v), mesh, causal=True)
    else:
        o = flash_attention(q, k, v, causal=True, mesh=mesh)
    out = jnp.einsum("bthk,hkd->btd", o.astype(dt), lp["wo"].astype(dt))
    return with_logical_constraint(out, "batch", "seq", "embed", mesh=mesh)


def _dense_mlp(
    x, lp, cfg, *, manual: bool, mesh: Mesh | None = None,
    constrain: bool = True,
):
    """SwiGLU. tp splits d_ff columns; manual mode psums the row-parallel
    down-projection (megatron pattern), GSPMD lets SPMD insert it.
    ``constrain=False`` skips the sharding constraint for mesh-free callers
    (the KV-cache decode path reuses this exact math)."""
    dt = cfg.compute_dtype
    h = rms_norm(x, lp["ln2"], eps=cfg.rms_eps, mesh=mesh).astype(dt)
    g = jnp.einsum("btd,df->btf", h, lp["w_gate"].astype(dt))
    u = jnp.einsum("btd,df->btf", h, lp["w_up"].astype(dt))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    out = jnp.einsum("btf,fd->btd", act, lp["w_down"].astype(dt))
    if manual:
        return lax.psum(out, "tp")
    if not constrain:
        return out
    return with_logical_constraint(out, "batch", "seq", "embed", mesh=mesh)


def _route_tokens(hn, router, top_k: int, *, scoring: str = "softmax",
                  bias=None):
    """Shared router gating for training AND decode (models/decode.py):
    fp32 logits (at ``highest`` matmul precision: on a TPU a float32
    product is otherwise rounded to bfloat16 first, and a near tie then
    flips), scores by softmax or sigmoid, top-k over the scores — plus
    ``bias`` [E] where the model has one, which chooses and never weighs
    — and epsilon-guarded renormalization of the selected scores. One
    implementation so the decode-vs-training token-exact parity cannot
    drift. Returns (gate_logits [.., E] f32, probs [.., E], gvals
    [.., k] normalized, gidx [.., k])."""
    gate_logits = jnp.einsum(
        "btd,de->bte", hn.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(gate_logits)
    else:
        probs = jax.nn.softmax(gate_logits, axis=-1)
    if bias is None:
        gvals, gidx = lax.top_k(probs, top_k)
    else:
        _, gidx = lax.top_k(probs + bias.astype(jnp.float32), top_k)
        gvals = jnp.take_along_axis(probs, gidx, axis=-1)
    gvals = gvals / jnp.maximum(gvals.sum(-1, keepdims=True), 1e-9)
    return gate_logits, probs, gvals, gidx


def _moe_mlp(x, lp, cfg, mesh: Mesh):
    """Capacity-based top-k MoE (Switch/Mesh-TF dispatch-combine einsums —
    fully static shapes, so XLA inserts the ep all-to-alls from the expert
    sharding constraint; no data-dependent control flow). GSPMD mode only.

    Tokens beyond an expert's capacity are dropped (residual passes them
    through unchanged) — the standard capacity_factor trade.

    Returns ``(out, aux)``; aux carries the Switch-style load-balance loss,
    the router z-loss, and diagnostics (drop rate, assignment entropy) for
    the train loop to surface. Without the balance term the router can
    collapse onto few experts — dropped tokens then pass silently through
    the residual and the layer stops training.
    """
    dt = cfg.compute_dtype
    b, t, d = x.shape
    e, kk = cfg.n_experts, cfg.expert_top_k
    cap = max(1, int(cfg.capacity_factor * b * t * kk / e))

    hn = rms_norm(x, lp["ln2"], eps=cfg.rms_eps, mesh=mesh)
    gate_logits, probs, gvals, gidx = _route_tokens(hn, lp["router"], kk)
    onehot_e = jax.nn.one_hot(gidx, e, dtype=jnp.float32)  # [b,t,k,E]

    # Switch balance loss (arXiv 2101.03961 eq. 4, generalized to top-k):
    # E · Σ_e f_e·P_e where f_e is the fraction of routed (token, choice)
    # slots assigned to expert e and P_e the mean router probability. f is
    # one-hot (non-differentiable) — the gradient flows through P; minimum
    # 1.0 at the uniform assignment. z-loss (PaLM §B): mean logsumexp², a
    # pull toward small router logits.
    frac = onehot_e.mean((0, 1, 2))                      # [E], sums to 1
    pmean = probs.mean((0, 1))                           # [E]
    balance = e * jnp.sum(frac * pmean)
    zloss = jnp.mean(jax.nn.logsumexp(gate_logits, axis=-1) ** 2)
    entropy = -jnp.sum(frac * jnp.log(frac + 1e-9))

    # Position of each (token, choice) within its expert: flatten in
    # (k-priority, token) order — all first choices queue before any second
    # choice — and cumsum per expert.
    # int32 cumsum: fp32 would lose exactness past 2^24 routed entries per
    # expert, colliding capacity slots silently at large batch*seq.
    flat = onehot_e.transpose(2, 0, 1, 3).reshape(kk * b * t, e).astype(jnp.int32)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos_e = (pos * flat).sum(-1).reshape(kk, b, t).transpose(1, 2, 0)  # [b,t,k]
    keep = (pos_e < cap).astype(jnp.float32)
    onehot_c = jax.nn.one_hot(pos_e, cap, dtype=jnp.float32)
    onehot_c = onehot_c * keep[..., None]               # [b,t,k,C]
    drop_rate = 1.0 - keep.mean()

    dispatch = jnp.einsum("btke,btkc->btec", onehot_e, onehot_c)
    combine = jnp.einsum("btke,btkc->btec", onehot_e * gvals[..., None], onehot_c)

    xin = jnp.einsum("btd,btec->ecd", hn.astype(dt), dispatch.astype(dt))
    xin = with_logical_constraint(xin, "expert", None, None, mesh=mesh)
    g = jnp.einsum("ecd,edf->ecf", xin, lp["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xin, lp["w_up"].astype(dt))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    out_e = jnp.einsum("ecf,efd->ecd", act, lp["w_down"].astype(dt))
    out_e = with_logical_constraint(out_e, "expert", None, None, mesh=mesh)
    out = jnp.einsum("ecd,btec->btd", out_e, combine.astype(dt))
    out = with_logical_constraint(out, "batch", "seq", "embed", mesh=mesh)
    aux = {
        "moe_balance": balance,
        "moe_zloss": zloss,
        "moe_drop_rate": drop_rate,
        "moe_entropy": entropy,
    }
    return out, aux


def _moe_mlp_manual(x, lp, cfg):
    """Capacity-based top-k MoE inside the pipeline trunk's shard_map:
    the manual-collective twin of ``_moe_mlp``. Each device routes its
    LOCAL tokens (batch sharded over dp×ep, seq over sp) across all E
    experts, packs per-expert capacity slabs, and exchanges them with one
    ``lax.all_to_all`` over ``ep`` so its resident E/ep experts see every
    ep-peer's tokens; a second all_to_all brings expert outputs home for
    the combine. Expert ff weights are additionally tp-column-split, so
    the combined output psums over tp exactly like ``_dense_mlp``'s
    megatron down-projection.

    Aux-loss parity with the GSPMD path: balance/z/entropy/drop stats are
    ``pmean``'d over the data axes (dp, ep, sp) BEFORE the nonlinear
    combinations (the Switch balance term is a product of two means —
    averaging per-device balances would not equal the global-stat loss
    the GSPMD trunk computes). Capacity is per (device, expert):
    ``cf·b_l·t_l·k/E`` local slots, so total capacity matches the GSPMD
    global formula when shards are equal-sized.
    """
    dt = cfg.compute_dtype
    b, t, d = x.shape  # local shard
    e, kk = cfg.n_experts, cfg.expert_top_k
    ep = lax.axis_size("ep")
    e_local = lp["w_gate"].shape[0]  # E / ep resident experts
    cap = max(1, int(cfg.capacity_factor * b * t * kk / e))

    hn = rms_norm(x, lp["ln2"], eps=cfg.rms_eps)
    gate_logits, probs, gvals, gidx = _route_tokens(hn, lp["router"], kk)
    onehot_e = jax.nn.one_hot(gidx, e, dtype=jnp.float32)  # [b,t,k,E]

    data_axes = ("dp", "ep", "sp")
    frac = lax.pmean(onehot_e.mean((0, 1, 2)), data_axes)       # [E]
    pmean_probs = lax.pmean(probs.mean((0, 1)), data_axes)      # [E]
    balance = e * jnp.sum(frac * pmean_probs)
    zloss = lax.pmean(
        jnp.mean(jax.nn.logsumexp(gate_logits, axis=-1) ** 2), data_axes
    )
    entropy = -jnp.sum(frac * jnp.log(frac + 1e-9))

    # Same slot assignment as the GSPMD path (k-priority order, int32).
    flat = onehot_e.transpose(2, 0, 1, 3).reshape(kk * b * t, e).astype(jnp.int32)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos_e = (pos * flat).sum(-1).reshape(kk, b, t).transpose(1, 2, 0)
    keep = (pos_e < cap).astype(jnp.float32)
    onehot_c = jax.nn.one_hot(pos_e, cap, dtype=jnp.float32) * keep[..., None]
    drop_rate = lax.pmean(1.0 - keep.mean(), data_axes)

    dispatch = jnp.einsum("btke,btkc->btec", onehot_e, onehot_c)
    combine = jnp.einsum("btke,btkc->btec", onehot_e * gvals[..., None], onehot_c)

    xin = jnp.einsum("btd,btec->ecd", hn.astype(dt), dispatch.astype(dt))
    # [E, C, d] -> [ep, E_l, C, d] -> exchange -> [E_l, ep·C, d]: slab j of
    # the received stack is peer j's tokens for MY resident experts.
    xin = xin.reshape(ep, e_local, cap, d)
    xin = lax.all_to_all(xin, "ep", split_axis=0, concat_axis=0)
    xin = xin.swapaxes(0, 1).reshape(e_local, ep * cap, d)
    g = jnp.einsum("ecd,edf->ecf", xin, lp["w_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xin, lp["w_up"].astype(dt))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    out_e = jnp.einsum("ecf,efd->ecd", act, lp["w_down"].astype(dt))
    # Reverse exchange: expert outputs back to the tokens' home devices.
    out_e = out_e.reshape(e_local, ep, cap, d).swapaxes(0, 1)
    out_e = lax.all_to_all(out_e, "ep", split_axis=0, concat_axis=0)
    out_e = out_e.reshape(e, cap, d)
    out = jnp.einsum("ecd,btec->btd", out_e, combine.astype(dt))
    # ff columns are tp-sliced (w_gate/w_up [.., f/tp], w_down [f/tp, ..])
    # — the partial down-projections sum over tp, like _dense_mlp manual.
    out = lax.psum(out, "tp")
    aux = {
        "moe_balance": balance,
        "moe_zloss": zloss,
        "moe_drop_rate": drop_rate,
        "moe_entropy": entropy,
    }
    return out, aux


def _decoder_layer(x, lp, cfg, cos, sin, *, manual: bool, mesh: Mesh | None):
    """Returns ``(x, aux)``; aux is the MoE router loss dict (per layer)
    when the config has experts — on the GSPMD path and (since r5) the
    manual pipeline path alike — else None."""
    x = x + _attention(x, lp, cfg, cos, sin, manual=manual, mesh=mesh)
    aux = None
    if cfg.n_experts and not manual:
        moe_out, aux = _moe_mlp(x, lp, cfg, mesh)
        x = x + moe_out
    elif cfg.n_experts:
        moe_out, aux = _moe_mlp_manual(x, lp, cfg)
        x = x + moe_out
    else:
        x = x + _dense_mlp(x, lp, cfg, manual=manual, mesh=mesh)
    return x, aux


# ---------------------------------------------------------------------------
# GSPMD trunk (pp == 1)
# ---------------------------------------------------------------------------

def _remat_policy(cfg: TransformerConfig):
    """None = save nothing (full recompute); the "dots" policy keeps what
    is expensive to recompute: matmul outputs and the flash forward's
    result (a Pallas call is no dot to XLA's policy, which alone would
    run the kernel twice a layer), so the backward re-runs only
    elementwise work."""
    if cfg.remat_policy == "full":
        return None
    if cfg.remat_policy == "dots":
        policies = jax.checkpoint_policies
        return policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(*FLASH_RESIDUALS),
        )
    raise ValueError(
        f"unknown remat_policy {cfg.remat_policy!r}; expected full|dots"
    )


def forward(
    params: dict, tokens: jax.Array, cfg: TransformerConfig,
    mesh: Mesh | None = None, *, return_aux: bool = False,
):
    """tokens [B, T] int32 -> logits [B, T, V] (compute dtype). Everything
    under jit + sharding constraints; call inside ``jax.jit``.

    ``return_aux=True`` additionally returns the layer-averaged MoE router
    aux dict (balance/z losses + diagnostics; empty dict for dense
    configs) — the train loss needs it, inference callers don't."""
    cfg.refuse_layered("forward")
    dt = cfg.compute_dtype
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, theta=cfg.rope_theta)
    x = params["embed"][tokens].astype(dt)
    x = with_logical_constraint(x, "batch", "seq", "embed", mesh=mesh)

    layer_fn = functools.partial(
        _decoder_layer, cfg=cfg, cos=cos, sin=sin, manual=False, mesh=mesh
    )
    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(cfg))

    if cfg.layer_scan_unroll >= cfg.n_layers:
        # Fully unrolled: a static Python loop over static slices (see
        # ``layer_scan_unroll``; no chip record favours either form).
        aux_list = []
        for layer in range(cfg.n_layers):
            lp = jax.tree.map(lambda p: p[layer], params["layers"])
            x, aux_l = layer_fn(x, lp)
            aux_list.append(aux_l)
        aux_layers = (
            None if aux_list[0] is None
            else jax.tree.map(lambda *xs: jnp.stack(xs), *aux_list)
        )
    else:
        x, aux_layers = lax.scan(
            layer_fn, x, params["layers"], unroll=cfg.layer_scan_unroll
        )
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps, mesh=mesh).astype(dt)
    logits = jnp.einsum("btd,dv->btv", x, params["unembed"].astype(dt))
    logits = with_logical_constraint(logits, "batch", "seq", "vocab", mesh=mesh)
    if not return_aux:
        return logits
    aux = (
        {} if aux_layers is None
        else jax.tree.map(lambda v: v.mean(), aux_layers)
    )
    return logits, aux


# ---------------------------------------------------------------------------
# Pipeline trunk (pp > 1): manual-collective layers inside shard_map
# ---------------------------------------------------------------------------

def _stage_param_specs(cfg: TransformerConfig) -> dict:
    """PartitionSpecs for pipeline-stage params: leading pp axis, tp on the
    megatron dims (so each shard_map body holds only its head/mlp slice);
    MoE experts split over ep (each body holds E/ep resident experts) with
    the ff dim still tp-column-split. The (tiny, fp32-routed) router
    replicates within the stage."""
    layer = {
        "ln1": P("pp", None, None),
        "wq": P("pp", None, None, "tp", None),
        "wk": P("pp", None, None, "tp", None),
        "wv": P("pp", None, None, "tp", None),
        "wo": P("pp", None, "tp", None, None),
        "ln2": P("pp", None, None),
    }
    if cfg.n_experts:
        layer["router"] = P("pp", None, None, None)
        layer["w_gate"] = P("pp", None, "ep", None, "tp")
        layer["w_up"] = P("pp", None, "ep", None, "tp")
        layer["w_down"] = P("pp", None, "ep", "tp", None)
    else:
        layer["w_gate"] = P("pp", None, None, "tp")
        layer["w_up"] = P("pp", None, None, "tp")
        layer["w_down"] = P("pp", None, "tp", None)
    return layer


def forward_pipeline(
    params: dict,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    *,
    num_microbatches: int,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
    return_aux: bool = False,
):
    """Pipelined trunk: embed/unembed stay GSPMD (outside the pipeline —
    the classic constraint that stages map microbatch -> same-shape
    microbatch), the layer stack runs as pp stages with manual tp psums and
    the in-shard_map sp ring. MoE stages route through ``_moe_mlp_manual``
    (experts resident per ep rank, all_to_all token exchange); their
    router aux losses are accumulated across microbatches inside the
    schedule and averaged, so pp×ep composes.

    ``return_aux=True`` additionally returns the layer- and
    microbatch-averaged MoE aux dict (empty for dense configs), mirroring
    ``forward``.

    ``schedule="interleaved"`` with ``virtual_stages=v`` assigns each
    device v round-robin chunks of n_layers/(v·pp) layers (Megatron
    virtual stages) — the bubble shrinks ~v-fold; see
    ``parallel.pipeline.schedule_info``."""
    cfg.refuse_layered("forward_pipeline")
    pp = mesh.shape["pp"]
    if cfg.n_experts and cfg.n_experts % mesh.shape.get("ep", 1):
        raise ValueError(
            f"n_experts {cfg.n_experts} not divisible by ep "
            f"{mesh.shape['ep']} — resident-expert slabs must be equal"
        )
    v = virtual_stages
    if schedule != "interleaved" and v != 1:
        raise ValueError("virtual_stages > 1 requires schedule='interleaved'")
    if cfg.n_layers % (pp * v):
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pp*virtual {pp * v}"
        )
    tp = mesh.shape.get("tp", 1)
    if cfg.kv_heads % tp:
        # The stage param specs slice wk/wv head axes over tp; a non-dividing
        # GQA head count would silently replicate K/V out of step with the
        # sliced wq.
        raise ValueError(
            f"pipeline trunk needs n_kv_heads ({cfg.kv_heads}) divisible by "
            f"tp ({tp}); use the GSPMD trunk or fewer tp shards"
        )
    dt = cfg.compute_dtype
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, theta=cfg.rope_theta)

    x = params["embed"][tokens].astype(dt)
    x = with_logical_constraint(x, "batch", "seq", "embed", mesh=mesh)

    if schedule == "interleaved":
        # [L, ...] -> [pp, v, L/(v*pp), ...] where [d, c] holds global
        # virtual stage c*pp + d (round-robin: [v*pp] -> [v, pp] indexes
        # [c, d], then swap to put the sharded device axis first).
        lv = cfg.n_layers // (pp * v)

        def to_chunks(p):
            return (
                p.reshape((v, pp, lv) + p.shape[1:]).swapaxes(0, 1)
            )

        stage_params = jax.tree.map(to_chunks, params["layers"])
    else:
        # [L, ...] -> [pp, L/pp, ...]
        stage_params = jax.tree.map(
            lambda p: p.reshape((pp, cfg.n_layers // pp) + p.shape[1:]),
            params["layers"],
        )

    def stage_fn(sp_params, xm):
        layer_fn = functools.partial(
            _decoder_layer, cfg=cfg, cos=cos, sin=sin, manual=True, mesh=None
        )
        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(cfg))

        def body(carry, lp):
            out, aux = layer_fn(carry, lp)
            return out, aux  # aux None for dense layers

        n_local = jax.tree.leaves(sp_params)[0].shape[0]
        out, aux_layers = lax.scan(
            body, xm, sp_params,
            unroll=min(cfg.layer_scan_unroll, n_local),
        )
        if not cfg.n_experts:
            return out
        # Sum over this chunk's layers; the schedule accumulates across
        # (chunks × microbatches) and forward_pipeline normalizes.
        return out, jax.tree.map(lambda v: v.sum(), aux_layers)

    param_specs = _stage_param_specs(cfg)
    if schedule == "interleaved":
        # Chunk axis rides unsharded between pp and the weight dims.
        param_specs = {
            k: P(spec[0], None, *spec[1:]) for k, spec in param_specs.items()
        }
    out = pipeline_apply(
        stage_fn,
        stage_params,
        x,
        mesh=mesh,
        num_microbatches=num_microbatches,
        data_spec=P(None, ("dp", "ep"), "sp", None),
        param_specs=param_specs,
        schedule=schedule,
        virtual=v,
        stage_aux=bool(cfg.n_experts),
    )
    if cfg.n_experts:
        x, aux_sum = out
        # aux_sum is Σ over (layer, microbatch); normalize to the same
        # per-layer/per-(micro)batch mean the GSPMD trunk reports.
        aux = jax.tree.map(
            lambda v: v / (cfg.n_layers * num_microbatches), aux_sum
        )
    else:
        x, aux = out, {}
    x = with_logical_constraint(x, "batch", "seq", "embed", mesh=mesh)
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps, mesh=mesh).astype(dt)
    logits = jnp.einsum("btd,dv->btv", x, params["unembed"].astype(dt))
    logits = with_logical_constraint(logits, "batch", "seq", "vocab", mesh=mesh)
    if not return_aux:
        return logits
    return logits, aux
