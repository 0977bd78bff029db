"""ResNet family, TPU-first — the workload BASELINE.json config 5 names
(ResNet-50, 8 workers, gang-scheduled + fault-restart).

Design choices for the MXU/XLA:

* NHWC layout with HWIO kernels — XLA's TPU conv emitter tiles these onto
  the MXU directly; channel counts stay multiples of 8.
* bfloat16 compute, fp32 master weights (cast at use, like the
  transformer).
* GroupNorm instead of BatchNorm: no running statistics and no
  cross-replica moment sync, so the block is a pure function of
  (params, x) — under ``jit`` + dp sharding there is nothing stateful to
  thread through, and accuracy at classification scale is equivalent.
* Stride-2 projection shortcuts (the v1.5 placement: stride on the 3x3).

Depths: 18/34 use basic blocks, 50/101/152 bottlenecks — same stage plan
table as the canonical family.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

STAGE_PLANS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
}


@dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    width: int = 64          # stem channels; stages are 1x/2x/4x/8x
    n_classes: int = 1000
    gn_groups: int = 8
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def plan(self) -> tuple[str, tuple[int, ...]]:
        try:
            return STAGE_PLANS[self.depth]
        except KeyError:
            raise ValueError(
                f"unsupported depth {self.depth}; legal: {sorted(STAGE_PLANS)}"
            ) from None


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * (
        (2.0 / fan_in) ** 0.5
    )


def _gn_params(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def resnet_init(key: jax.Array, cfg: ResNetConfig) -> dict:
    block_kind, stages = cfg.plan
    expansion = 4 if block_kind == "bottleneck" else 1
    keys = iter(jax.random.split(key, 4 + sum(stages) * 4))
    params: dict = {
        "stem": {
            "conv": _conv_init(next(keys), 7, 7, 3, cfg.width),
            "gn": _gn_params(cfg.width),
        },
        "stages": [],
    }
    cin = cfg.width
    for si, n_blocks in enumerate(stages):
        cmid = cfg.width * (2 ** si)
        cout = cmid * expansion
        blocks = []
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            block: dict = {}
            if block_kind == "basic":
                block["conv1"] = _conv_init(next(keys), 3, 3, cin, cmid)
                block["gn1"] = _gn_params(cmid)
                block["conv2"] = _conv_init(next(keys), 3, 3, cmid, cout)
                block["gn2"] = _gn_params(cout)
            else:
                block["conv1"] = _conv_init(next(keys), 1, 1, cin, cmid)
                block["gn1"] = _gn_params(cmid)
                block["conv2"] = _conv_init(next(keys), 3, 3, cmid, cmid)
                block["gn2"] = _gn_params(cmid)
                block["conv3"] = _conv_init(next(keys), 1, 1, cmid, cout)
                block["gn3"] = _gn_params(cout)
            if stride != 1 or cin != cout:
                block["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
                block["proj_gn"] = _gn_params(cout)
            blocks.append(block)
            cin = cout
        params["stages"].append(blocks)
    params["head"] = {
        "w": jax.random.normal(next(keys), (cin, cfg.n_classes), jnp.float32)
        * (cin ** -0.5),
        "b": jnp.zeros((cfg.n_classes,), jnp.float32),
    }
    return params


def _conv(x, w, stride=1, dtype=None):
    return lax.conv_general_dilated(
        x, w.astype(dtype or x.dtype),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _group_norm(x, gn, groups, eps=1e-5):
    """Single-accumulation GroupNorm: moments via E[x²]−E[x]² with fp32
    accumulation directly off the bf16 activations. The naive form
    (upcast the whole tensor, two-pass mean/var) materialized fp32 copies
    of stage-1-sized activations several times per norm — rewriting it
    this way cut the ResNet-50 train step severalfold on the earlier
    platform (not re-measured on the current chip): the norm fuses into a pair of reduces plus
    one elementwise pass. E[x²]−E[x]² cancellation is a non-issue at
    post-conv activation scale with fp32 accumulation (clamped at 0)."""
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True, dtype=jnp.float32)
    mean2 = jnp.mean(
        jnp.square(xg.astype(jnp.float32)), axis=(1, 2, 4), keepdims=True
    )
    inv = lax.rsqrt(jnp.maximum(mean2 - jnp.square(mean), 0.0) + eps)
    y = (xg.astype(jnp.float32) - mean) * inv
    y = y.reshape(b, h, w, c) * gn["scale"] + gn["bias"]
    return y.astype(x.dtype)


def _block(x, p, kind, stride, groups, dt):
    out = x
    if kind == "basic":
        out = jax.nn.relu(_group_norm(_conv(out, p["conv1"], stride, dt),
                                      p["gn1"], groups))
        out = _group_norm(_conv(out, p["conv2"], 1, dt), p["gn2"], groups)
    else:
        out = jax.nn.relu(_group_norm(_conv(out, p["conv1"], 1, dt),
                                      p["gn1"], groups))
        out = jax.nn.relu(_group_norm(_conv(out, p["conv2"], stride, dt),
                                      p["gn2"], groups))
        out = _group_norm(_conv(out, p["conv3"], 1, dt), p["gn3"], groups)
    if "proj" in p:
        x = _group_norm(_conv(x, p["proj"], stride, dt), p["proj_gn"], groups)
    return jax.nn.relu(out + x)


def resnet_apply(params: dict, images: jax.Array, cfg: ResNetConfig) -> jax.Array:
    """images: [B, H, W, 3] -> logits [B, n_classes] (fp32)."""
    block_kind, stages = cfg.plan
    dt = cfg.compute_dtype
    if images.dtype == jnp.uint8:
        # On-device decode of byte-transferred batches: the data plane
        # ships raw uint8 (4× fewer H2D bytes than float32) and the cast
        # + [0,1) scale happen here, fused into the stem conv. Callers
        # needing a different normalization pass it via
        # make_image_classifier_step(preprocess=...) instead.
        x = images.astype(dt) * jnp.asarray(1.0 / 255.0, dt)
    else:
        x = images.astype(dt)
    x = _conv(x, params["stem"]["conv"], stride=2, dtype=dt)
    x = jax.nn.relu(_group_norm(x, params["stem"]["gn"], cfg.gn_groups))
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    for si, blocks in enumerate(params["stages"]):
        for bi, bp in enumerate(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            x = _block(x, bp, block_kind, stride, cfg.gn_groups, dt)
    x = x.mean(axis=(1, 2)).astype(jnp.float32)  # global average pool
    return x @ params["head"]["w"] + params["head"]["b"]
