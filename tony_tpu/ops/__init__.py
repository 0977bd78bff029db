"""Hot-path ops: Pallas TPU kernels with pure-JAX fallbacks.

The reference has no compute ops at all (SURVEY.md: "no kernels, no autograd,
no tensors") — this layer is the TPU-native capability the rebuild adds so
the framework's models keep the MXU busy: flash attention, fused RMSNorm,
RoPE, stable cross-entropy. Every op dispatches to a Pallas kernel on TPU
and a numerically identical blockwise-JAX path elsewhere (which is also the
recompute used for the backward pass).
"""

from tony_tpu.ops.attention import (
    cache_decode_attention,
    cache_prefill_attention,
    flash_attention,
    flash_attention_lse,
    grouped_cache_attention,
)
from tony_tpu.ops.grouped import grouped_matmul
from tony_tpu.ops.hybrid import (
    lightning_decode,
    lightning_prefill,
    sparse_decode_attention,
    sparse_prefill_attention,
)
from tony_tpu.ops.norms import rms_norm
from tony_tpu.ops.rope import apply_rope, rope_frequencies, rotate_rope
from tony_tpu.ops.losses import softmax_cross_entropy

__all__ = [
    "cache_decode_attention",
    "cache_prefill_attention",
    "grouped_cache_attention",
    "flash_attention",
    "flash_attention_lse",
    "grouped_matmul",
    "lightning_decode",
    "lightning_prefill",
    "sparse_decode_attention",
    "sparse_prefill_attention",
    "rms_norm",
    "apply_rope",
    "rope_frequencies",
    "rotate_rope",
    "softmax_cross_entropy",
]
