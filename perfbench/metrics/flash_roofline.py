"""Flash-attention Mosaic calls (forward, dq, dkv) against their roofline.
The trace gives the kernels of ops/attention.py no name of their own (they
are called "checkpoint" or "pallas_call" after the enclosing scope), so they
are told apart by their result shapes, [batch*heads, seq, head_dim] in bf16:
forward returns (out, f32 log-sum-exp), dq one array, dkv two."""
from yardstick.readers import flash_roofline

O = r"bf16\[\d+,\d+,\d+\]"
PATTERNS = {"fwd": rf"^mosaic:\({O},f32\[\d+,1,\d+\]\)$",
            "dq": rf"^mosaic:{O}$",
            "dkv": rf"^mosaic:\({O},{O}\)$"}


def read(run):
    return flash_roofline(run, PATTERNS)
