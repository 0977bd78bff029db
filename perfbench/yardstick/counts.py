"""Operations and bytes that the algorithm NEEDS, from plain shapes — the
benchmark's own count, kept apart from the program's. Recomputed
operations are never counted. What follows from one architecture (its
parameters, the bytes a decode iteration streams, a trained token's
FLOPs) is counted by that architecture's model module,
``models/<model>.py``; here is what is no model's. Pure Python."""

from __future__ import annotations


def flash_call_cost(kind: str, batch: int, seq: int, heads: int,
                    kv_heads: int, dh: int, itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes one causal flash-attention call needs.
    ``fwd``: QK^T and PV over the causal half = 2·B·H·S²·Dh; reads q, k, v,
    writes o (+ the fp32 log-sum-exp row). The backward needs five
    matmuls over the causal half (s, dp, dv, dk, dq) = 5·B·H·S²·Dh; this
    program splits it in two kernels, ``dq`` (needs 3 of them, reads q k v
    do, writes dq) and ``dkv`` (needs 4, reads q k v do, writes dk dv) —
    the score recompute is needed once by the algorithm, so each kernel is
    charged what it cannot avoid alone and the pair is NOT charged the
    second recompute twice: dq 2·, dkv 3· (B·H·S²·Dh). K and V are read at
    the kv head count (GQA)."""
    unit = batch * heads * seq * seq * dh
    q_bytes = batch * seq * heads * dh * itemsize
    kv_bytes = batch * seq * kv_heads * dh * itemsize
    lse = batch * heads * seq * 4
    if kind == "fwd":
        return {"flops": 2.0 * unit,
                "bytes": 2 * q_bytes + 2 * kv_bytes + lse}
    if kind == "dq":
        return {"flops": 2.0 * unit,
                "bytes": 3 * q_bytes + 2 * kv_bytes + 2 * lse}
    if kind == "dkv":
        return {"flops": 3.0 * unit,
                "bytes": 2 * q_bytes + 4 * kv_bytes + 2 * lse}
    raise ValueError(f"unknown flash call kind {kind!r}")


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
