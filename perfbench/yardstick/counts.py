"""Operations and bytes that the algorithm NEEDS, from shapes — the
benchmark's own count, kept apart from the program's. Recomputed
operations are never counted. Pure Python."""

from __future__ import annotations


def model_dims(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], f=cfg["intermediate_size"],
        h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        dh=cfg["head_dim"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"],
    )


def layer_matmul_params(cfg: dict) -> int:
    """Parameters of one decoder layer that a token multiplies: q, k, v, o
    and the three SwiGLU matrices (the two norm vectors are not matmuls)."""
    m = model_dims(cfg)
    attn = m["d"] * m["h"] * m["dh"] * 2 + m["d"] * m["hkv"] * m["dh"] * 2
    return attn + 3 * m["d"] * m["f"]


def params_total(cfg: dict) -> int:
    """Every stored parameter: layers with their norms, embedding, final
    norm, untied head."""
    m = model_dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * m["d"]
    return m["layers"] * per_layer + 2 * m["v"] * m["d"] + m["d"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one trained token: 6 per matmul parameter
    (layers and the output head; the embedding LOOKUP is a gather and
    costs no matmul), plus causal attention 6·L·S·H·Dh per token
    (= 6·L·B·S²·H·Dh a batch: QK^T and PV over the causal half, backward
    counted as twice forward). Recomputation under remat is not counted."""
    m = model_dims(cfg)
    matmul = m["layers"] * layer_matmul_params(cfg) + m["d"] * m["v"]
    attention = 6 * m["layers"] * seq * m["h"] * m["dh"]
    return 6.0 * matmul + attention


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


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of weights one decode iteration must stream once: every layer
    matrix and norm, the final norm and the output head. The embedding
    table is gathered (one row a slot), not streamed."""
    m = model_dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * m["d"]
    return (m["layers"] * per_layer + m["d"] * m["v"] + m["d"]) * itemsize


def decode_iter_bytes(cfg: dict, live_positions: int, active_slots: int,
                      itemsize: int = 2) -> int:
    """Bytes one decode iteration NEEDS: the weights once, K and V of the
    live positions of the active slots read once, one new K and V row
    written per active slot per layer, one embedding row per slot."""
    m = model_dims(cfg)
    kv_row = m["hkv"] * m["dh"] * itemsize
    read_kv = 2 * m["layers"] * live_positions * kv_row
    write_kv = 2 * m["layers"] * active_slots * kv_row
    embed = active_slots * m["d"] * itemsize
    return weight_bytes(cfg, itemsize) + read_kv + write_kv + embed
