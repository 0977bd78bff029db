"""Model module ``dense``: everything the benchmark knows of one
architecture, and the only place that knows it — a pre-norm decoder of
uniform layers with grouped-query attention, rotary embedding, SwiGLU and
an untied head (Mistral's / Llama's). A configuration names it with
``"model": "dense"``. Never the system under test: pure functions of the
configuration's dict. Imports no jax until a function needs it (the
harness process reads the counts and must not hold the chip).

The benchmark's layout of a decoder layer (Mistral's names):
input_norm [d], q_proj [d, H, Dh], k_proj / v_proj [d, Hkv, Dh],
o_proj [H, Dh, d], post_norm [d], gate_proj / up_proj [d, F],
down_proj [F, d]; and embed [V, d], final_norm [d], lm_head [d, V].

Names of the program this file depends on: ``TransformerConfig`` (fields
``vocab_size``, ``d_model``, ``n_layers``, ``n_heads``, ``head_dim``,
``d_ff``, ``rope_theta``, ``n_kv_heads``, and what a job passes through:
``max_seq``, ``dtype``, ``remat``, ``remat_policy``), and the parameter
tree's leaf names (``embed``, ``layers.{ln1,wq,wk,wv,wo,ln2,w_gate,w_up,
w_down}``, ``final_norm``, ``unembed``)."""

from __future__ import annotations

from yardstick import counts

PROGRAM_LAYER_NAMES = {
    "ln1": "input_norm", "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
    "wo": "o_proj", "ln2": "post_norm", "w_gate": "gate_proj",
    "w_up": "up_proj", "w_down": "down_proj",
}
PROGRAM_TOP_NAMES = {"embed": "embed", "final_norm": "final_norm",
                     "unembed": "lm_head"}


# -- the program's model configuration ---------------------------------------
def program_config(cfg: dict, run: dict, **sizes):
    """The program's model configuration for its public entry points
    (``ServingEngine(weights, model_cfg, ...)``, ``make_train_step(
    model_cfg, mesh, ...)``). ``sizes``: what the job knows and the
    configuration's sizes do not say (``max_seq``, ``dtype``, remat)."""
    from tony_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        n_kv_heads=cfg["num_key_value_heads"], **sizes)


# -- the leaf table ----------------------------------------------------------
def leaf_table(cfg: dict) -> dict:
    """Leaf name -> ``weights.Leaf``: shape, scale, whether a norm, and the
    layers that carry it (every layer carries every layer leaf here)."""
    from yardstick.weights import Leaf, check_table

    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, v = cfg["head_dim"], cfg["vocab_size"]
    every = range(cfg["num_hidden_layers"])
    return check_table({
        "input_norm": Leaf((d,), norm=True, layers=every),
        "q_proj": Leaf((d, h, dh), d ** -0.5, layers=every),
        "k_proj": Leaf((d, hkv, dh), d ** -0.5, layers=every),
        "v_proj": Leaf((d, hkv, dh), d ** -0.5, layers=every),
        "o_proj": Leaf((h, dh, d), (h * dh) ** -0.5, layers=every),
        "post_norm": Leaf((d,), norm=True, layers=every),
        "gate_proj": Leaf((d, f), d ** -0.5, layers=every),
        "up_proj": Leaf((d, f), d ** -0.5, layers=every),
        "down_proj": Leaf((f, d), f ** -0.5, layers=every),
        "embed": Leaf((v, d), 1.0),
        "final_norm": Leaf((d,), norm=True),
        "lm_head": Leaf((d, v), d ** -0.5),
    })


def program_params(key, cfg: dict, dtype):
    """The benchmark's seeded weights in the program's parameter tree."""
    from yardstick import weights

    table = leaf_table(cfg)
    layers = weights.stacked_layers(key, table, dtype)
    top = weights.top_tree(key, table, dtype)
    tree = {prog: top[ours] for prog, ours in PROGRAM_TOP_NAMES.items()}
    tree["layers"] = {prog: layers[ours]
                      for prog, ours in PROGRAM_LAYER_NAMES.items()}
    return tree


def leaf_norms(tree) -> dict:
    """||leaf|| under the benchmark's leaf names, from a program tree."""
    import jax.numpy as jnp

    out = {ours: tree[prog] for prog, ours in PROGRAM_TOP_NAMES.items()}
    out.update({ours: tree["layers"][prog]
                for prog, ours in PROGRAM_LAYER_NAMES.items()})
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in out.items()}


# -- the counts: operations and bytes the algorithm NEEDS --------------------
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


def matmul_params_per_token(cfg: dict) -> int:
    """Matmul parameters one token multiplies: every layer's and the output
    head's (the embedding LOOKUP is a gather and costs no matmul)."""
    m = model_dims(cfg)
    return m["layers"] * layer_matmul_params(cfg) + m["d"] * m["v"]


def params_total(cfg: dict) -> int:
    """Every stored parameter: layers with their norms, embedding, final
    norm, untied head."""
    m = model_dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * m["d"]
    return m["layers"] * per_layer + 2 * m["v"] * m["d"] + m["d"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of one trained token: 6 per matmul parameter
    (layers and the output head), plus causal attention 6·L·S·H·Dh per
    token (= 6·L·B·S²·H·Dh a batch: QK^T and PV over the causal half,
    backward counted as twice forward). Recomputation under remat is not
    counted."""
    m = model_dims(cfg)
    attention = 6 * m["layers"] * seq * m["h"] * m["dh"]
    return 6.0 * matmul_params_per_token(cfg) + attention


def attention_call_cost(cfg: dict, kind: str, batch: int, seq: int,
                        tp: int = 1) -> dict:
    """FLOPs and HBM bytes of one causal flash-attention call (``kind``:
    ``fwd``, ``dq``, ``dkv``) on one device's shard: heads split over
    ``tp``, K and V at the kv head count."""
    m = model_dims(cfg)
    return counts.flash_call_cost(kind, batch, seq, m["h"] // tp,
                                  max(m["hkv"] // tp, 1), m["dh"])


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
