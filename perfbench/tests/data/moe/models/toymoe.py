"""Model module ``toymoe``: the mixture-of-experts trunk the program trains
and serves today, as a toy for CPU rehearsals and tests — the proof that an
ARCHITECTURE is added as files. Attention as in ``dense``; every layer's
MLP is ``num_local_experts`` SwiGLU experts behind a softmax router: top-k
on the probabilities, the chosen weights normalised, no shared expert, no
capacity (every token reaches its top-k experts).

Leaves besides the attention's and the norms': router [d, E] (the engine
keeps it in float32), experts_gate / experts_up [E, d, F], experts_down
[E, F, d].

Names of the program this file depends on: ``TransformerConfig`` (as
``dense``, plus ``n_experts``, ``expert_top_k``, ``capacity_factor``,
``moe_balance_coef``, ``moe_zloss_coef``) and the parameter tree's leaf
names (as ``dense``, plus ``layers.router``; ``w_gate`` / ``w_up`` /
``w_down`` stacked over experts)."""

from __future__ import annotations

from yardstick import counts

PROGRAM_LAYER_NAMES = {
    "ln1": "input_norm", "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
    "wo": "o_proj", "ln2": "post_norm", "router": "router",
    "w_gate": "experts_gate", "w_up": "experts_up", "w_down": "experts_down",
}
PROGRAM_TOP_NAMES = {"embed": "embed", "final_norm": "final_norm",
                     "unembed": "lm_head"}


def program_config(cfg: dict, run: dict, **sizes):
    """``capacity_factor`` = E / k (and a hair) gives every expert room for
    every token, so the program drops none, as the reference drops none;
    the router's two auxiliary coefficients are the configuration's, which
    are what the reference's loss adds."""
    from tony_tpu.models import TransformerConfig

    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        n_kv_heads=cfg["num_key_value_heads"], n_experts=e, expert_top_k=k,
        capacity_factor=e / k + 1e-3,
        moe_balance_coef=cfg["router_aux_loss_coef"],
        moe_zloss_coef=cfg["router_z_loss_coef"], **sizes)


def leaf_table(cfg: dict) -> dict:
    from yardstick.weights import Leaf, check_table

    d, f, e = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_local_experts"])
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
        "router": Leaf((d, e), d ** -0.5, layers=every),
        "experts_gate": Leaf((e, d, f), d ** -0.5, layers=every),
        "experts_up": Leaf((e, d, f), d ** -0.5, layers=every),
        "experts_down": Leaf((e, f, d), f ** -0.5, layers=every),
        "embed": Leaf((v, d), 1.0),
        "final_norm": Leaf((d,), norm=True),
        "lm_head": Leaf((d, v), d ** -0.5),
    })


def program_params(key, cfg: dict, dtype):
    from yardstick import weights

    table = leaf_table(cfg)
    layers = weights.stacked_layers(key, table, dtype)
    top = weights.top_tree(key, table, dtype)
    tree = {prog: top[ours] for prog, ours in PROGRAM_TOP_NAMES.items()}
    tree["layers"] = {prog: layers[ours]
                      for prog, ours in PROGRAM_LAYER_NAMES.items()}
    return tree


def leaf_norms(tree) -> dict:
    import jax.numpy as jnp

    out = {ours: tree[prog] for prog, ours in PROGRAM_TOP_NAMES.items()}
    out.update({ours: tree["layers"][prog]
                for prog, ours in PROGRAM_LAYER_NAMES.items()})
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in out.items()}


# -- the counts --------------------------------------------------------------
def _attention_params(cfg: dict) -> int:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * dh * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_local_experts"]


def params_total(cfg: dict) -> int:
    """Every stored parameter: all experts of every layer."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    per_layer = (_attention_params(cfg) + _router_params(cfg) + 2 * d
                 + cfg["num_local_experts"] * _expert_params(cfg))
    return cfg["num_hidden_layers"] * per_layer + 2 * v * d + d


def matmul_params_per_token(cfg: dict) -> int:
    """ACTIVE matmul parameters: attention, the router, the k experts a
    token reaches, and the output head."""
    per_layer = (_attention_params(cfg) + _router_params(cfg)
                 + cfg["num_experts_per_tok"] * _expert_params(cfg))
    return (cfg["num_hidden_layers"] * per_layer
            + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    attention = (6 * cfg["num_hidden_layers"] * seq
                 * cfg["num_attention_heads"] * cfg["head_dim"])
    return 6.0 * matmul_params_per_token(cfg) + attention


def attention_call_cost(cfg: dict, kind: str, batch: int, seq: int,
                        tp: int = 1) -> dict:
    return counts.flash_call_cost(
        kind, batch, seq, cfg["num_attention_heads"] // tp,
        max(cfg["num_key_value_heads"] // tp, 1), cfg["head_dim"])


def weight_bytes(cfg: dict, active_slots: int, itemsize: int = 2) -> int:
    """Bytes of weights one decode iteration must stream: per layer the
    attention, the norms, the float32 router and the experts its slots can
    reach — at most min(E, slots x k), the most the iteration can need —
    then the final norm and the head."""
    d = cfg["hidden_size"]
    reached = min(cfg["num_local_experts"],
                  active_slots * cfg["num_experts_per_tok"])
    per_layer = ((_attention_params(cfg) + 2 * d
                  + reached * _expert_params(cfg)) * itemsize
                 + _router_params(cfg) * 4)
    return (cfg["num_hidden_layers"] * per_layer
            + (d * cfg["vocab_size"] + d) * itemsize)


def decode_iter_bytes(cfg: dict, live_positions: int, active_slots: int,
                      itemsize: int = 2) -> int:
    layers = cfg["num_hidden_layers"]
    kv_row = cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    kv = 2 * layers * (live_positions + active_slots) * kv_row
    embed = active_slots * cfg["hidden_size"] * itemsize
    return weight_bytes(cfg, active_slots, itemsize) + kv + embed
