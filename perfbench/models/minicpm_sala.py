"""Model module ``minicpm_sala``: everything the benchmark knows of one
architecture, and the only place that knows it — MiniCPM-SALA's decoder
(``model_type`` ``minicpm_sala``): pre-norm layers of TWO mixer kinds in the
order ``mixer_types`` gives. ``lightning-attn``: lightning (linear)
attention, ``lightning_nh`` heads of ``lightning_head_dim``, rope, a float32
recurrent state per head that decays by the head's slope, an RMSNorm over
all heads' dims of the result and a sigmoid output gate. ``minicpm4``:
InfLLM-V2 block-sparse softmax attention, ``num_key_value_heads`` KV heads,
no rope, a sigmoid output gate; past ``dense_len`` positions a query reads
the first block, the blocks of its last ``window_size`` positions and the
``topk`` blocks its KV group's heads score highest against the means of the
keys over ``kernel_size`` positions every ``kernel_stride``. Both: RMSNorm
with a learnt weight over each head's dims of q and of k (``qk_norm``), a
dense SwiGLU, and the family's muP scales (``scale_emb`` on the embedding,
``scale_depth / sqrt(L)`` on every sub-block's result with L the PUBLISHED
depth, the head reading norm(x) / (``hidden_size`` / ``dim_model_base``)).
A configuration names it with ``"model": "minicpm_sala"``. Never the system
under test: pure functions of the configuration's dict. Imports no jax
until a function needs it.

What ``config.json`` does not give, the configuration states under
``sparse_config`` and ``assumed`` (the family's published InfLLM-V2 sizes;
the Lightning Attention slopes ``exp(-2^(-8h/H))``).

The benchmark's layout of a layer: input_norm, post_norm [d]; q_proj [d, H,
D], o_proj [H, D, d], q_norm [D], o_gate [d, H * D], gate_proj, up_proj
[d, F], down_proj [F, d] (every layer); k_proj, v_proj [d, Hkv, D],
sparse_k_norm [D] (minicpm4 layers); lightning_k_proj, lightning_v_proj
[d, H, D], k_norm [D], lightning_o_norm [H * D] (lightning layers); and
embed [V, d], final_norm [d], lm_head [d, V].

Names of the program this file depends on: ``TransformerConfig`` (fields
``vocab_size``, ``d_model``, ``n_layers``, ``n_heads``, ``head_dim``,
``n_kv_heads``, ``d_ff``, ``rope_theta``, ``rms_eps``, ``attn_kinds`` with
the kinds ``sparse`` and ``linear``, ``qk_norm``, ``no_rope_kinds``,
``gated_kinds``, ``out_norm_kinds``, ``embed_scale``, ``residual_scale``,
``logit_scale``, ``sparse_kernel``, ``sparse_stride``, ``sparse_block``,
``sparse_topk``, ``sparse_init_blocks``, ``sparse_window``,
``sparse_dense_len``, and what a job passes through: ``max_seq``,
``dtype``), and the parameter tree of a layered configuration: ``embed``,
``final_norm``, ``unembed`` and ``layers.<attention kind>_dense.{ln1,wq,wk,
wv,wo,ln2,q_norm,k_norm,w_ogate,o_norm,w_gate,w_up,w_down}``, each stacked
over the kind's layers."""

from __future__ import annotations

import math

KIND = {"minicpm4": "sparse", "lightning-attn": "linear"}
COMMON_LEAVES = {"ln1": "input_norm", "wq": "q_proj", "wo": "o_proj",
                 "ln2": "post_norm", "q_norm": "q_norm",
                 "w_ogate": "o_gate", "w_gate": "gate_proj",
                 "w_up": "up_proj", "w_down": "down_proj"}
ATTENTION_LEAVES = {
    "sparse": {"wk": "k_proj", "wv": "v_proj", "k_norm": "sparse_k_norm"},
    "linear": {"wk": "lightning_k_proj", "wv": "lightning_v_proj",
               "k_norm": "k_norm", "o_norm": "lightning_o_norm"},
}
PROGRAM_TOP_NAMES = {"embed": "embed", "final_norm": "final_norm",
                     "unembed": "lm_head"}


# -- what the configuration's keys say ---------------------------------------
def layer_kinds(cfg: dict) -> list:
    """The program's attention kind of every layer kept."""
    return [KIND[m] for m in cfg["mixer_types"][:cfg["num_hidden_layers"]]]


def layers_of(cfg: dict, kind: str | None = None) -> tuple:
    return tuple(i for i, k in enumerate(layer_kinds(cfg))
                 if kind in (None, k))


def published_depth(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_hidden_layers",
                                        cfg["num_hidden_layers"])


def model_dims(cfg: dict) -> dict:
    if (cfg["lightning_nh"] != cfg["num_attention_heads"]
            or cfg["lightning_nkv"] != cfg["lightning_nh"]
            or cfg["lightning_head_dim"] != cfg["head_dim"]):
        raise ValueError("the program runs the lightning layers at the "
                         "attention layers' head count and width, ungrouped")
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                dh=cfg["head_dim"], hkv=cfg["num_key_value_heads"],
                f=cfg["intermediate_size"], v=cfg["vocab_size"])


def residual_scale(cfg: dict) -> float:
    return cfg["scale_depth"] / math.sqrt(published_depth(cfg))


def logit_scale(cfg: dict) -> float:
    return cfg["dim_model_base"] / cfg["hidden_size"]


def sparse_sizes(cfg: dict) -> dict:
    return dict(cfg["sparse_config"])


# -- the program's model configuration ---------------------------------------
def program_config(cfg: dict, run: dict, **sizes):
    from tony_tpu.models import TransformerConfig

    if (cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["attn_use_rope"] or not cfg["lightning_use_rope"]
            or not cfg["qk_norm"] or not cfg["use_output_gate"]
            or not cfg["use_output_norm"] or not cfg["attn_use_output_gate"]
            or cfg["lightning_scale"] != "1/sqrt(d)"
            or cfg["hidden_act"] != "silu"):
        raise ValueError("a key of this configuration asks for what "
                         "neither the program nor the reference has")
    m, sp = model_dims(cfg), sparse_sizes(cfg)
    return TransformerConfig(
        vocab_size=m["v"], d_model=m["d"], n_layers=cfg["num_hidden_layers"],
        n_heads=m["h"], head_dim=m["dh"], n_kv_heads=m["hkv"], d_ff=m["f"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        attn_kinds=tuple(layer_kinds(cfg)), qk_norm=True,
        no_rope_kinds=("sparse",), gated_kinds=("sparse", "linear"),
        out_norm_kinds=("linear",), embed_scale=float(cfg["scale_emb"]),
        residual_scale=residual_scale(cfg), logit_scale=logit_scale(cfg),
        sparse_kernel=sp["kernel_size"], sparse_stride=sp["kernel_stride"],
        sparse_block=sp["block_size"], sparse_topk=sp["topk"],
        sparse_init_blocks=sp["init_blocks"],
        sparse_window=sp["window_size"], sparse_dense_len=sp["dense_len"],
        **sizes)


# -- the leaf table ----------------------------------------------------------
def leaf_table(cfg: dict) -> dict:
    """Leaf name -> ``weights.Leaf``; ``layers`` the layers that carry
    it: every layer, the minicpm4 or the lightning layers.

    A minicpm4 layer's seeded keys and values are given what a trained
    layer's have and unit-variance draws lack (the configuration's
    ``seeded_weights`` states both numbers and what was read for them): its
    k_norm weight is N(0, std^2) a dim, so a query's scores against the
    keys have that std and the softmax over ten thousand keys is PEAKED
    (at a std of 1 it is nearly flat, and the layer returns the same small
    average of v whichever blocks it reads), and its v_proj is drawn
    ``sparse_v_gain`` times larger, so that the three minicpm4 layers
    weigh in the logits as the nine lightning layers do. With both, which
    blocks a query reads decides what it returns, and the comparison that
    decides ``correct`` sees the selection."""
    from yardstick.weights import Leaf, check_table

    m, seeded = model_dims(cfg), cfg["seeded_weights"]
    d, h, dh, hkv, f = m["d"], m["h"], m["dh"], m["hkv"], m["f"]
    every = layers_of(cfg)
    sparse, linear = layers_of(cfg, "sparse"), layers_of(cfg, "linear")
    return check_table({
        "input_norm": Leaf((d,), norm=True, layers=every),
        "q_proj": Leaf((d, h, dh), d ** -0.5, layers=every),
        "o_proj": Leaf((h, dh, d), (h * dh) ** -0.5, layers=every),
        "post_norm": Leaf((d,), norm=True, layers=every),
        "q_norm": Leaf((dh,), norm=True, layers=every),
        "k_norm": Leaf((dh,), norm=True, layers=linear),
        "sparse_k_norm": Leaf((dh,), float(seeded["sparse_k_norm_std"]),
                              layers=sparse),
        "o_gate": Leaf((d, h * dh), d ** -0.5, layers=every),
        "gate_proj": Leaf((d, f), d ** -0.5, layers=every),
        "up_proj": Leaf((d, f), d ** -0.5, layers=every),
        "down_proj": Leaf((f, d), f ** -0.5, layers=every),
        "k_proj": Leaf((d, hkv, dh), d ** -0.5, layers=sparse),
        "v_proj": Leaf((d, hkv, dh), seeded["sparse_v_gain"] * d ** -0.5,
                       layers=sparse),
        "lightning_k_proj": Leaf((d, h, dh), d ** -0.5, layers=linear),
        "lightning_v_proj": Leaf((d, h, dh), d ** -0.5, layers=linear),
        "lightning_o_norm": Leaf((h * dh,), norm=True, layers=linear),
        "embed": Leaf((m["v"], d), 1.0),
        "final_norm": Leaf((d,), norm=True),
        "lm_head": Leaf((d, m["v"]), d ** -0.5),
    })


def group_leaves(group: str) -> dict:
    """Program leaf -> the benchmark's, for one group of the program's
    tree (``<attention kind>_dense``)."""
    return {**COMMON_LEAVES, **ATTENTION_LEAVES[group.split("_")[0]]}


def program_params(key, cfg: dict, dtype):
    """The benchmark's seeded weights in the program's parameter tree:
    groups of stacks by layer kind, each leaf stacked over the kind's own
    layers (a leaf's bits follow from its name and its layer)."""
    import jax
    import jax.numpy as jnp

    from yardstick import weights

    table = leaf_table(cfg)
    top = weights.top_tree(key, table, dtype)
    tree = {prog: top[ours] for prog, ours in PROGRAM_TOP_NAMES.items()}
    tree["layers"] = {}
    groups: dict = {}
    for i, kind in enumerate(layer_kinds(cfg)):
        groups.setdefault(f"{kind}_dense", []).append(i)
    for group, members in groups.items():
        at = jnp.asarray(members)
        tree["layers"][group] = {
            prog: jax.vmap(lambda l, n=ours: weights.leaf(
                key, table, n, l, dtype))(at)
            for prog, ours in group_leaves(group).items()}
    return tree


def leaf_norms(tree) -> dict:
    """||leaf|| under the benchmark's leaf names, from a program tree (a
    leaf that both groups carry: over both)."""
    import jax.numpy as jnp

    squares: dict = {ours: jnp.sum(jnp.square(tree[prog].astype(jnp.float32)))
                     for prog, ours in PROGRAM_TOP_NAMES.items()}
    for group, leaves in tree["layers"].items():
        for prog, ours in group_leaves(group).items():
            squares[ours] = squares.get(ours, 0.0) + jnp.sum(
                jnp.square(leaves[prog].astype(jnp.float32)))
    return {k: jnp.sqrt(v) for k, v in squares.items()}


# -- the counts: operations and bytes the algorithm NEEDS --------------------
def _layer_params(cfg: dict, kind: str) -> int:
    """q, o and the gate at H x D; k and v at the kind's head count; the
    SwiGLU; the four norms (and a lightning layer's output norm)."""
    m = model_dims(cfg)
    wide, hkv = m["d"] * m["h"] * m["dh"], (m["hkv"] if kind == "sparse"
                                            else m["h"])
    n = 3 * wide + 2 * m["d"] * hkv * m["dh"] + 3 * m["d"] * m["f"]
    n += 2 * m["d"] + 2 * m["dh"]
    return n + (m["h"] * m["dh"] if kind == "linear" else 0)


def params_total(cfg: dict) -> int:
    m = model_dims(cfg)
    return (sum(_layer_params(cfg, k) for k in layer_kinds(cfg))
            + 2 * m["v"] * m["d"] + m["d"])


def matmul_params_per_token(cfg: dict) -> int:
    m = model_dims(cfg)
    small = 2 * m["d"] + 2 * m["dh"]
    return (sum(_layer_params(cfg, k) - small
                - (m["h"] * m["dh"] if k == "linear" else 0)
                for k in layer_kinds(cfg)) + m["d"] * m["v"])


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of weights one decode iteration must stream: every layer,
    the final norm and the head. The embedding table is gathered."""
    m = model_dims(cfg)
    return (sum(_layer_params(cfg, k) for k in layer_kinds(cfg))
            + m["d"] * m["v"] + m["d"]) * itemsize


def selected_keys(cfg: dict, position: float) -> float:
    """Keys one KV group of a query at ``position`` attends in a sparse
    layer: every key before ``dense_len``; else the first blocks, the
    blocks from the one that holds position - window + 1 on, up to the
    query, and ``topk`` blocks of the rest."""
    sp = sparse_sizes(cfg)
    if position < sp["dense_len"]:
        return position + 1
    block = sp["block_size"]
    first = max(position - (sp["window_size"] - 1), 0) // block
    rest = max(first - sp["init_blocks"], 0)
    return (min(sp["init_blocks"], first) * block
            + position + 1 - first * block
            + min(rest, sp["topk"]) * block)


def sparse_attention_bytes(cfg: dict, live_positions: float,
                           active_slots: float, itemsize: int = 2) -> float:
    """Bytes the selection and the sparse attention of ONE decode
    iteration need, over the sparse layers: per slot and KV head the
    complete K^c rows (float32) of its live positions, and K and V of the
    selected blocks' keys, each slot taken at the mean live position."""
    m, sp = model_dims(cfg), sparse_sizes(cfg)
    if active_slots <= 0:
        return 0.0
    mean = live_positions / active_slots
    kc_rows = max(mean - sp["kernel_size"], 0) / sp["kernel_stride"] + 1
    per_head = (kc_rows * m["dh"] * 4
                + selected_keys(cfg, mean) * 2 * m["dh"] * itemsize)
    return len(layers_of(cfg, "sparse")) * active_slots * m["hkv"] * per_head


def state_bytes(cfg: dict, active_slots: float) -> float:
    """Bytes of recurrent state one decode iteration reads and writes:
    every live slot's float32 [H, D, D] in every lightning layer, once
    each way."""
    m = model_dims(cfg)
    return (len(layers_of(cfg, "linear")) * active_slots
            * 2 * m["h"] * m["dh"] * m["dh"] * 4)


def decode_iter_bytes(cfg: dict, live_positions: int, active_slots: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode iteration NEEDS: the weights once, the live slots'
    states read and written, K^c and the SELECTED keys' K and V of the
    live slots, the rows written (a K and a V row a sparse layer, the K^c
    rows a position touches), one embedding row per slot."""
    m, sp = model_dims(cfg), sparse_sizes(cfg)
    written = len(layers_of(cfg, "sparse")) * active_slots * m["hkv"] * (
        2 * m["dh"] * itemsize
        + 2 * (sp["kernel_size"] // sp["kernel_stride"]) * m["dh"] * 4)
    embed = active_slots * m["d"] * itemsize
    return (weight_bytes(cfg, itemsize) + state_bytes(cfg, active_slots)
            + sparse_attention_bytes(cfg, live_positions, active_slots,
                                     itemsize) + written + embed)


def kv_bytes_of_keys(cfg: dict, keys: float, itemsize: int = 2) -> float:
    """K and V bytes of ``keys`` keys of every KV head of one sparse layer
    and one slot."""
    m = model_dims(cfg)
    return m["hkv"] * keys * 2 * m["dh"] * itemsize


def lightning_call_cost(cfg: dict, kind: str, rows: float,
                        chunk: int = 0) -> dict:
    """FLOPs and bytes ONE call of a lightning kernel needs. ``decode``
    over ``rows`` live slots: the state read and written, q, k, v in, o
    out; the outer product and the read. ``prefill``: ``rows`` chunks of
    ``chunk`` positions: two [C, C, D] and two [C, D, D] products a head;
    q, k, v and the state in, o (float32) and the state out."""
    m = model_dims(cfg)
    h, d = m["h"], m["dh"]
    state = h * d * d * 4
    if kind == "decode":
        return {"flops": rows * h * 4.0 * d * d,
                "bytes": rows * (2.0 * state + 4 * h * d * 4)}
    if kind == "prefill":
        return {"flops": rows * h * (4.0 * chunk * chunk * d
                                     + 4.0 * chunk * d * d),
                "bytes": rows * (2.0 * state + 3 * chunk * h * d * 2
                                 + chunk * h * d * 4)}
    raise ValueError(f"unknown lightning call kind {kind!r}")


def sparse_decode_trace_name(cfg: dict, slots: int) -> str:
    """The trace's name of the sparse layers' decode attention kernel: its
    result [slots, Hkv, H / Hkv, D], which no other call of the two
    programs has."""
    m = model_dims(cfg)
    return (f"mosaic:bf16[{slots},{m['hkv']},{m['h'] // m['hkv']},"
            f"{m['dh']}]")


def train_flops_per_token(cfg: dict, seq: int) -> float:
    raise NotImplementedError("minicpm_sala is served, not trained: the "
                              "lightning and sparse layers have no backward")


def attention_call_cost(cfg: dict, kind: str, batch: int, seq: int,
                        tp: int = 1) -> dict:
    raise NotImplementedError("minicpm_sala runs no flash-attention call")
