"""Model module ``lfm2_moe``: everything the benchmark knows of one
architecture, and the only place that knows it — LFM2-8B-A1B's decoder
(``model_type`` ``lfm2_moe``): pre-norm layers whose token mixer
``layer_types`` names. ``conv``: a gated short convolution, no attention at
all: ``[B | C | z] = u W_in`` ([d, 3d]), ``g = B * z``, a causal depthwise
convolution of ``conv_L_cache`` taps over g, times C, through ``W_out``
([d, d]); no bias (``conv_bias`` false). ``full_attention``: grouped-query
softmax attention, ``num_attention_heads`` heads of ``hidden_size /
num_attention_heads`` dims over ``num_key_value_heads`` KV heads, an RMSNorm
with a learnt weight over each head's dims of q and of k, then rope over
all of them. The first ``num_dense_layers`` layers have a dense SwiGLU of
width ``intermediate_size``; the others ``num_experts`` SwiGLU experts of
width ``moe_intermediate_size`` behind a sigmoid router with a selection
bias (``use_expert_bias``), ``num_experts_per_tok`` a token, the chosen
scores normalised (``norm_topk_prob``), times ``routed_scaling_factor``.
The head is the embedding's transpose. A configuration names it with
``"model": "lfm2_moe"``. Never the system under test: pure functions of the
configuration's dict. Imports no jax until a function needs it.

The benchmark's layout of a layer: input_norm, post_norm [d] (every
layer); conv_in_proj [d, 3d], conv_weight [d, L], conv_out_proj [d, d]
(conv layers); q_proj [d, H, D], k_proj, v_proj [d, Hkv, D], o_proj
[H, D, d], q_norm, k_norm [D] (attention layers); gate_proj, up_proj
[d, F0], down_proj [F0, d] (dense layers); router [d, E], router_bias [E],
experts_gate / experts_up [E, d, F], experts_down [E, F, d] (expert
layers); and embed [V, d], final_norm [d].

Names of the program this file depends on: ``TransformerConfig`` (fields
``vocab_size``, ``d_model``, ``n_layers``, ``n_heads``, ``head_dim``,
``n_kv_heads``, ``rope_theta``, ``rms_eps``, ``attn_kinds`` with the kinds
``full`` and ``conv``, ``conv_kernel``, ``qk_norm``, ``n_dense_layers``,
``dense_d_ff``, ``d_ff``, ``n_experts``, ``expert_top_k``,
``router_scoring``, ``router_bias``, ``tie_embeddings``, and what a job
passes through: ``max_seq``, ``dtype``), and the parameter tree of a
layered configuration: ``embed``, ``final_norm`` (no ``unembed``: the head
is tied) and ``layers.<kind>_<mlp kind>.{ln1,ln2}`` with ``{in_proj,
conv_w,out_proj}`` (conv) or ``{wq,wk,wv,wo,q_norm,k_norm}`` (full) and
``{w_gate,w_up,w_down}`` or ``{router,router_bias,w_gate,w_up,w_down}``,
each stacked over the kind's layers."""

from __future__ import annotations

KIND = {"conv": "conv", "full_attention": "full"}
MIXER_LEAVES = {
    "conv": {"in_proj": "conv_in_proj", "conv_w": "conv_weight",
             "out_proj": "conv_out_proj"},
    "full": {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
             "q_norm": "q_norm", "k_norm": "k_norm"},
}
COMMON_LEAVES = {"ln1": "input_norm", "ln2": "post_norm"}
MLP_LEAVES = {
    "dense": {"w_gate": "gate_proj", "w_up": "up_proj",
              "w_down": "down_proj"},
    "moe": {"router": "router", "router_bias": "router_bias",
            "w_gate": "experts_gate", "w_up": "experts_up",
            "w_down": "experts_down"},
}
PROGRAM_TOP_NAMES = {"embed": "embed", "final_norm": "final_norm"}
# The selection bias a thousandth (the configuration's ``assumed``, and
# ``mimo_v2_flash``'s reason: a seeded router is even in expectation and
# the stand-in for a trained bias must not unbalance it).
ROUTER_BIAS_SCALE = 0.001


# -- what the configuration's keys say ---------------------------------------
def layer_kinds(cfg: dict) -> list:
    """(mixer kind, mlp kind) of the layers kept, in the program's names."""
    n, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    return [(KIND[t], "dense" if i < dense else "moe")
            for i, t in enumerate(cfg["layer_types"][:n])]


def layers_of(cfg: dict, attn: str | None = None,
              mlp: str | None = None) -> tuple:
    return tuple(i for i, (a, m) in enumerate(layer_kinds(cfg))
                 if attn in (None, a) and mlp in (None, m))


def model_dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], h=h, dh=cfg["hidden_size"] // h,
                hkv=cfg["num_key_value_heads"], f0=cfg["intermediate_size"],
                f=cfg["moe_intermediate_size"], e=cfg["num_experts"],
                k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
                taps=cfg["conv_L_cache"])


# -- the program's model configuration ---------------------------------------
def program_config(cfg: dict, run: dict, **sizes):
    from tony_tpu.models import TransformerConfig

    kinds = layer_kinds(cfg)
    if cfg["conv_bias"] or not cfg["norm_topk_prob"] \
            or not cfg["use_expert_bias"] \
            or cfg["routed_scaling_factor"] != 1 \
            or len(cfg["layer_types"]) < cfg["num_hidden_layers"]:
        raise ValueError("a key of this configuration asks for what "
                         "neither the program nor the reference has")
    m = model_dims(cfg)
    return TransformerConfig(
        vocab_size=m["v"], d_model=m["d"], n_layers=len(kinds),
        n_heads=m["h"], head_dim=m["dh"], n_kv_heads=m["hkv"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["norm_eps"],
        attn_kinds=tuple(a for a, _ in kinds), conv_kernel=m["taps"],
        qk_norm=True, n_dense_layers=cfg["num_dense_layers"],
        dense_d_ff=m["f0"], d_ff=m["f"], n_experts=m["e"],
        expert_top_k=m["k"], router_scoring="sigmoid", router_bias=True,
        tie_embeddings=True, **sizes)


# -- the leaf table ----------------------------------------------------------
def leaf_table(cfg: dict) -> dict:
    """Leaf name -> ``weights.Leaf``; ``layers`` the layers that carry
    it: every layer, the conv or the attention layers, the dense or the
    expert layers. A tap of the convolution is drawn at 1 / sqrt(L), so
    that the operator's result has the variance of its input's gate. The
    embedding is drawn at the scale of a HEAD, 1 / sqrt(d): the head is
    tied to it, and at an embedding's usual unit variance the residual
    stream's own token stands 2,048 / rms(x) = 380 above logits of std 45,
    so that greedy decoding repeats its input whatever the layers compute
    and every gap of ``correct`` reads 0.0, a control's too (found on the
    chip, PR 43: the configuration's ``assumed.embedding_scale``)."""
    from yardstick.weights import Leaf, check_table

    m = model_dims(cfg)
    d, h, dh, hkv, e = m["d"], m["h"], m["dh"], m["hkv"], m["e"]
    every = layers_of(cfg)
    conv, full = layers_of(cfg, "conv"), layers_of(cfg, "full")
    dense, moe = layers_of(cfg, mlp="dense"), layers_of(cfg, mlp="moe")
    return check_table({
        "input_norm": Leaf((d,), norm=True, layers=every),
        "post_norm": Leaf((d,), norm=True, layers=every),
        "conv_in_proj": Leaf((d, 3 * d), d ** -0.5, layers=conv),
        "conv_weight": Leaf((d, m["taps"]), m["taps"] ** -0.5, layers=conv),
        "conv_out_proj": Leaf((d, d), d ** -0.5, layers=conv),
        "q_proj": Leaf((d, h, dh), d ** -0.5, layers=full),
        "k_proj": Leaf((d, hkv, dh), d ** -0.5, layers=full),
        "v_proj": Leaf((d, hkv, dh), d ** -0.5, layers=full),
        "o_proj": Leaf((h, dh, d), (h * dh) ** -0.5, layers=full),
        "q_norm": Leaf((dh,), norm=True, layers=full),
        "k_norm": Leaf((dh,), norm=True, layers=full),
        "gate_proj": Leaf((d, m["f0"]), d ** -0.5, layers=dense),
        "up_proj": Leaf((d, m["f0"]), d ** -0.5, layers=dense),
        "down_proj": Leaf((m["f0"], d), m["f0"] ** -0.5, layers=dense),
        "router": Leaf((d, e), d ** -0.5, layers=moe),
        "router_bias": Leaf((e,), ROUTER_BIAS_SCALE, layers=moe),
        "experts_gate": Leaf((e, d, m["f"]), d ** -0.5, layers=moe),
        "experts_up": Leaf((e, d, m["f"]), d ** -0.5, layers=moe),
        "experts_down": Leaf((e, m["f"], d), m["f"] ** -0.5, layers=moe),
        "embed": Leaf((m["v"], d), d ** -0.5),
        "final_norm": Leaf((d,), norm=True),
    })


def group_leaves(group: str) -> dict:
    """Program leaf -> the benchmark's, for one group of the program's
    tree (``<mixer kind>_<mlp kind>``)."""
    attn, mlp = group.split("_")
    return {**COMMON_LEAVES, **MIXER_LEAVES[attn], **MLP_LEAVES[mlp]}


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
    for i, (a, m) in enumerate(layer_kinds(cfg)):
        groups.setdefault(f"{a}_{m}", []).append(i)
    for group, members in groups.items():
        at = jnp.asarray(members)
        tree["layers"][group] = {
            prog: jax.vmap(lambda l, n=ours: weights.leaf(
                key, table, n, l, dtype))(at)
            for prog, ours in group_leaves(group).items()}
    return tree


def leaf_norms(tree) -> dict:
    """||leaf|| under the benchmark's leaf names, from a program tree (a
    leaf that several groups carry: over all of them)."""
    import jax.numpy as jnp

    squares: dict = {ours: jnp.sum(jnp.square(tree[prog].astype(jnp.float32)))
                     for prog, ours in PROGRAM_TOP_NAMES.items()}
    for group, leaves in tree["layers"].items():
        for prog, ours in group_leaves(group).items():
            squares[ours] = squares.get(ours, 0.0) + jnp.sum(
                jnp.square(leaves[prog].astype(jnp.float32)))
    return {k: jnp.sqrt(v) for k, v in squares.items()}


# -- the counts: operations and bytes the algorithm NEEDS --------------------
def _mixer_params(cfg: dict, attn: str) -> int:
    """A layer's operator: the conv's two matrices and its taps, or q, o,
    k, v and the two head norms."""
    m = model_dims(cfg)
    if attn == "conv":
        return 4 * m["d"] * m["d"] + m["d"] * m["taps"]
    return (2 * m["d"] * m["h"] * m["dh"] + 2 * m["d"] * m["hkv"] * m["dh"]
            + 2 * m["dh"])


def _mixer_matmul_params(cfg: dict, attn: str) -> int:
    m = model_dims(cfg)
    small = m["d"] * m["taps"] if attn == "conv" else 2 * m["dh"]
    return _mixer_params(cfg, attn) - small


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_fixed_params(cfg: dict, attn: str, mlp: str) -> int:
    """A layer's parameters outside its experts: the operator, the two
    norms, and the dense MLP or the router with its bias."""
    m = model_dims(cfg)
    n = _mixer_params(cfg, attn) + 2 * m["d"]
    if mlp == "dense":
        return n + 3 * m["d"] * m["f0"]
    return n + m["d"] * m["e"] + m["e"]


def params_total(cfg: dict) -> int:
    """Every parameter stored here: all experts of the layers kept, the
    embedding once (the head is tied to it), the final norm."""
    m = model_dims(cfg)
    layers = sum(
        _layer_fixed_params(cfg, a, mlp)
        + (m["e"] * _expert_params(cfg) if mlp == "moe" else 0)
        for a, mlp in layer_kinds(cfg))
    return layers + m["v"] * m["d"] + m["d"]


def matmul_params_per_token(cfg: dict) -> int:
    """ACTIVE matmul parameters of one token: the operator's matrices, the
    dense MLP or the router and the ``num_experts_per_tok`` experts it
    uses, the head."""
    m = model_dims(cfg)
    total = m["d"] * m["v"]
    for a, mlp in layer_kinds(cfg):
        total += _mixer_matmul_params(cfg, a)
        total += (3 * m["d"] * m["f0"] if mlp == "dense" else
                  m["d"] * m["e"] + m["k"] * _expert_params(cfg))
    return total


def experts_touched(cfg: dict, tokens: float) -> float:
    """Experts that ``tokens`` tokens of a uniform router reach in one
    layer: E x (1 - (1 - k/E)^tokens) (all 32 at 128)."""
    m = model_dims(cfg)
    return m["e"] * (1.0 - (1.0 - m["k"] / m["e"]) ** tokens)


def weight_bytes(cfg: dict, active_slots: float, itemsize: int = 2) -> float:
    """Bytes of weights one decode iteration must stream: everything
    outside the experts once (the router and its bias are float32), the
    experts its slots reach, the final norm and the head, which IS the
    embedding matrix (its rows a slot gathers are counted with the
    iteration's rows)."""
    m = model_dims(cfg)
    total = (m["d"] * m["v"] + m["d"]) * itemsize
    for a, mlp in layer_kinds(cfg):
        total += _layer_fixed_params(cfg, a, mlp) * itemsize
        if mlp == "moe":
            total += (m["d"] * m["e"] + m["e"]) * (4 - itemsize)
            total += (experts_touched(cfg, active_slots)
                      * _expert_params(cfg) * itemsize)
    return total


def cache_attention_bytes(cfg: dict, live_positions: float,
                          active_slots: float, itemsize: int = 2) -> float:
    """K and V bytes the decode attention of one iteration needs: the live
    positions in every attention layer, at the heads' own 64 dims (what a
    row of the device's cache pads is not needed)."""
    m = model_dims(cfg)
    return (len(layers_of(cfg, "full")) * live_positions * m["hkv"]
            * 2 * m["dh"] * itemsize)


def conv_state_bytes(cfg: dict, active_slots: float,
                     itemsize: int = 2) -> float:
    """Bytes of conv state one decode iteration reads and writes: the
    decoding slots' ``conv_L_cache - 1`` rows of g in every conv layer,
    once each way."""
    m = model_dims(cfg)
    return (len(layers_of(cfg, "conv")) * active_slots
            * 2 * (m["taps"] - 1) * m["d"] * itemsize)


def expert_ffn_bytes(cfg: dict, active_slots: float,
                     itemsize: int = 2) -> float:
    """Bytes the expert products of one decode iteration need, over all
    expert layers: the touched experts' weights once, the pairs'
    activations in (d) and out (d)."""
    m = model_dims(cfg)
    pairs = active_slots * m["k"]
    per_layer = (experts_touched(cfg, active_slots) * _expert_params(cfg)
                 + 2 * pairs * m["d"]) * itemsize
    return len(layers_of(cfg, mlp="moe")) * per_layer


def decode_iter_bytes(cfg: dict, live_positions: float, active_slots: float,
                      itemsize: int = 2) -> float:
    """Bytes one decode iteration NEEDS: the weights (``weight_bytes``),
    K and V read (``cache_attention_bytes``), one new K and V row written
    per slot per attention layer, the conv state rows read and written
    (``conv_state_bytes``), one embedding row per slot."""
    m = model_dims(cfg)
    written = (len(layers_of(cfg, "full")) * active_slots * m["hkv"]
               * 2 * m["dh"] * itemsize)
    embed = active_slots * m["d"] * itemsize
    return (weight_bytes(cfg, active_slots, itemsize)
            + cache_attention_bytes(cfg, live_positions, active_slots,
                                    itemsize)
            + conv_state_bytes(cfg, active_slots, itemsize)
            + written + embed)


def decode_trace_shapes(cfg: dict, slots: int) -> dict:
    """Result shapes by which a traced run tells the decode program's
    kernels and products apart: the cache attention's [slots, H, 2 x D]
    (the cache holds two 64-wide KV heads a row and the kernel returns
    both halves), the two grouped expert products over slots x k pair
    rows, [pairs, 2F] and [pairs, d]."""
    m = model_dims(cfg)
    pairs = slots * m["k"]
    return {"cache_attention": [(slots, m["h"], 2 * m["dh"])],
            "expert_ffn": [(pairs, 2 * m["f"]), (pairs, m["d"])]}


def conv_operator_shapes(cfg: dict, slots: int) -> dict:
    """The decode program's operations that only the conv operator runs,
    by (dtype, result shape): ``in_proj``: the product [slots, 1, 3d];
    ``window``: the state's rows and the new gate side by side in float32,
    [slots, L, d], which the convolution reads; ``state``: the state's
    rows [slots, L - 1, d] (their re-layout in and out and the select that
    keeps a parked lane's). The gate and the ``out_proj`` product share
    their result shape [slots, d] with other layers' operations and are
    not here."""
    m = model_dims(cfg)
    return {"in_proj": ("bfloat16", (slots, 1, 3 * m["d"])),
            "window": ("float32", (slots, m["taps"], m["d"])),
            "state": ("bfloat16", (slots, m["taps"] - 1, m["d"]))}


def norm_calls_per_f32_norm(cfg: dict) -> float:
    """bfloat16 RMSNorm calls of one pass through the layers (each layer's
    input norm, a dense layer's post norm) for each float32 one (an expert
    layer's post norm, which feeds the float32 router): what
    ``yardstick/kernel_readers.expert_ffn`` subtracts by."""
    every, dense, moe = (len(layers_of(cfg)), len(layers_of(cfg, mlp="dense")),
                         len(layers_of(cfg, mlp="moe")))
    return (every + dense) / moe


def train_flops_per_token(cfg: dict, seq: int) -> float:
    raise NotImplementedError("lfm2_moe is served, not trained: the conv "
                              "layers have no backward")


def attention_call_cost(cfg: dict, kind: str, batch: int, seq: int,
                        tp: int = 1) -> dict:
    raise NotImplementedError("lfm2_moe runs no flash-attention call")
