"""Model module ``mimo_v2_flash``: everything the benchmark knows of one
architecture, and the only place that knows it — MiMo-V2-Flash's decoder
(``model_type`` ``mimo_v2_flash``): pre-norm layers of TWO attention kinds
in the order ``hybrid_layer_pattern`` gives (0 full, 1 sliding window), q/k
heads of one width and v heads of another, rotary embedding on the leading
``partial_rotary_factor`` of a head, the value scaled, a learnt per-head
sink in the window layers' softmax; a dense SwiGLU in the layers
``moe_layer_freq`` marks 0 and, in the others, ``n_routed_experts`` SwiGLU
experts behind a sigmoid router with a selection bias (``noaux_tc``), top-k
normalised, no shared expert. A configuration names it with ``"model":
"mimo_v2_flash"``. Never the system under test: pure functions of the
configuration's dict. Imports no jax until a function needs it.

A configuration of this module states the chip's SHARE of a deployment
(the ``model-configs`` guide, section 4): ``n_routed_experts`` is the number
of experts HELD here, ``published.n_routed_experts`` the router's width,
``deployment.experts_first`` the first held expert; ``vocab_size`` the held
rows; ``num_hidden_layers`` the leading layers kept, the two per-layer
lists copied whole and read as far.

The benchmark's layout of a layer: input_norm, post_norm [d]; q_proj
[d, H, Dk], o_proj [H, Dv, d] (every layer); k_proj [d, Hkv, Dk], v_proj
[d, Hkv, Dv] (full layers); swa_k_proj, swa_v_proj at the window layers'
KV head count, swa_sink [H] (window layers); gate_proj, up_proj [d, F0],
down_proj [F0, d] (dense layers); router [d, E], router_bias [E],
experts_gate / experts_up [held, d, F], experts_down [held, F, d] (expert
layers); and embed [V, d], final_norm [d], lm_head [d, V].

Names of the program this file depends on: ``TransformerConfig`` (fields
``vocab_size``, ``d_model``, ``n_layers``, ``n_heads``, ``head_dim``,
``v_head_dim``, ``rotary_dim``, ``v_scale``, ``rms_eps``, ``n_kv_heads``,
``rope_theta``, ``attn_kinds``, ``window``, ``window_kv_heads``,
``window_rope_theta``, ``window_sink``, ``n_dense_layers``, ``dense_d_ff``,
``d_ff``, ``n_experts``, ``expert_top_k``, ``router_scoring``,
``router_bias``, ``experts_held``, its property ``layer_groups``, and what
a job passes through: ``max_seq``, ``dtype``), and the parameter tree of a
layered configuration: ``embed``, ``final_norm``, ``unembed`` and
``layers.<attention kind>_<mlp kind>.{ln1,wq,wk,wv,wo,ln2,sink,w_gate,
w_up,w_down,router,router_bias}``, each stacked over the kind's layers."""

from __future__ import annotations

# program leaf -> the benchmark's leaf, by the layer's attention kind
ATTENTION_LEAVES = {
    "full": {"wk": "k_proj", "wv": "v_proj"},
    "window": {"wk": "swa_k_proj", "wv": "swa_v_proj", "sink": "swa_sink"},
}
COMMON_LEAVES = {"ln1": "input_norm", "wq": "q_proj", "wo": "o_proj",
                 "ln2": "post_norm"}
MLP_LEAVES = {
    "dense": {"w_gate": "gate_proj", "w_up": "up_proj",
              "w_down": "down_proj"},
    "moe": {"router": "router", "router_bias": "router_bias",
            "w_gate": "experts_gate", "w_up": "experts_up",
            "w_down": "experts_down"},
}
PROGRAM_TOP_NAMES = {"embed": "embed", "final_norm": "final_norm",
                     "unembed": "lm_head"}
# Scales the source does not give (the configuration lists them under
# ``assumed``): the sinks as unit normals; the selection bias a thousandth.
# noaux_tc's biases exist to keep a trained router's load even, and a
# seeded router is even in expectation, so the stand-in must not unbalance
# it: at a tenth (the sigmoid scores of the top 8 of 256 lie above 0.87 and
# 0.0065 apart) an expert's share moved three-fold with its bias, the
# busiest held expert took 2.4 times the mean, and a run's speed followed
# its seed by 5%. At a thousandth the bias still turns one choice in five.
SINK_SCALE, ROUTER_BIAS_SCALE = 1.0, 0.001


# -- what the configuration's keys say ---------------------------------------
def layer_kinds(cfg: dict) -> list:
    """(attention kind, mlp kind) of the layers kept."""
    n = cfg["num_hidden_layers"]
    return [("window" if a else "full", "moe" if m else "dense")
            for a, m in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n])]


def layers_of(cfg: dict, attn: str | None = None,
              mlp: str | None = None) -> tuple:
    return tuple(i for i, (a, m) in enumerate(layer_kinds(cfg))
                 if attn in (None, a) and mlp in (None, m))


def rotary_dims(cfg: dict) -> int:
    """Leading dims of a q/k head that rotate: the even floor of
    ``partial_rotary_factor`` x ``head_dim`` (64 of 192)."""
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def router_width(cfg: dict) -> int:
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def experts_held(cfg: dict) -> tuple:
    """(first, count) of the experts this chip holds."""
    return (cfg.get("deployment", {}).get("experts_first", 0),
            cfg["n_routed_experts"])


def model_dims(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        dk=cfg["head_dim"], dv=cfg["v_head_dim"],
        hkv={"full": cfg["num_key_value_heads"],
             "window": cfg["swa_num_key_value_heads"]},
        f0=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        e=router_width(cfg), held=cfg["n_routed_experts"],
        k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
        window=cfg["sliding_window"])


# -- the program's model configuration ---------------------------------------
def program_config(cfg: dict, run: dict, **sizes):
    from tony_tpu.models import TransformerConfig

    kinds = layer_kinds(cfg)
    mlps = [m for _, m in kinds]
    n_dense = mlps.index("moe") if "moe" in mlps else len(mlps)
    if "dense" in mlps[n_dense:]:
        raise ValueError("the program takes dense layers only before the "
                         "expert layers")
    if cfg["add_full_attention_sink_bias"] or cfg["n_shared_experts"] \
            or cfg["routed_scaling_factor"] or not cfg["norm_topk_prob"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("a key of this configuration asks for what "
                         "neither the program nor the reference has")
    m = model_dims(cfg)
    return TransformerConfig(
        vocab_size=m["v"], d_model=m["d"], n_layers=len(kinds),
        n_heads=m["h"], head_dim=m["dk"], v_head_dim=m["dv"],
        rotary_dim=rotary_dims(cfg), v_scale=cfg["attention_value_scale"],
        rms_eps=cfg["layernorm_epsilon"], n_kv_heads=m["hkv"]["full"],
        rope_theta=float(cfg["rope_theta"]),
        attn_kinds=tuple(a for a, _ in kinds), window=m["window"],
        window_kv_heads=m["hkv"]["window"],
        window_rope_theta=float(cfg["swa_rope_theta"]),
        window_sink=bool(cfg["add_swa_attention_sink_bias"]),
        n_dense_layers=n_dense, dense_d_ff=m["f0"], d_ff=m["f"],
        n_experts=m["e"], expert_top_k=m["k"],
        router_scoring=cfg["scoring_func"],
        router_bias=cfg["topk_method"] == "noaux_tc",
        experts_held=experts_held(cfg), **sizes)


# -- the leaf table ----------------------------------------------------------
def leaf_table(cfg: dict) -> dict:
    """Leaf name -> ``weights.Leaf``; ``layers`` the layers that carry
    it: every layer, the full or the window layers, the dense or the
    expert layers."""
    from yardstick.weights import Leaf, check_table

    m = model_dims(cfg)
    d, h, dk, dv = m["d"], m["h"], m["dk"], m["dv"]
    every = layers_of(cfg)
    full, window = layers_of(cfg, "full"), layers_of(cfg, "window")
    dense, moe = layers_of(cfg, mlp="dense"), layers_of(cfg, mlp="moe")
    held = m["held"]
    return check_table({
        "input_norm": Leaf((d,), norm=True, layers=every),
        "q_proj": Leaf((d, h, dk), d ** -0.5, layers=every),
        "o_proj": Leaf((h, dv, d), (h * dv) ** -0.5, layers=every),
        "post_norm": Leaf((d,), norm=True, layers=every),
        "k_proj": Leaf((d, m["hkv"]["full"], dk), d ** -0.5, layers=full),
        "v_proj": Leaf((d, m["hkv"]["full"], dv), d ** -0.5, layers=full),
        "swa_k_proj": Leaf((d, m["hkv"]["window"], dk), d ** -0.5,
                           layers=window),
        "swa_v_proj": Leaf((d, m["hkv"]["window"], dv), d ** -0.5,
                           layers=window),
        "swa_sink": Leaf((h,), SINK_SCALE, layers=window),
        "gate_proj": Leaf((d, m["f0"]), d ** -0.5, layers=dense),
        "up_proj": Leaf((d, m["f0"]), d ** -0.5, layers=dense),
        "down_proj": Leaf((m["f0"], d), m["f0"] ** -0.5, layers=dense),
        "router": Leaf((d, m["e"]), d ** -0.5, layers=moe),
        "router_bias": Leaf((m["e"],), ROUTER_BIAS_SCALE, layers=moe),
        "experts_gate": Leaf((held, d, m["f"]), d ** -0.5, layers=moe),
        "experts_up": Leaf((held, d, m["f"]), d ** -0.5, layers=moe),
        "experts_down": Leaf((held, m["f"], d), m["f"] ** -0.5, layers=moe),
        "embed": Leaf((m["v"], d), 1.0),
        "final_norm": Leaf((d,), norm=True),
        "lm_head": Leaf((d, m["v"]), d ** -0.5),
    })


def group_leaves(group: str) -> dict:
    """Program leaf -> the benchmark's, for one group of the program's
    tree (``<attention kind>_<mlp kind>``)."""
    attn, mlp = group.split("_")
    return {**COMMON_LEAVES, **ATTENTION_LEAVES[attn], **MLP_LEAVES[mlp]}


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
# Unpadded widths throughout: a K row is Dk wide here whatever the device
# stores it at.
def _attention_params(cfg: dict, attn: str) -> int:
    m = model_dims(cfg)
    return (m["d"] * m["h"] * (m["dk"] + m["dv"])
            + m["d"] * m["hkv"][attn] * (m["dk"] + m["dv"]))


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_fixed_params(cfg: dict, attn: str, mlp: str) -> int:
    """A layer's parameters outside its experts: attention, the two norms,
    the sinks, and the dense MLP or the router with its bias."""
    m = model_dims(cfg)
    n = _attention_params(cfg, attn) + 2 * m["d"]
    if attn == "window" and cfg["add_swa_attention_sink_bias"]:
        n += m["h"]
    if mlp == "dense":
        return n + 3 * m["d"] * m["f0"]
    return n + m["d"] * m["e"] + m["e"]


def params_total(cfg: dict) -> int:
    """Every parameter stored HERE: the held experts, the held rows."""
    m = model_dims(cfg)
    layers = sum(
        _layer_fixed_params(cfg, a, mlp)
        + (m["held"] * _expert_params(cfg) if mlp == "moe" else 0)
        for a, mlp in layer_kinds(cfg))
    return layers + 2 * m["v"] * m["d"] + m["d"]


def experts_per_token_here(cfg: dict) -> float:
    """Held experts a token uses on average under a uniform router:
    k x held / E (8 x 16 / 256 = 0.5)."""
    m = model_dims(cfg)
    return m["k"] * m["held"] / m["e"]


def matmul_params_per_token(cfg: dict) -> float:
    """ACTIVE matmul parameters of one token HERE: attention, the dense
    MLP or the router and the held experts it uses on average, the head
    over the held rows."""
    m = model_dims(cfg)
    total = m["d"] * m["v"]
    for a, mlp in layer_kinds(cfg):
        total += _attention_params(cfg, a)
        total += (3 * m["d"] * m["f0"] if mlp == "dense" else
                  m["d"] * m["e"]
                  + experts_per_token_here(cfg) * _expert_params(cfg))
    return total


def experts_touched(cfg: dict, tokens: int) -> float:
    """Held experts that ``tokens`` tokens of a uniform router reach in
    one layer: held x (1 - (1 - k/E)^tokens) (13.9 of 16 at 64)."""
    m = model_dims(cfg)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["e"]) ** tokens)


def weight_bytes(cfg: dict, active_slots: int, itemsize: int = 2) -> float:
    """Bytes of weights one decode iteration must stream: everything
    outside the experts once (the router, its bias and the sinks are
    float32), the held experts its slots reach, the final norm and the
    head. The embedding table is gathered, not streamed."""
    m = model_dims(cfg)
    total = (m["d"] * m["v"] + m["d"]) * itemsize
    for a, mlp in layer_kinds(cfg):
        total += _layer_fixed_params(cfg, a, mlp) * itemsize
        if mlp == "moe":
            total += (m["d"] * m["e"] + m["e"]) * (4 - itemsize)
            total += (experts_touched(cfg, active_slots)
                      * _expert_params(cfg) * itemsize)
    return total


def cache_attention_bytes(cfg: dict, live_positions: int,
                          active_slots: int, itemsize: int = 2) -> float:
    """K and V bytes the decode attention of one iteration needs: the live
    positions in every full layer, the last ``sliding_window`` positions of
    every active slot in every window layer (a prompt here is at least a
    window long)."""
    m = model_dims(cfg)
    row = (m["dk"] + m["dv"]) * itemsize
    full = len(layers_of(cfg, "full")) * live_positions * m["hkv"]["full"]
    window = (len(layers_of(cfg, "window")) * active_slots * m["window"]
              * m["hkv"]["window"])
    return (full + window) * row


def expert_ffn_bytes(cfg: dict, active_slots: int,
                     itemsize: int = 2) -> float:
    """Bytes the expert products of one decode iteration need, over all
    expert layers: the touched held experts' weights once, the pairs'
    activations in (d) and out (d)."""
    m = model_dims(cfg)
    pairs = active_slots * experts_per_token_here(cfg)
    per_layer = (experts_touched(cfg, active_slots) * _expert_params(cfg)
                 + 2 * pairs * m["d"]) * itemsize
    return len(layers_of(cfg, mlp="moe")) * per_layer


def decode_iter_bytes(cfg: dict, live_positions: int, active_slots: int,
                      itemsize: int = 2) -> float:
    """Bytes one decode iteration NEEDS: the weights (``weight_bytes``),
    K and V read (``cache_attention_bytes``), one new K and V row written
    per active slot per layer, one embedding row per slot."""
    m = model_dims(cfg)
    written = sum(m["hkv"][a] for a, _ in layer_kinds(cfg)) * active_slots \
        * (m["dk"] + m["dv"]) * itemsize
    embed = active_slots * m["d"] * itemsize
    return (weight_bytes(cfg, active_slots, itemsize)
            + cache_attention_bytes(cfg, live_positions, active_slots,
                                    itemsize) + written + embed)


def decode_trace_shapes(cfg: dict, slots: int) -> dict:
    """Result shapes by which a traced run tells the decode program's
    kernels apart (the trace names a Mosaic call by its result): the
    cache attention's [slots, H, Dv], and the two grouped expert products
    over slots x k pair rows, [pairs, 2F] and [pairs, d]."""
    m = model_dims(cfg)
    pairs = slots * m["k"]
    return {"cache_attention": [(slots, m["h"], m["dv"])],
            "expert_ffn": [(pairs, 2 * m["f"]), (pairs, m["d"])]}


def norm_calls_per_f32_norm(cfg: dict) -> float:
    """bfloat16 RMSNorm calls of one pass through the layers (each layer's
    input norm, a dense layer's post norm) for each float32 one (an expert
    layer's post norm, which feeds the float32 router): what
    ``yardstick/kernel_readers.expert_ffn`` subtracts by."""
    every, dense, moe = (len(layers_of(cfg)), len(layers_of(cfg, mlp="dense")),
                         len(layers_of(cfg, mlp="moe")))
    return (every + dense) / moe

