"""Shared arithmetic of the per-layer metrics that hold the kernels of ONE
decode iteration against the HBM roofline: the bytes the calls need (counted
by the cell's model module from the live load of the traced span), over the
bandwidth, over the device time the calls took.

The trace names a Mosaic call by its result shape alone
(``xplane._op_name``: ``mosaic:bf16[512,4096]``), so a kernel is told from
the others by the shapes its model module states
(``decode_trace_shapes(cfg, slots)``). Where another program's kernel has
the same result shape the two cannot be told apart by name; the one such
case is handled in ``expert_ffn`` below and stated there. A program that
has no such kernels (the commit before they were added) gives None, and the
metric is left out of the line."""

from __future__ import annotations

from yardstick import readers, stats

SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def mosaic_name(shape, dtype: str) -> str:
    return f"mosaic:{SHORT[dtype]}[{','.join(str(n) for n in shape)}]"


def live_decode_load(run):
    """(slots in decode, positions they hold) over the traced span, from
    the requests' own records; None where nothing decoded."""
    lo, hi = run["traced_span_client"]
    slots, positions = stats.live_load(run["requests"], lo, hi)
    return (slots, positions) if slots > 0 else None


def calls_and_seconds(trace: dict, names) -> tuple:
    took = {n: s for n, s in trace["device_ops"]}
    calls = sum(trace["device_op_calls"].get(n, 0) for n in set(names))
    return calls, sum(took.get(n, 0.0) for n in set(names))


def share(run, need_bytes: float, calls: float, seconds: float,
          calls_per_iteration: int):
    """100 x (needed bytes of one iteration / bandwidth) x iterations /
    seconds, the iterations being the calls seen over the calls one
    iteration makes."""
    if calls <= 0 or seconds <= 0 or calls_per_iteration <= 0:
        return None
    floor = need_bytes / readers.peaks_of(run)["hbm_bytes_per_s"]
    return 100.0 * floor * (calls / calls_per_iteration) / seconds


def kernel_shapes(run, kind: str):
    model = run["cell"].model
    if not hasattr(model, "decode_trace_shapes"):
        return None
    return model.decode_trace_shapes(
        run["config"], int(run["job"]["slots"]))[kind]


def cache_attention(run):
    """Decode attention calls of both cache kinds: K and V bytes of the
    live positions (full layers) and of the last window of every slot in
    decode (window layers), over the calls' device time."""
    t = run["trace"]
    shapes = kernel_shapes(run, "cache_attention") if t else None
    load = live_decode_load(run) if shapes else None
    if not load:
        return None
    cfg, model = run["config"], run["cell"].model
    dtype = cfg["run"]["kv_dtype"]
    calls, seconds = calls_and_seconds(
        t, [mosaic_name(s, dtype) for s in shapes])
    slots, positions = load
    return share(run, model.cache_attention_bytes(cfg, positions, slots),
                 calls, seconds, len(model.layer_kinds(cfg)))


def expert_ffn(run):
    """The grouped expert products of decode (two a layer): the touched
    held experts' weights once and the pairs' activations in and out, over
    the calls' device time.

    One collision is taken out: where a prefill round's rows (batch x
    chunk) equal decode's pair rows (slots x experts per token), the
    prefill program's bfloat16 RMSNorm calls have the products' result
    shape and so their name. The same round also norms the expert
    layers' input in float32, under a name of its own
    (``mosaic:f32[rows,d]``), which nothing else has: the bfloat16 norms
    are taken as ``norm_calls_per_f32_norm`` calls for each of those (the
    model module's count) at HALF the float32 call's time each (half the
    bytes in and out, a streaming kernel), and both are subtracted. An
    estimate, stated in PERF.md section 7 with its cure (a kernel name
    of its own in the trace)."""
    t = run["trace"]
    shapes = kernel_shapes(run, "expert_ffn") if t else None
    load = live_decode_load(run) if shapes else None
    if not load:
        return None
    cfg, model = run["config"], run["cell"].model
    dtype = cfg["run"]["weights_dtype"]
    calls, seconds = calls_and_seconds(
        t, [mosaic_name(s, dtype) for s in shapes])
    twin_calls, twin_seconds = calls_and_seconds(
        t, [mosaic_name(s, "float32") for s in shapes])
    if twin_calls:
        per_twin = model.norm_calls_per_f32_norm(cfg)
        calls -= twin_calls * per_twin
        seconds -= 0.5 * twin_seconds * per_twin
    slots, _ = load
    return share(run, model.expert_ffn_bytes(cfg, slots), calls, seconds,
                 2 * len(model.layers_of(cfg, mlp="moe")))


def expert_load_max_over_mean(run):
    """Pairs on the busiest held expert over the mean of the held, from
    ``ServingEngine.stats()["experts"]["pairs_per_expert"]`` (the engine's
    life in the job)."""
    experts = ((run.get("job") or {}).get("engine_stats") or {}).get(
        "experts") or {}
    pairs = experts.get("pairs_per_expert")
    if not pairs or sum(pairs) <= 0:
        return None
    return max(pairs) * len(pairs) / sum(pairs)
