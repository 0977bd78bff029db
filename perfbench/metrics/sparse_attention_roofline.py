"""The sparse layers' decode attention against the HBM roofline: K and V of
the SELECTED blocks' keys of the slots in decode, over the bandwidth, over
the device time of the kernel's calls. The keys are the program's own count,
made on the device where the blocks are listed
(``ServingEngine.stats()["sparse"]["keys_read"]`` over the decode
iterations' slots, the sparse layers and their KV groups: the mean a slot,
a layer and an iteration, per KV head), the slots the mean in decode over the traced span
(the job's own samples), the bytes a key the model module's. The kernel is
told apart by its result shape [slots, KV heads, query heads a group, head
width], which no other call of the two programs has
(``sparse_decode_trace_name``). The selection itself runs as XLA fusions the
trace cannot name, and the prefill kernel's needed bytes depend on each
chunk's position: neither is in this share (PERF.md section 7). A program
without the kernel gives None."""
from yardstick import engine_readers, kernel_readers as kr
from yardstick.traced_slots import decoding_slots


def read(run):
    t, model = run["trace"], run["cell"].model
    if not t or not hasattr(model, "sparse_decode_trace_name"):
        return None
    stats = engine_readers.engine_stats(run)
    sparse, slots = stats.get("sparse") or {}, decoding_slots(run)
    cfg, job = run["config"], run["job"]
    layers = len(model.layers_of(cfg, "sparse"))
    if not slots or not stats.get("decode_slots_sum") or not layers:
        return None
    groups = layers * model.model_dims(cfg)["hkv"]
    keys = sparse.get("keys_read", 0) / (stats["decode_slots_sum"] * groups)
    calls, seconds = kr.calls_and_seconds(
        t, [model.sparse_decode_trace_name(cfg, int(job["slots"]))])
    need = layers * slots * model.kv_bytes_of_keys(cfg, keys)
    return kr.share(run, need, calls, seconds, layers)
