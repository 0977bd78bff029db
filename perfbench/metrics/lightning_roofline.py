"""The lightning layers' two kernels against their roofline: over the
traced window, the sum over the decode calls (one a lightning layer an
iteration: the live slots' float32 states read and written where they lie)
and the prefill calls (one a lightning layer a round: a chunk's q, k, v and
the state in, o and the state out; two [C, C, D] and two [C, D, D] products
a head) of the larger of FLOPs over the peak and needed bytes over the
bandwidth, over the sum of their device time. The calls are told apart by
their result shapes, (f32[slots,H,D], f32[slots+1,H,D,D]) and
(f32[rows,H,C,D], f32[rows,H,D,D]): no other call of the two programs
returns a pair of float32 arrays of those shapes. The counts are the model
module's (``lightning_call_cost``); a decode call is counted at the slots
in decode over the traced span (the job's own samples; a parked lane moves
no state of its slot), a prefill call at the rows of a round that are no padding (the engine's
own count over its life). A program without the kernels gives None."""
import re

from yardstick import counts, engine_readers, readers
from yardstick.traced_slots import decoding_slots

PAIR = re.compile(r"^mosaic:\(f32\[(\d+),(\d+),(\d+)(?:,(\d+))?\],"
                  r"f32\[(\d+),(\d+),(\d+),(\d+)\]\)$")


def read(run):
    t, model = run["trace"], run["cell"].model
    if not t or not hasattr(model, "lightning_call_cost"):
        return None
    cfg = run["config"]
    stats = engine_readers.engine_stats(run)
    rounds = stats.get("prefill_rounds") or 0
    slots = decoding_slots(run)
    least, took = 0.0, dict(t["device_ops"])
    seconds = 0.0
    for name, calls in t["device_op_calls"].items():
        m = PAIR.match(name)
        if not m:
            continue
        if m.group(4) is None:          # decode: o [slots, H, D]
            if not slots or int(m.group(5)) != int(m.group(1)) + 1:
                continue
            cost = model.lightning_call_cost(cfg, "decode", rows=slots)
        else:                           # prefill: o [rows, H, C, D]
            rows = int(m.group(1))
            padded = (stats.get("prefill_rows_padded", 0) / rounds
                      if rounds else 0.0)
            cost = model.lightning_call_cost(cfg, "prefill",
                                             rows=max(rows - padded, 0.0),
                                             chunk=int(m.group(3)))
        floor, _ = counts.roofline_seconds(cost["flops"], cost["bytes"],
                                           readers.peaks_of(run))
        least += floor * calls
        seconds += took.get(name, 0.0)
    return 100.0 * least / seconds if seconds > 0 else None
