"""Device time, per decode iteration, of the operations that only the conv
layers' operator runs in the decode program, in ms: those whose result
shape no other operation of the two programs has
(``models/<model>.py::conv_operator_shapes``: the ``in_proj`` product
``bf16[slots,1,3d]``, the convolution's window of state rows and gate
``f32[slots,L,d]``, and the state's rows in and out ``bf16[slots,L-1,d]``),
summed over the traced window and divided by the ``decode_window`` programs
that started in it (times the window's steps). The operator's gate and its
``out_proj`` product share their result shape with the attention layers'
output projection and the dense layers' down product and are in no number
here.

A TIME and not a share of the HBM roofline (ISSUE 43 asked for
``conv_operator_hbm_roofline``): XLA prefetches ``W_in`` into VMEM under the
operations before the product (async slices of the parameter), so the
product itself took 21 us for 25 MB of weights, 148-160% of what the
bandwidth allows (my chip runs, PR 43): the bytes move inside other
operations' time, and the trace's self times cannot say when. A program
without conv layers, or whose trace holds no ``in_proj`` product, gives
None."""
from yardstick import kernel_readers as kr, xplane


def read(run):
    t, model = run["trace"], run["cell"].model
    if not t or not hasattr(model, "conv_operator_shapes"):
        return None
    prog = xplane.program(t, "decode_window")
    if not prog:
        return None
    job = run["job"]
    shapes = model.conv_operator_shapes(run["config"], int(job["slots"]))
    seconds = 0.0
    for part, (dtype, shape) in shapes.items():
        suffix = ":" + kr.mosaic_name(shape, dtype).split(":", 1)[1]
        names = [n for n in t["device_op_calls"] if n.endswith(suffix)]
        calls, took = kr.calls_and_seconds(t, names)
        if part == "in_proj" and calls <= 0:
            return None
        seconds += took
    return 1000.0 * seconds / (prog["calls"] * int(job["decode_window"]))
