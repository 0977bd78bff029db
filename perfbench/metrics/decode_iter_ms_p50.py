"""Median device duration of one decode_window program."""
from yardstick import xplane


def read(run):
    t = run["trace"]
    prog = xplane.program(t, "decode_window") if t else None
    return prog["median_s"] * 1000.0 if prog else None
