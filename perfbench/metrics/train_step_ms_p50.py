"""Median host-clock time of one fenced step (dispatch to loss readback)."""
from yardstick.readers import median_ms


def read(run):
    return median_ms(run["job"]["step_ms"])
