"""Needed bytes of one decode iteration over the HBM bandwidth, over the
decode_window program's device time."""
from yardstick.readers import decode_hbm_roofline as read  # noqa: F401
