"""Device idle time of the traced window that idle_gaps puts down to the
spans tony:engine.decode_launch and tony:engine.prefill_launch (from a device
span's start to the jitted call's return: the host is still handing the
program over), over window_s."""
from yardstick.dispatch_readers import launch_idle_pct as read  # noqa: F401
