"""Device idle time of the traced window that idle_gaps puts down to the
spans tony:engine.decode_readback and tony:engine.prefill_readback (the fenced
device_get: the host waits on the device), over window_s."""
from yardstick.dispatch_readers import readback_idle_pct as read  # noqa: F401
