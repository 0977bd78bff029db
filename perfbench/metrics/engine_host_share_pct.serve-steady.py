"""Host-only share of the engine's working iterations:
ServingEngine.stats() working_wall_ms less phase_ms prefill_device and
decode_device (spans tony:engine.prefill_device / decode_device), over
working_wall_ms (span tony:engine.step). Over the engine's life in the
job (warm-up, pre-roll, window, drain), not the window alone."""
from yardstick.engine_readers import host_share_pct as read  # noqa: F401
