"""KV positions in use over positions reserved:
ServingEngine.stats()["kv"] live_position_ms over reserved_positions x
working_wall_ms, summed at the end of each tony:engine.step. Over the
engine's life in the job (warm-up, pre-roll, window, drain), not the
window alone."""
from yardstick.engine_readers import kv_live_pct as read  # noqa: F401
