"""p90 of submit -> slot assigned over the engine's last 512 retired
requests: ServingEngine.stats()["queue_wait_ms"]["p90"], the length of
span tony:request.queue. Over the engine's life in the job (warm-up,
pre-roll, window, drain), not the window alone."""
from yardstick.engine_readers import latency_p90


def read(run):
    return latency_p90(run, "queue_wait_ms")
