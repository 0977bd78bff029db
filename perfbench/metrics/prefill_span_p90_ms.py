"""p90 of slot assigned -> first token over the engine's last 512
retired requests that prefilled here:
ServingEngine.stats()["prefill_span_ms"]["p90"], the length of span
tony:request.prefill. Over the engine's life in the job (warm-up,
pre-roll, window, drain), not the window alone."""
from yardstick.engine_readers import latency_p90


def read(run):
    return latency_p90(run, "prefill_span_ms")
