"""Profiler integration — fills the seam the reference reserved for
TensorBoard-style observability (TaskExecutor.java:121-124 reserves a port
and registers its URL through the AM; SURVEY.md §5.1 maps that seam to
``jax.profiler``). Training code calls these; the executor supplies
``PROFILER_PORT`` when ``tony.profiler.enabled`` is set."""

from __future__ import annotations

import contextlib
import logging
import os

from tony_tpu import constants

log = logging.getLogger(__name__)

_started = False


def maybe_start_profiler_server() -> int | None:
    """Start ``jax.profiler.start_server`` on the port the executor
    reserved (no-op without PROFILER_PORT, so scripts can call this
    unconditionally). Returns the port, or None."""
    global _started
    port = os.environ.get(constants.PROFILER_PORT)
    if not port or _started:
        return int(port) if port else None
    import jax

    jax.profiler.start_server(int(port))
    _started = True
    log.info("jax profiler server on port %s", port)
    return int(port)


def default_trace_dir() -> str:
    """Traces default to the job's writable scratch (the executor exports
    TONY_LOG_DIR), so captured profiles land next to the task logs that
    task URLs already point at."""
    root = os.environ.get(constants.TONY_LOG_DIR, ".")
    return os.path.join(root, "profile")


@contextlib.contextmanager
def trace(log_dir: str | None = None):
    """Capture a Perfetto/XProf trace of the enclosed steps into
    ``log_dir`` (default: ``$TONY_LOG_DIR/profile``; viewable in
    TensorBoard's profile tab or xprof)."""
    import jax

    with jax.profiler.trace(log_dir or default_trace_dir()):
        yield


class StepProfiler:
    """Capture a window of training steps — the usual pattern of profiling
    steps [start, start+num) once compilation and input pipelines are warm::

        prof = profiling.StepProfiler(start=10, num=5)
        for step in range(steps):
            prof.before_step(step)
            state, metrics = train_step(state, batch)
            prof.after_step(step)

    No-ops outside the window, so it can stay in production loops."""

    def __init__(self, start: int = 10, num: int = 5,
                 log_dir: str | None = None) -> None:
        self.start = start
        self.stop = start + num
        self.log_dir = log_dir or default_trace_dir()
        self._active = False

    def before_step(self, step: int) -> None:
        # >= start (not ==): a loop resumed mid-window must still profile
        # its remaining in-window steps.
        if self.start <= step < self.stop and not self._active:
            import jax

            jax.profiler.start_trace(self.log_dir)
            self._active = True
            log.info("profiling steps %d..%d into %s",
                     self.start, self.stop - 1, self.log_dir)

    def after_step(self, step: int) -> None:
        if self._active and step >= self.stop - 1:
            import jax

            jax.profiler.stop_trace()
            self._active = False

    def close(self) -> None:
        """Stop an in-flight trace (e.g. the loop ended inside the
        window)."""
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False


def annotate(name: str, **metadata):
    """Named span in the device trace (jax.profiler.TraceAnnotation);
    ``metadata`` rides the event as stats. Outside a profiler session
    entering it costs a flag test. ``observability.trace`` spans enter
    one each, so the program's spans sit beside the device ops."""
    import jax

    return jax.profiler.TraceAnnotation(name, **metadata)
