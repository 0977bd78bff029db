"""What both job scripts need inside the process that holds the chip:
the stage log, the device report, compilation events with their times,
the profiler's start and stop, the peak of device memory. Imports jax
lazily: importing this file touches no device."""

from __future__ import annotations

import json
import os
import time
from pathlib import Path


class Work:
    """The run's work directory: parameters in, stage lines and results
    out. Every file is published atomically."""

    def __init__(self, params_path: str) -> None:
        with open(params_path) as f:
            self.params = json.load(f)
        self.dir = Path(self.params["work"])

    def stage(self, name: str) -> None:
        with open(self.dir / "stages.log", "a") as f:
            f.write(f"{name} {time.time():.6f}\n")

    def publish(self, name: str, obj) -> None:
        tmp = self.dir / f".{name}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, self.dir / name)

    def read(self, name: str):
        path = self.dir / name
        if not path.is_file():
            return None
        with open(path) as f:
            return json.load(f)


def device_report() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def memory_peak_bytes() -> int | None:
    """Peak on the fullest chip, where the backend reports it."""
    import jax

    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileLog:
    """Wall-clock times of every compilation and every load of a compiled
    program from the persistent cache, from jax's own monitoring events:
    the harness counts those that fall inside the window, and there must
    be none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        import jax.monitoring

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event in self.EVENTS:
            self.times.append(time.time())

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


def program_compile_s() -> float:
    """Sum of the program's own ``tony_compile_ms`` observations so far:
    the first call of each instrumented program (trace, compile or cache
    load, and one execution)."""
    from tony_tpu import observability

    snap = observability.default_registry().snapshot()
    hist = snap["histograms"].get("tony_compile_ms")
    return (hist["sum"] / 1000.0) if hist else 0.0


class Tracer:
    """Starts and stops the profiler around a few seconds of the window,
    with a ``bench:traced`` host span over exactly what was traced."""

    def __init__(self, out_dir: Path) -> None:
        self.out_dir = out_dir
        self.span = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans come from annotations
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench:traced")
        self.span.__enter__()

    def stop(self) -> None:
        import jax

        self.span.__exit__(None, None, None)
        self.span = None
        jax.profiler.stop_trace()
