"""Distributed trace spans — the gang-scheduling waterfall, visible.

One trace id per job, minted by the coordinator and propagated two ways:

* ``TONY_TRACE_ID`` in every task's launch env (coordinator → executor →
  user process, riding the same env contract as the task identity);
* RPC metadata: every framed request carries a ``trace`` field
  (``rpc/client.py`` attaches it, ``rpc/server.py`` records it via
  ``note_rpc_trace`` so handlers can stamp events with the caller's id).

Each process records spans into its own ``Tracer``; executors and user
processes flush theirs to ``$TONY_LOG_DIR/trace-*.jsonl`` (one Chrome
trace event per line), and the coordinator merges every file with its
own spans into one ``trace.json`` per job at stop — loadable directly
in ``chrome://tracing`` / Perfetto, where staging → rendezvous wait →
first step reads as a waterfall.

A span records its name, start, end, its own id and the id of the span
that caused it (``args.span_id`` / ``args.parent_id``: the span open in
the same context when it began), and feeds three sinks at once:

* the tracer's ring of the newest ``RING_SPANS`` spans (bounded, so a
  hot loop may record every iteration for weeks);
* the profiler: a ``with tracer.span(...)`` also enters a
  ``jax.profiler.TraceAnnotation`` of the same name when jax is already
  loaded — a no-op outside a profiler session, and inside one the span
  sits in the ``.xplane.pb`` beside the device ops, on the profiler's
  clock, carrying its ``span_id``;
* the caller: ``start_ns`` / ``end_ns`` / ``dur_ns`` stay on the span, so
  a counter summed at the same boundary reads the same two stamps.

One clock for all of it: ``now_ns()``, an epoch anchor taken once per
process plus ``perf_counter_ns`` deltas — monotonic, and on the
epoch-nanosecond timeline the profiler stamps host events with (an
``.xplane.pb`` counts from its session's ``profile_start_time``, itself
an epoch stamp; measured on a TPU host, the two records of a span start
within 2 µs of each other).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import logging
import os
import sys
import threading
import time
import uuid
from collections import deque
from pathlib import Path
from typing import Any
from tony_tpu.analysis import sync_sanitizer as _sync

log = logging.getLogger(__name__)

TRACE_ID_ENV = "TONY_TRACE_ID"

# Spans a tracer keeps: the newest win. The serving engine records about
# ten per working iteration, so this holds its last ~1,500 iterations.
RING_SPANS = 16384

_PERF_ANCHOR_NS = time.perf_counter_ns()
_EPOCH_ANCHOR_NS = time.time_ns()


def now_ns() -> int:
    """Epoch nanoseconds that never step back: the process's epoch
    anchor plus the monotonic clock's advance since."""
    return _EPOCH_ANCHOR_NS + time.perf_counter_ns() - _PERF_ANCHOR_NS


def perf_counter_to_ns(t: float) -> int:
    """A ``time.perf_counter()`` reading on ``now_ns()``'s timeline."""
    return _EPOCH_ANCHOR_NS + int(t * 1e9) - _PERF_ANCHOR_NS


# Process-wide, so (pid, span_id) names one span of a merged job trace.
_span_ids = itertools.count(1)

# os.getpid() is a system call (microseconds where calls are sandboxed,
# as on the TPU hosts measured): read once, and again in a forked child.
_pid = os.getpid()


def _refresh_pid() -> None:
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_refresh_pid)

# The id of the span open in this context (thread or task), the parent
# of whatever begins next.
_current_span: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "tony_current_span", default=None
)

# tony_tpu.profiling.annotate, once jax is loaded: observability must
# stay importable (and cheap) in the processes that never import jax.
_annotate = None


def _profiler_annotation(name: str, span_id: int):
    global _annotate
    if _annotate is None:
        if "jax" not in sys.modules:
            return None
        from tony_tpu import profiling

        _annotate = profiling.annotate
    return _annotate(name, span_id=span_id)

# The trace id presented by the current RPC request (server side).
_rpc_trace: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "tony_rpc_trace", default=None
)


def note_rpc_trace(trace_id: str | None) -> None:
    """Record the caller's trace id for the duration of this dispatch."""
    _rpc_trace.set(trace_id)


def current_rpc_trace() -> str | None:
    return _rpc_trace.get()


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def ambient_trace_id() -> str | None:
    """The trace id this process was launched under, if any."""
    return os.environ.get(TRACE_ID_ENV) or None


class Span:
    """One open interval. ``end()`` is idempotent; attributes land in the
    Chrome event's ``args``. As a context manager (``with
    tracer.span(...)``) it is also the parent of the spans begun inside
    it and a ``TraceAnnotation`` in the profiler's trace; a span from
    ``begin()`` may be ended on another thread, so it is neither."""

    __slots__ = ("_tracer", "name", "attrs", "span_id", "parent_id",
                 "start_ns", "end_ns", "_token", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = next(_span_ids)
        self.parent_id = _current_span.get()
        self.end_ns: int | None = None
        self._token = None
        self._annotation = None
        self.start_ns = 0          # stamped by begin() or __enter__

    @property
    def dur_ns(self) -> int:
        """Start to end, or to now while the span is open."""
        end = self.end_ns if self.end_ns is not None else now_ns()
        return end - self.start_ns

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    def end(self) -> None:
        if self.end_ns is not None:
            return
        self.end_ns = now_ns()
        self._tracer._record(self.name, self.start_ns, self.end_ns,
                             self.span_id, self.parent_id, self.attrs)

    def __enter__(self) -> "Span":
        self._token = _current_span.set(self.span_id)
        self._annotation = _profiler_annotation(self.name, self.span_id)
        # Stamped beside the profiler's own stamp, so the two records of
        # this span start within a call of each other.
        self.start_ns = now_ns()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self.end()
        _current_span.reset(self._token)


class Tracer:
    """Per-process span recorder in Chrome trace-event form.

    ``proc`` names the lane ("coordinator", "executor:worker:0", ...);
    it becomes the event's ``args.proc`` and a ``process_name`` metadata
    row so Perfetto labels the track."""

    def __init__(
        self, trace_id: str | None = None, proc: str = "",
    ) -> None:
        self.trace_id = trace_id or ambient_trace_id() or new_trace_id()
        self.proc = proc or f"proc-{os.getpid()}"
        # No lock: one C call appends (and drops the oldest) or copies
        # the deque, and the interpreter lock makes each atomic.
        self._ring: deque[tuple] = deque(maxlen=RING_SPANS)

    # -- recording ---------------------------------------------------------
    def begin(self, name: str, **attrs: Any) -> Span:
        """An open span the caller ends with ``end()``, on any thread."""
        span = self.span(name, **attrs)
        span.start_ns = now_ns()
        return span

    def span(self, name: str, **attrs: Any) -> Span:
        """``with tracer.span("load") as s: ...`` — see ``Span``."""
        return Span(self, name, attrs)

    def record(self, name: str, start_ns: int, end_ns: int,
               **attrs: Any) -> int:
        """A span written after the fact from two ``now_ns()`` stamps (a
        request's life, known only when it retires). It has no parent;
        spans of one request share ``request=``. Returns its id."""
        span_id = next(_span_ids)
        self._record(name, start_ns, end_ns, span_id, None, attrs)
        return span_id

    def _record(self, name: str, start_ns: int, end_ns: int, span_id: int,
                parent_id: int | None, attrs: dict[str, Any]) -> None:
        self._ring.append((name, start_ns, end_ns, span_id, parent_id, _pid,
                           threading.get_ident() % 100000, attrs))

    def __len__(self) -> int:
        return len(self._ring)

    # -- export ------------------------------------------------------------
    def to_chrome_events(self) -> list[dict[str, Any]]:
        rows = self._ring.copy()
        events = [{
            "name": name, "ph": "X",
            "ts": start_ns // 1000,
            "dur": max((end_ns - start_ns) // 1000, 1),
            "pid": pid, "tid": tid,
            "args": {"trace_id": self.trace_id, "proc": self.proc,
                     "span_id": span_id, "parent_id": parent_id, **attrs},
        } for name, start_ns, end_ns, span_id, parent_id, pid, tid, attrs
            in rows]
        if events:
            events.insert(0, {
                "name": "process_name", "ph": "M", "pid": os.getpid(),
                "args": {"name": self.proc},
            })
        return events

    def write_jsonl(self, path: str | os.PathLike[str]) -> None:
        """One event per line — mergeable by the coordinator even when
        this process died before writing a well-formed JSON document."""
        try:
            with open(path, "w") as f:
                for event in self.to_chrome_events():
                    f.write(json.dumps(event) + "\n")
        except OSError:
            log.warning("could not write trace to %s", path, exc_info=True)


def read_trace_jsonl(path: str | os.PathLike[str]) -> list[dict[str, Any]]:
    """Lenient per-line reader (torn tails skipped — a SIGKILLed writer
    must not hide the other processes' spans)."""
    from tony_tpu.observability.events import parse_jsonl

    try:
        return parse_jsonl(Path(path).read_text())
    except OSError:
        return []


def merge_job_trace(
    tracer: Tracer, logs_dir: str | os.PathLike[str] | None,
) -> dict[str, Any]:
    """The per-job Chrome trace document: the coordinator's spans plus
    every ``trace-*.jsonl`` executors and user processes left in the
    logs dir (local backends; remote executors' spans stay with their
    own logs)."""
    events = tracer.to_chrome_events()
    if logs_dir is not None:
        root = Path(logs_dir)
        if root.is_dir():
            for path in sorted(root.glob("trace-*.jsonl")):
                events.extend(read_trace_jsonl(path))
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"trace_id": tracer.trace_id},
    }


_default_tracer: Tracer | None = None
_default_lock = _sync.make_lock("trace:_default_lock")


def default_tracer() -> Tracer:
    """The user-process tracer: trace id from TONY_TRACE_ID, spans
    flushed to the job scratch dir at interpreter exit so the
    coordinator's merge picks them up."""
    global _default_tracer
    with _default_lock:
        if _default_tracer is None:
            job = os.environ.get("JOB_NAME", "")
            idx = os.environ.get("TASK_INDEX", "")
            proc = f"user:{job}:{idx}" if job else f"user-{os.getpid()}"
            _default_tracer = Tracer(proc=proc)
            log_dir = os.environ.get("TONY_LOG_DIR")
            if log_dir:
                import atexit

                # Session id in the name: the scratch dir is shared
                # across session retries, and each session's spans must
                # survive into the merged job trace.
                session = os.environ.get("SESSION_ID", "0")
                suffix = (
                    f"{job}-{idx}-s{session}" if job else str(os.getpid())
                )
                path = Path(log_dir) / f"trace-user-{suffix}.jsonl"
                atexit.register(
                    lambda: _default_tracer.write_jsonl(path)
                    if len(_default_tracer) else None
                )
        return _default_tracer


def span(name: str, **attrs: Any):
    """Module-level convenience: ``with observability.span("load"): ...``."""
    return default_tracer().span(name, **attrs)
