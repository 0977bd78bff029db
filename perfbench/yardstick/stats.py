"""Metric arithmetic: percentiles over every request due in a window (a
missing one is infinitely late), rates over all the work and all the time
of a window, quartile spread. Pure Python, no jax."""

from __future__ import annotations

import bisect
import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the value at rank ceil(q·n)) — of ALL the
    values given: a request with no answer enters as ``math.inf`` and so
    moves the tail instead of leaving the sample."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the contract's
    measure of run-to-run spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


class Request:
    """What the load generator knows of one request, on the client's clock
    (seconds from the schedule's zero)."""

    __slots__ = ("index", "due", "sent", "answered", "status", "prompt_len",
                 "max_new", "length", "ttft_ms", "wall_ms", "tokens",
                 "prompt", "error")

    def __init__(self, index, due, prompt_len, max_new):
        self.index = index
        self.due = due
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.sent = None
        self.answered = None     # client clock at the answer
        self.status = None       # HTTP status, or None: no answer yet
        self.length = 0
        self.ttft_ms = None      # the engine's own, counted from submit
        self.wall_ms = None
        self.tokens = None
        self.prompt = None
        self.error = None

    @property
    def ok(self) -> bool:
        """Answered, and with as many tokens as were asked for: the traffic
        sends no end token, so a shorter answer is a request ended early,
        which would flatter every latency and is counted as failed."""
        return (self.status == 200 and self.length == self.max_new >= 1
                and self.ttft_ms is not None)

    def ttft_client_ms(self) -> float:
        """Due time → first token as a client could reckon it from an
        answer that does not stream: (answer − wall_ms − due) is
        everything before ``submit`` (generator lateness, HTTP, the server's
        accept), ``ttft_ms`` is submit → first token. Missing: infinite."""
        if not self.ok:
            return math.inf
        before_submit = (self.answered - self.wall_ms / 1000.0 - self.due)
        return max(before_submit, 0.0) * 1000.0 + self.ttft_ms

    def tpot_ms(self):
        """Time per output token after the first; None for one token."""
        if not self.ok or self.length < 2:
            return None
        return (self.wall_ms - self.ttft_ms) / (self.length - 1)


def serving_metrics(requests, t0: float, t1: float) -> dict:
    """Serving numbers of the window [t0, t1) from the requests' records.
    ``tokens_answered_per_s`` counts an answer's tokens at the instant it
    arrives (the server does not stream; those due in the pre-roll count
    too): information beside ``serve_tokens_per_s``, which is
    ``generated_rate`` of the engine's own counter."""
    due = [r for r in requests if t0 <= r.due < t1]
    tokens_in = sum(r.length for r in requests
                    if r.ok and t0 <= r.answered < t1)
    out = {
        "due_in_window": len(due),
        "tokens_answered_per_s": tokens_in / (t1 - t0),
        "lateness_p50_ms": None, "lateness_max_ms": None,
    }
    sent = [r for r in due if r.sent is not None]
    if sent:
        late = [(r.sent - r.due) * 1000.0 for r in sent]
        out["lateness_p50_ms"] = percentile(late, 50)
        out["lateness_max_ms"] = max(late)
    if due:
        ttft = [r.ttft_client_ms() for r in due]
        out["serve_ttft_p50_ms"] = percentile(ttft, 50)
        out["serve_ttft_p90_ms"] = percentile(ttft, 90)
        tpot = [t for t in (r.tpot_ms() for r in due) if t is not None]
        if tpot:
            out["serve_tpot_p50_ms"] = percentile(tpot, 50)
            out["serve_tpot_p90_ms"] = percentile(tpot, 90)
    return out


def counter_at(samples, t: float) -> float:
    """A counter that only grows, read at ``t`` from ``samples`` (rows of
    [time, ..., count], in time's order): linear between the two samples
    around ``t``. Before the first or after the last there is nothing to
    read: an error, never an extrapolation."""
    if not samples or not samples[0][0] <= t <= samples[-1][0]:
        raise ValueError(
            f"no samples of the counter around {t!r}: they span "
            f"{samples[0][0]!r} to {samples[-1][0]!r}" if samples
            else "no samples of the counter")
    hi = bisect.bisect_left(samples, t, key=lambda row: row[0])
    (ta, a), (tb, b) = ((samples[i][0], samples[i][-1])
                        for i in (max(hi - 1, 0), hi))
    return b if tb == ta else a + (b - a) * (t - ta) / (tb - ta)


def generated_rate(samples, t0: float, t1: float) -> float:
    """Tokens the engine generated inside [t0, t1) over its length: the
    difference of its counter between the window's two edges. A token
    counts when it is made, so an answer that began before the window or
    ends after it gives the part of it that falls inside."""
    return (counter_at(samples, t1) - counter_at(samples, t0)) / (t1 - t0)


def tokens_unaccounted(generated: int, answered: int, allowance: int) -> int:
    """By how many tokens the engine's count at its end, less the tokens
    of every answer a client received, lies outside [0, ``allowance``]:
    ``allowance`` is what the requests that were cut or never answered
    may have had generated for them, 0 where every request was followed
    to its end. Exact; anything but 0 is a counter that has drifted from
    the served path."""
    diff = generated - answered
    return max(-diff, diff - allowance, 0)


def train_rate(step_ends, tokens_per_step: int, window_s: float,
               chips: int) -> float:
    """Tokens of every step that finished inside the window, over ALL of
    the window's wall time, over the chips. Not a median of step times: a
    stall lowers it. (The training job closes its window with the step in
    flight when the asked-for seconds ran out, so ``window_s`` is the last
    step's end and every step started is counted.)"""
    finished = sum(1 for t in step_ends if t <= window_s)
    return finished * tokens_per_step / window_s / chips


def live_load(requests, t_a: float, t_b: float, step_s: float = 0.1):
    """Mean over [t_a, t_b) of the slots in decode and of the positions
    their caches hold, reckoned from the requests' own records: a request
    decodes from its first token (answer - wall + ttft on the client's
    clock) to its answer, and holds its prompt plus what it has generated
    so far (taken as growing evenly)."""
    spans = []
    for r in requests:
        if not r.ok or r.length < 2:
            continue
        end = r.answered
        start = end - (r.wall_ms - r.ttft_ms) / 1000.0
        spans.append((start, end, r.prompt_len, r.length))
    slots, positions, n = 0.0, 0.0, 0
    t = t_a
    while t < t_b:
        for start, end, prompt, length in spans:
            if start <= t < end:
                slots += 1
                positions += prompt + 1 + (length - 1) * (t - start) / (end - start)
        n += 1
        t += step_s
    return (slots / n, positions / n) if n else (0.0, 0.0)
