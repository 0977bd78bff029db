"""Open-loop HTTP load generator: sends each request when it is due,
whether or not earlier ones have answered, and times everything on the
client's clock from when the request was DUE. One dispatcher thread; a
request's own thread only blocks on its socket (the server answers once,
when the request retires). Stdlib only."""

from __future__ import annotations

import http.client
import json
import threading
import time

from yardstick.stats import Request


class LoadRun:
    def __init__(self, host: str, port: int, schedule: list[dict],
                 timeout_s: float = 300.0) -> None:
        self.host, self.port = host, port
        self.timeout_s = timeout_s
        self.schedule = schedule
        self.requests = [
            Request(r["index"], r["due"], len(r["prompt"]),
                    r["max_new_tokens"]) for r in schedule]
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        self.t0 = None           # monotonic clock at the schedule's zero

    def now(self) -> float:
        return time.monotonic() - self.t0

    def _one(self, rec: Request, body: bytes, keep_tokens: bool) -> None:
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        try:
            rec.sent = self.now()
            conn.request("POST", "/generate", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            rec.answered = self.now()
            rec.status = resp.status
            if resp.status == 200:
                out = json.loads(raw)
                # the tokens as received, not the program's own count of
                # them: no end token is sent, so every answer is due whole
                rec.length = len(out["tokens"])
                rec.ttft_ms = float(out["ttft_ms"])
                rec.wall_ms = float(out["wall_ms"])
                if rec.length != rec.max_new:
                    rec.error = (f"{rec.length} tokens answered of "
                                 f"{rec.max_new} asked for")
                if keep_tokens:
                    rec.tokens = [int(t) for t in out["tokens"]]
            else:
                rec.error = raw[:200].decode(errors="replace")
        except (OSError, ValueError, KeyError, http.client.HTTPException) as exc:
            rec.answered = self.now()
            rec.status = -1
            rec.error = repr(exc)[:200]
        finally:
            conn.close()

    def start(self, keep_tokens: bool = True) -> None:
        """Start sending; returns at once. ``self.t0`` is the zero."""
        bodies = [json.dumps({
            "prompt": r["prompt"], "max_new_tokens": r["max_new_tokens"],
            "temperature": r["temperature"]}).encode()
            for r in self.schedule]
        for rec, r in zip(self.requests, self.schedule):
            rec.prompt = r["prompt"]
        order = sorted(range(len(self.schedule)),
                       key=lambda i: self.schedule[i]["due"])
        self.t0 = time.monotonic()

        def dispatch() -> None:
            for i in order:
                wait = self.schedule[i]["due"] - self.now()
                if wait > 0 and self._stop.wait(wait):
                    return
                if self._stop.is_set():
                    return
                t = threading.Thread(
                    target=self._one,
                    args=(self.requests[i], bodies[i], keep_tokens),
                    daemon=True)
                self._threads.append(t)
                t.start()

        self._dispatcher = threading.Thread(target=dispatch, daemon=True)
        self._dispatcher.start()

    def wait_until(self, t: float) -> None:
        wait = t - self.now()
        if wait > 0:
            time.sleep(wait)

    def drain(self, predicate, limit_s: float) -> bool:
        """Wait until every request that ``predicate`` picks has ended, at
        most ``limit_s``. True when all ended."""
        deadline = time.monotonic() + limit_s
        while time.monotonic() < deadline:
            if all(r.status is not None
                   for r in self.requests if predicate(r)):
                return True
            time.sleep(0.02)
        return False

    def stop(self) -> None:
        """Send nothing more (requests in flight end by themselves once
        the server answers or closes)."""
        self._stop.set()
        self._dispatcher.join(timeout=5)

    def join(self, limit_s: float) -> None:
        deadline = time.monotonic() + limit_s
        for t in list(self._threads):
            t.join(timeout=max(0.0, deadline - time.monotonic()))


def get_json(host: str, port: int, path: str, timeout: float = 2.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return json.loads(resp.read())
    finally:
        conn.close()


def post_json(host: str, port: int, path: str, body: dict,
              timeout: float = 300.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()
