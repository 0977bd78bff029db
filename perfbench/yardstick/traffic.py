"""The one general traffic generator. A traffic mix is a data file of
parameters under ``traffic/``; this turns (file, seed, seconds) into the
requests or records of a run, and is a pure function of them.

Every seed gets the SAME set of sizes and the same set of arrival gaps, in
another order: lengths are the n evenly spaced quantiles of the file's
distribution, gaps the n evenly spaced quantiles of the exponential,
scaled so that they fill the span exactly, and the triples (gap, prompt
length, output length) stand in ONE order that the traffic file's own
``order_seed`` draws. A run's seed draws the token ids and, where the file
says ``seed_turns_order``, turns that circle to another starting point. So
every run of a cell offers the same work, the same number of requests and
the same neighbours around each request; a tail then moves with the system
and not with which long prompts happened to meet (measured, PR 24: free
reshuffling by the seed spread a p90 of time to first token by 13%, two
runs of one seed by under 1%; above the knee even the turn moved the rate
by 3%, because it changes which whole answers land inside the window).
Needs numpy only."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    # --seed may exceed 32 signed bits; SeedSequence takes any whole number.
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5)/n of a clipped lognormal."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu = math.log(spec["median"])
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.exp(mu + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(n: int, span_s: float) -> np.ndarray:
    """n exponential inter-arrival gaps at evenly spaced quantiles, scaled
    to sum to ``span_s``: a Poisson process's gaps with the sampling noise
    of their sum taken out."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (span_s / gaps.sum())


def _phase(traffic: dict, seed: int, stream: int, start: float,
           span_s: float, vocab: int) -> list[dict]:
    n = int(round(traffic["rate_rps"] * span_s))
    if n < 1:
        return []
    order = _rng(int(traffic["order_seed"]), stream)
    rng = _rng(seed, stream)
    turn = int(rng.integers(0, n)) if traffic["seed_turns_order"] else 0
    gaps = np.roll(order.permutation(quantile_gaps(n, span_s)), turn)
    # A request is due at the END of its gap less half of it, so that the
    # first is not at 0 and the last not at the span's end.
    due = start + np.cumsum(gaps) - gaps / 2.0
    prompts = np.roll(order.permutation(
        quantile_lengths(traffic["prompt_len"], n)), turn)
    outputs = np.roll(order.permutation(
        quantile_lengths(traffic["output_len"], n)), turn)
    out = []
    for i in range(n):
        out.append({
            "due": float(due[i]),
            "prompt": rng.integers(0, vocab, size=int(prompts[i])).tolist(),
            "max_new_tokens": int(outputs[i]),
            "temperature": float(traffic["temperature"]),
        })
    return out


def serving_schedule(traffic: dict, seed: int, seconds: float,
                     vocab: int) -> dict:
    """Requests of one run, due times in seconds from the schedule's zero.
    The window is [preroll_s, preroll_s + seconds). Requests due in the
    pre-roll fill the engine before timing starts."""
    pre = float(traffic["preroll_s"])
    requests = (_phase(traffic, seed, 1, 0.0, pre, vocab)
                + _phase(traffic, seed, 2, pre, float(seconds), vocab))
    for i, r in enumerate(requests):
        r["index"] = i
    return {"window": (pre, pre + float(seconds)), "requests": requests}


def warmup_requests(traffic: dict, vocab: int) -> list[dict]:
    """The fixed warm-up list, the same for every seed: one request per
    listed prompt length, a few tokens each, so that every program shape
    the window can reach is compiled or loaded before it."""
    rng = _rng(0, 7)
    return [{"prompt": rng.integers(0, vocab, size=int(n)).tolist(),
             "max_new_tokens": int(traffic["warmup"]["max_new_tokens"]),
             "temperature": 0.0}
            for n in traffic["warmup"]["prompt_lens"]]


def training_records(traffic: dict, seed: int, vocab: int) -> np.ndarray:
    """Token records [records, seq + 1] uint16, every row different."""
    rng = _rng(seed, 3)
    return rng.integers(
        0, vocab, size=(int(traffic["records"]), int(traffic["seq"]) + 1),
        dtype=np.uint16)
