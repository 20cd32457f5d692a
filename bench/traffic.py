"""The one traffic generator: every mix is a data file of its parameters.

A mix file (``bench/traffic/<name>.json``) holds::

    {"arrivals": {"kind": "poisson", "rate": 3.0}
              or {"kind": "bursty", "rate": 3.0, "period_s": 8,
                  "burst_s": 2, "burst_factor": 4},
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.7,
                "min": 64, "max": 2048},
     "output": {... the same keys ...},
     "schedule_seed": 1,
     "lead_in_s": 5,
     "trace": {"start_s": 10, "seconds": 3}}

``rate`` is the mean number of requests per second, fixed once from a sweep
on the chip; it is never recomputed at run time. The generator fixes the
*count* of requests due in the lead-in and in the window (the integral of the
rate over each), their set of inter-arrival gaps, and their lengths (the
stratified quantiles of the stated distributions). ``schedule_seed``, part of
the mix, orders the gaps and pairs the lengths with the arrivals, so every
run of a mix serves one schedule: the same requests, of the same sizes, due
at the same times. A window of ~40 long-lived requests never reaches a steady
state, so where each long request falls decides the window's work; a run seed
that reordered them changed the work from seed to seed. The run seed draws
the prompt tokens: uniform ids over the vocabulary.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List

import numpy as np
from scipy.special import ndtri


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float            # seconds from the window's start (< 0: lead-in)
    prompt: tuple
    max_new_tokens: int
    in_window: bool


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    for k in ("arrivals", "prompt", "output", "schedule_seed", "lead_in_s"):
        if k not in mix:
            raise ValueError(f"{path}: traffic mix lacks {k!r}")
    return mix


def intensity(arr: dict):
    """Rate function r(t) of the arrival process, t in seconds from the
    window's start; periodic bursts start at t = 0 mod period."""
    kind = arr["kind"]
    if kind == "poisson":
        return lambda t: arr["rate"]
    if kind == "bursty":
        p, b, f = arr["period_s"], arr["burst_s"], arr["burst_factor"]
        base = arr["rate"] * p / (f * b + (p - b))   # mean over a period = rate
        return lambda t: base * (f if (t % p) < b else 1.0)
    raise ValueError(f"unknown arrival kind {kind!r}")


def _times(rate_fn, t0: float, t1: float, rng, step: float = 1e-3):
    """Arrival times in [t0, t1). The count is fixed to the integral of the
    rate, and the gaps between arrivals in unit-rate time are the stratified
    quantiles of the exponential distribution, in an order drawn by
    ``rng``. The cumulative rate then maps unit-rate time onto the clock (a Poisson process where the rate is
    constant, bursts where it is not)."""
    grid = np.arange(t0, t1 + step / 2, step)
    r = np.array([rate_fn(t) for t in grid[:-1]])
    cum = np.concatenate([[0.0], np.cumsum(r * step)])
    n = int(round(cum[-1]))
    if n == 0:
        return np.zeros(0), 0
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    c = gaps.mean() / 2
    u = (np.concatenate([[0.0], np.cumsum(gaps[:-1])]) + c) \
        * cum[-1] / (gaps.sum() + 2 * c)
    return np.interp(u, cum, grid), n


def lengths(dist: dict, n: int, rng) -> np.ndarray:
    """Stratified quantiles of a clipped lognormal, shuffled by ``rng``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    q = (np.arange(n) + 0.5) / max(n, 1)
    x = dist["median"] * np.exp(dist["sigma"] * ndtri(q))
    x = np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)
    return rng.permutation(x)


def generate(mix: dict, seconds: float, vocab: int, seed: int) -> List[Arrival]:
    """Every request of one run, in due order: the lead-in's, then the
    window's. The schedule (due times, prompt and output lengths) follows
    from the mix alone; ``seed`` draws the prompt tokens."""
    sched = np.random.default_rng(int(mix["schedule_seed"]))
    rng = np.random.default_rng(seed)
    rate_fn = intensity(mix["arrivals"])
    out: List[Arrival] = []
    for t0, t1, in_window in ((-float(mix["lead_in_s"]), 0.0, False),
                              (0.0, float(seconds), True)):
        times, n = _times(rate_fn, t0, t1, sched)
        p_len = lengths(mix["prompt"], n, sched)
        o_len = lengths(mix["output"], n, sched)
        for t, pl, ol in zip(times, p_len, o_len):
            toks = tuple(int(x) for x in rng.integers(0, vocab, size=int(pl)))
            out.append(Arrival(float(t), toks, int(ol), in_window))
    return out

