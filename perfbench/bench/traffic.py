"""Seeded open-loop traffic from a cell's data file.

Every seed gets the same multiset of inter-arrival gaps, prompt lengths and
relative deadlines, drawn at fixed quantiles of their distributions, in an
order drawn from the seed.  So two seeds offer the same work in another
order, and the spread between seeds is the system's, not the draw's.

Arrival processes (the rate arithmetic follows the program's
``repro.serving.traffic.generators``):

* ``poisson``: exponential gaps at ``rate`` per second;
* ``flash-crowd``: ``base_rate`` outside ``[spike_at, spike_at +
  spike_len)`` (fractions of the window) and ``spike_rate`` inside it.

Prompt lengths: ``lognormal`` with ``median`` and ``sigma``, clipped to
``[min, max]``; or ``fixed``.  Deadlines: uniform in ``[lo_ms, hi_ms]``.
"""
from __future__ import annotations

import statistics

import numpy as np


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose)."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tag]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps at ``rate``, at fixed quantiles (ascending)."""
    return -np.log1p(-_quantiles(n)) / rate


def _segment(rate: float, t0: float, t1: float, rng) -> np.ndarray:
    """Arrivals in ``[t0, t1)``: ``round(rate * span)`` of them, spaced by a
    seeded order of fixed-quantile exponential gaps, rescaled so the span
    holds them whole."""
    n = int(round(rate * (t1 - t0)))
    if n <= 0:
        return np.empty(0)
    g = rng.permutation(exp_gaps(rate, n))
    t = np.cumsum(g)
    # the (n+1)-th gap would end the span: scale the n gaps to leave room
    t *= (t1 - t0) / (t[-1] + float(np.mean(g)))
    return t0 + t


def arrivals(spec: dict, seconds: float, rng) -> np.ndarray:
    """Sorted arrival offsets in ``[0, seconds)`` for an arrival ``spec``."""
    kind = spec["kind"]
    if kind == "poisson":
        return _segment(float(spec["rate"]), 0.0, seconds, rng)
    if kind == "flash-crowd":
        a = float(spec["spike_at"]) * seconds
        b = a + float(spec["spike_len"]) * seconds
        parts = [_segment(float(spec["base_rate"]), 0.0, a, rng),
                 _segment(float(spec["spike_rate"]), a, b, rng),
                 _segment(float(spec["base_rate"]), b, seconds, rng)]
        return np.concatenate(parts)
    raise ValueError(f"unknown arrival kind {kind!r}")


def prompt_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` prompt lengths (int) at fixed quantiles, in a seeded order."""
    kind = spec["kind"]
    if kind == "fixed":
        return np.full(n, int(spec["length"]), np.int64)
    if kind != "lognormal":
        raise ValueError(f"unknown length kind {kind!r}")
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    ln = np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    ln = np.clip(ln, int(spec["min"]), int(spec["max"])).astype(np.int64)
    return rng.permutation(ln)


def deadlines_s(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` relative deadlines in seconds, uniform quantiles, seeded order."""
    lo, hi = float(spec["lo_ms"]) / 1e3, float(spec["hi_ms"]) / 1e3
    return rng.permutation(lo + (hi - lo) * _quantiles(n))


def bucket_for(n: int, buckets) -> int:
    for b in sorted(buckets):
        if n <= b:
            return int(b)
    raise ValueError(f"{n} exceeds buckets {list(buckets)}")


def make_requests(traffic: dict, seconds: float, seed: int, *,
                  vocab: int) -> list:
    """The window's requests as plain dicts: ``offset`` (s), ``rel`` (s),
    ``seq_len``, ``bucket`` and ``inputs`` (token ids, left-padded with 0
    to the length bucket)."""
    t = arrivals(traffic["arrivals"], seconds, seed_rng(seed, "arrivals"))
    n = len(t)
    lens = prompt_lengths(traffic["prompt_len"], n, seed_rng(seed, "lengths"))
    rels = deadlines_s(traffic["deadline"], n, seed_rng(seed, "deadlines"))
    rng = seed_rng(seed, "contents")
    out = []
    for i in range(n):
        L = int(lens[i])
        b = bucket_for(L, traffic["len_buckets"])
        x = np.zeros(b, np.int32)
        x[b - L:] = rng.integers(1, vocab, size=L)
        out.append(dict(offset=float(t[i]), rel=float(rels[i]), seq_len=L,
                        bucket=b, inputs=x, index=i))
    return out

