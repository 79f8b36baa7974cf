"""Arrival processes for open-loop traffic.

``constant`` and ``mmpp`` are copies of ``repro.launch.loadgen``'s
generators, kept here so that a change to the program cannot change the
traffic.  ``poisson`` gives every seed the same multiset of exponential
gaps (their quantiles at ``(i + 0.5) / CYCLE``), each cycle of ``CYCLE``
gaps in its own seeded order: the offered work is then the same on every
seed and only its order differs.
"""
from __future__ import annotations

import numpy as np

CYCLE = 64


def poisson_arrivals(rate: float, n: int, rng) -> np.ndarray:
    """``n`` arrival times: Exp(1/rate) gaps taken at fixed quantiles,
    shuffled per cycle."""
    if rate <= 0:
        raise ValueError("rate must be > 0")
    gaps = -np.log1p(-(np.arange(CYCLE) + 0.5) / CYCLE) / rate
    reps = -(-int(n) // CYCLE)
    out = np.concatenate([rng.permutation(gaps) for _ in range(reps)])
    return np.cumsum(out[:int(n)])


def constant_arrivals(rate: float, n: int, rng=None) -> np.ndarray:
    """Deterministic spacing: arrival i at ``(i + 1) / rate``."""
    if rate <= 0:
        raise ValueError("rate must be > 0")
    return (np.arange(int(n), dtype=np.float64) + 1.0) / rate


def mmpp_arrivals(rate: float, n: int, rng, burst: float = 4.0,
                  dwell: float = 8.0) -> np.ndarray:
    """2-state Markov-modulated Poisson process: a hot state (rate
    ``burst * b``) and a cold one (rate ``b / burst``) with Exp(``dwell``)
    dwell times; ``b`` makes the long-run mean rate equal ``rate``."""
    if rate <= 0 or burst < 1.0 or dwell <= 0:
        raise ValueError("need rate > 0, burst >= 1, dwell > 0")
    b = 2.0 * rate / (burst + 1.0 / burst)
    state_rates = (burst * b, b / burst)
    out: list[float] = []
    t, state = 0.0, 0
    while len(out) < n:
        t_end = t + rng.exponential(dwell)
        r = state_rates[state]
        while len(out) < n:
            t_next = t + rng.exponential(1.0 / r)
            if t_next > t_end:
                break
            out.append(t_next)
            t = t_next
        t = t_end
        state = 1 - state
    return np.asarray(out, dtype=np.float64)


def arrivals(spec: dict, n: int, rng) -> np.ndarray:
    """Arrival times (seconds from the window's start) for a traffic file's
    ``arrivals`` entry: ``{"process": ..., "rate": ..., other params}``."""
    kw = {k: v for k, v in spec.items() if k not in ("process", "rate")}
    process = spec["process"]
    if process == "poisson":
        return poisson_arrivals(spec["rate"], n, rng, **kw)
    if process == "constant":
        return constant_arrivals(spec["rate"], n, rng, **kw)
    if process == "mmpp":
        return mmpp_arrivals(spec["rate"], n, rng, **kw)
    raise ValueError(f"unknown arrival process {process!r}")
