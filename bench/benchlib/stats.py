"""Arithmetic of the window's record: latency percentiles from due
times, rates, and the spread of repeated runs."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence

import numpy as np


def latencies_ms(due: np.ndarray, done: np.ndarray) -> np.ndarray:
    """Per-request latency from its due time to its result, in ms; a
    request that never answered (``done`` is NaN) counts as infinitely
    late, so it misses every latency limit."""
    lat = (np.asarray(done, np.float64) - np.asarray(due, np.float64))
    return np.where(np.isnan(lat), np.inf, lat * 1e3)


def percentile(values: np.ndarray, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default), infinite values kept in place."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    pos = (v.size - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or v[lo] == v[hi]:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def rate(done: np.ndarray, t0: float, t1: float) -> float:
    """Completions inside ``[t0, t1]`` per second of it."""
    d = np.asarray(done, np.float64)
    return float(np.sum((d >= t0) & (d <= t1))) / (t1 - t0)


def spread(values: Sequence[float]) -> Optional[float]:
    """Interquartile distance as a share of the median, with Python's
    ``statistics.quantiles(values, n=4)`` quartiles."""
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else None


def overshoot(win) -> dict:
    """How long the open-loop generator lost to neither a due time nor
    its own previous call: request ``i`` was sent at ``submitted[i]``,
    though the generator was free from ``max(due[i], returned[i-1])``.
    Its median and largest value in ms, and when the largest began."""
    sub = np.asarray(win.submitted, np.float64)
    if sub.size == 0:
        return {"p50_ms": 0.0, "max_ms": 0.0, "at_s": 0.0}
    prev = np.concatenate([[-np.inf], np.asarray(win.returned)[:-1]])
    free = np.maximum(np.asarray(win.due, np.float64),
                      np.nan_to_num(prev, nan=-np.inf))
    over = (sub - free) * 1e3
    i = int(np.nanargmax(over))
    return {"p50_ms": float(np.nanmedian(over)), "max_ms": float(over[i]),
            "at_s": float(free[i] - win.t_start)}
