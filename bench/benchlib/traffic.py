"""One generator for every traffic mix: what is sent, and when.

A mix is a JSON file of parameters. ``"loop": "open"`` replays a fixed
arrival trace of single queries; ``"loop": "closed"`` keeps ``callers``
batch calls of ``batch`` queries in flight.

Every seed sends the same work. An open mix's trace is drawn once from
the mix's own ``schedule_seed`` and its requests are the first
``rate * seconds`` queries of the configuration's fixed query set;
``--seed`` only permutes which of those queries fills which arrival
slot. A closed mix walks the whole query set in a seed-permuted order,
wrapping at its end.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class OpenSchedule:
    due_s: np.ndarray          # [N] offsets from the window's start
    query_idx: np.ndarray      # [N] row of the query set sent at each


def arrival_offsets(n: int, seconds: float, schedule_seed: int,
                    burst: dict | None = None) -> np.ndarray:
    """``n`` arrival offsets in ``[0, seconds)``.

    Poisson arrivals conditioned on ``n`` of them in the window:
    unit-rate exponential gaps from ``schedule_seed``, scaled so that
    the ``n+1``-th arrival lands at ``seconds``. With ``burst`` =
    ``{"on_s": a, "off_s": b}`` the same arrivals are squeezed into the
    "on" phases of an on/off cycle of period ``a + b`` (same mean rate,
    arrivals ``(a+b)/a`` times denser while on).
    """
    rng = np.random.default_rng(schedule_seed)
    gaps = rng.exponential(1.0, size=n + 1)
    cum = np.cumsum(gaps)
    t = seconds * cum[:n] / cum[n]
    if burst:
        on, off = float(burst["on_s"]), float(burst["off_s"])
        busy = seconds * on / (on + off)       # total "on" time
        u = t * busy / seconds                 # position in on-time
        cycle = np.floor(u / on)
        t = cycle * (on + off) + (u - cycle * on)
    return t


def open_schedule(mix: dict, seconds: float, seed: int,
                  n_queries: int) -> OpenSchedule:
    n = int(round(mix["rate_qps"] * seconds))
    if n > n_queries:
        raise ValueError(f"{n} requests need more than the {n_queries} "
                         "queries of the set")
    due = arrival_offsets(n, seconds, mix["schedule_seed"],
                          mix.get("burst"))
    perm = np.random.default_rng(seed).permutation(n)
    return OpenSchedule(due_s=due, query_idx=perm)


def closed_order(seed: int, n_queries: int) -> np.ndarray:
    """The seed-permuted order in which a closed mix walks the set."""
    return np.random.default_rng(seed).permutation(n_queries)
