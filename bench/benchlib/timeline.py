"""Shared arithmetic of the readers that time a query's phases from
the program's own spans (``repro.obs.Tracer``, monotonic clock).

Each function takes a :class:`benchlib.runner.Run` and returns a number,
or ``None`` where the run holds nothing to read: a program without the
span or attribute reads ``None``, never raises.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from benchlib.devtrace import _union as union
from benchlib.readers import _spans as finished

QUERY = "query"                      # a query's root span, routed to answered
QUEUED = "executor.queued"           # a shard copy's wait in its topic queue
ROUTE = "coordinator.route"          # routing, launch to mask on the host
WALK = "kernel.beam_walk"            # one walk launch, launch to results on host


def mean_ms(run, name: str) -> Optional[float]:
    """Mean duration of the ``name`` spans, in ms."""
    spans = finished(run, name)
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(spans) * 1e3


def open_intervals(run, names: Sequence[str], t0: float,
                   t1: float) -> List[List[float]]:
    """Union of the intervals in which a span of ``names`` is open,
    clipped to ``[t0, t1]``."""
    out = []
    for name in names:
        for s in finished(run, name):
            a, b = max(s.t0, t0), min(s.t1, t1)
            if b > a:
                out.append((a, b))
    return union(out)


def overlap(a: List[List[float]], b: List[List[float]]) -> float:
    """Length of the intersection of two unions (each sorted, disjoint)."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def open_share(run, inside: str, outside: Sequence[str]) -> Optional[float]:
    """Share of the window in which an ``inside`` span is open and no
    span of ``outside`` is."""
    w0, w1 = run.window.t_start, run.window.t_end
    if w1 <= w0:
        return None
    ins = open_intervals(run, (inside,), w0, w1)
    if not ins:
        return None
    held = (sum(e - s for s, e in ins)
            - overlap(ins, open_intervals(run, outside, w0, w1)))
    return held / (w1 - w0)


def walk_useful_share(run) -> Optional[float]:
    """Expansions of the launches' real rows over the rows the walk
    carried: Σ ``expansions`` ÷ Σ (``trips`` × executor batch)."""
    walks = [s for s in finished(run, WALK)
             if "expansions" in s.attrs and "trips" in s.attrs]
    rows = run.cell.config["engine"]["executor_batch"]
    carried = sum(s.attrs["trips"] for s in walks) * rows
    if not carried:
        return None
    return sum(s.attrs["expansions"] for s in walks) / carried
