"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Each reader file is one metric, found by its name; it calls one of
these on the :class:`benchlib.runner.Run` it is given and returns a
number, or ``None`` where the run holds nothing to read.
"""
from __future__ import annotations

from typing import Optional

from benchlib import stats as ST

WALK_PROGRAM = "jit_hnsw_search"       # the beam walk's jitted program
ROUTE_PROGRAM = "jit__route_queries"   # the meta-HNSW routing program


def latency_ms(run, q: float) -> Optional[float]:
    """Percentile of due-to-answer latency over every request of an
    open-loop window (closed loops have no due times of their own)."""
    if run.cell.traffic["loop"] != "open" or not run.window.attempted:
        return None
    return ST.percentile(ST.latencies_ms(run.window.due, run.window.done),
                         q)


def qps(run) -> Optional[float]:
    w = run.window
    q = ST.rate(w.done, w.t_start, w.t_end)
    return q if q > 0 else None


def _spans(run, name):
    return [s for s in run.spans if s.name == name and s.t1 is not None]


def self_ms(run, name: str) -> Optional[float]:
    """Mean self time of ``name`` spans (duration less the part its
    child spans cover), in ms per span."""
    spans = _spans(run, name)
    if not spans:
        return None
    ids = {s.span_id for s in spans}
    child = {}
    for s in run.spans:
        if s.parent_id in ids and s.t1 is not None:
            child[s.parent_id] = child.get(s.parent_id, 0.0) + s.duration
    total = sum(s.duration - child.get(s.span_id, 0.0) for s in spans)
    return total / len(spans) * 1e3


def batch_fill(run) -> Optional[float]:
    """Real queries per walk launch over the launch's padded rows."""
    spans = _spans(run, "kernel.beam_walk")
    if not spans:
        return None
    rows = run.cell.config["engine"]["executor_batch"]
    return sum(s.attrs["batch"] for s in spans) / (len(spans) * rows)


def redispatch_share(run) -> Optional[float]:
    """Shard dispatches sent again (hedge or recovery) per primary
    shard dispatch, over the window."""
    primary = len([s for s in run.spans if s.name == "dispatch"])
    if not primary:
        return None
    again = (run.stats_after["redispatched"]
             - run.stats_before["redispatched"])
    return again / primary


def program_ms(run, program: str) -> Optional[float]:
    """Device time per execution of a program in the traced slice."""
    if run.trace is None or program not in run.trace["programs"]:
        return None
    count, seconds = run.trace["programs"][program]
    return seconds / count * 1e3


def idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
