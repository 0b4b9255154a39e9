"""Drive a window of traffic through a client and record it.

A client is anything with ``search(q, k)`` and ``search_batch(Q, k)``
returning futures with ``add_done_callback`` and ``result(timeout)``,
whose result has ``ids`` and ``scores``: the program's
``PyramidClient``, or the control in its place. All times are
``time.monotonic()`` seconds.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Window:
    t_start: float
    t_end: float
    due: np.ndarray            # [N] when each request was due
    submitted: np.ndarray      # [N] when it was handed to the client
    returned: np.ndarray       # [N] when the client's call returned
    done: np.ndarray           # [N] when its answer came (NaN: never)
    query_idx: np.ndarray      # [N] row of the query set it asked for
    answers: List[Optional[tuple]]   # (ids, scores), None if none/failed

    @property
    def attempted(self) -> int:
        return len(self.due)

    @property
    def failed(self) -> int:
        return sum(a is None for a in self.answers)


class _Recorder:
    """Collects completion times from done-callbacks."""

    def __init__(self):
        self.lock = threading.Lock()
        self.done = {}

    def callback(self, i: int):
        def _cb(_fut, i=i):
            t = time.monotonic()
            with self.lock:
                self.done[i] = t
        return _cb


def _collect(futures, deadline: float):
    """Wait for each future until ``deadline``; ``(ids, scores)`` of
    each that answered, ``None`` for one that did not or failed."""
    out = []
    for fut in futures:
        if fut is None:
            out.append(None)
            continue
        try:
            r = fut.result(timeout=max(0.0, deadline - time.monotonic()))
            out.append((np.asarray(r.ids), np.asarray(r.scores)))
        except Exception:   # late, expired or failed: judged unanswered
            out.append(None)
    return out


def run_open(client, queries, due_s, query_idx, k: int, t_start: float,
             seconds: float, close_wait_s: float) -> Window:
    """Send query ``query_idx[i]`` at ``t_start + due_s[i]``, one
    ``search`` call each, from one thread, whatever has come back."""
    n = len(due_s)
    due = t_start + np.asarray(due_s, np.float64)
    submitted = np.full(n, np.nan)
    returned = np.full(n, np.nan)
    rec = _Recorder()
    futures = [None] * n
    for i in range(n):
        wait = due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        submitted[i] = time.monotonic()
        try:
            fut = client.search(queries[query_idx[i]], k)
        except Exception:   # a refused request counts as unanswered
            continue
        finally:
            returned[i] = time.monotonic()
        fut.add_done_callback(rec.callback(i))
        futures[i] = fut
    t_end = t_start + seconds
    answers = _collect(futures, max(t_end, time.monotonic())
                       + close_wait_s)
    return _window(t_start, t_end, due, submitted, returned, rec,
                   query_idx, answers)


def run_closed(client, queries, order, batch: int, callers: int, k: int,
               t_start: float, seconds: float,
               close_wait_s: float) -> Window:
    """``callers`` threads each send ``search_batch`` calls of ``batch``
    queries, taken in turn from ``order`` (wrapping), and wait for every
    answer before the next call. No call starts after the window."""
    t_end = t_start + seconds
    lock = threading.Lock()
    cursor = [0]
    reqs = []        # (query row, submitted) in request order
    futures = []
    returned = {}
    rec = _Recorder()

    def caller():
        while time.monotonic() < t_end:
            with lock:
                lo = cursor[0]
                cursor[0] += batch
                idx = order[np.arange(lo, lo + batch) % len(order)]
                base = len(reqs)
                now = time.monotonic()
                reqs.extend((int(q), now) for q in idx)
                futures.extend([None] * batch)
            try:
                futs = client.search_batch(queries[idx], k)
            except Exception:
                continue
            finally:
                returned[base] = time.monotonic()
            for j, fut in enumerate(futs):
                futures[base + j] = fut
                fut.add_done_callback(rec.callback(base + j))
            deadline = max(t_end, time.monotonic()) + close_wait_s
            for fut in futs:
                try:
                    fut.result(timeout=max(0.0,
                                           deadline - time.monotonic()))
                except Exception:
                    pass

    wait = t_start - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    threads = [threading.Thread(target=caller, name=f"caller-{c}")
               for c in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    query_idx = np.array([q for q, _ in reqs], np.int64)
    submitted = np.array([s for _, s in reqs], np.float64)
    ret = np.full(len(reqs), np.nan)
    for base, t in returned.items():
        ret[base: base + batch] = t
    answers = _collect(futures, time.monotonic())
    return _window(t_start, t_end, submitted, submitted, ret, rec,
                   query_idx, answers)


def _window(t_start, t_end, due, submitted, returned, rec, query_idx,
            answers):
    done = np.full(len(due), np.nan)
    with rec.lock:
        for i, t in rec.done.items():
            done[i] = t
    for i, a in enumerate(answers):
        if a is None:
            done[i] = np.nan
    return Window(t_start, t_end, np.asarray(due), np.asarray(submitted),
                  np.asarray(returned), done, np.asarray(query_idx),
                  answers)
