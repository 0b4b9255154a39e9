"""Faults planted in the program's timed path, to show that the check
catches each one.

Each is armed only while the measured window runs, so set-up and
warm-up run sound, as they would before a fault shows in service:

* ``alter_id`` — the walk's best id of every partial replaced by its
  neighbour in id order, where the executor produces it (an answer
  altered where it is produced; on a quantized engine the host rerank
  then scores the wrong row exactly);
* ``half_batch`` — the walk answers only the first half of each
  executor batch (half of the batch left out);
* ``drop_half_partials`` — the coordinator's merge keeps only the first
  half (rounded up) of a query's shard partials, in shard order.
"""
from __future__ import annotations

import contextlib

FAULTS = ("alter_id", "half_batch", "drop_half_partials")


@contextlib.contextmanager
def planted(name: str, n: int):
    """Plant fault ``name`` for the windows driven inside the block;
    ``n`` is the number of indexed vectors."""
    from benchlib import runner
    from repro.serving import engine as E

    if name not in FAULTS:
        raise ValueError(f"no fault {name!r}; there are {FAULTS}")
    armed = []
    saved = []

    def patch(obj, attr, new):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "drop_half_partials":
        merge = E.merge_topk_np

        def broken_merge(scores, ids, *, k, alive=None):
            if armed:
                # every partial is k wide (k * rerank_factor when
                # quantized), concatenated in shard order
                keep = -(-ids.shape[1] // k // 2) * k
                scores, ids = scores[:, :keep], ids[:, :keep]
                alive = None if alive is None else alive[:, :keep]
            return merge(scores, ids, k=k, alive=alive)
        patch(E, "merge_topk_np", broken_merge)
    else:
        walk = E.Executor._search

        def broken_walk(self, batch):
            outs = walk(self, batch)
            if not armed:
                return outs
            if name == "half_batch":
                return outs[: len(outs) // 2]
            altered = []
            for ids, scores in outs:
                ids = ids.copy()
                ids[0] = (ids[0] + 1) % n
                altered.append((ids, scores))
            return altered
        patch(E.Executor, "_search", broken_walk)

    for drive in ("run_open", "run_closed"):
        def window(*a, _drive=getattr(runner, drive), **kw):
            armed.append(True)
            try:
                return _drive(*a, **kw)
            finally:
                armed.clear()
        patch(runner, drive, window)
    try:
        yield
    finally:
        for obj, attr, old in reversed(saved):
            setattr(obj, attr, old)
