"""The comparison that decides ``correct``.

Every request due in the window is judged, once the window has closed
and each answer has come or the wait has run out:

* ``unanswered`` — requests with no answer, or one that failed (exact:
  limit 0);
* ``malformed`` — answers that are not ``k`` distinct valid ids with
  non-increasing scores (exact: limit 0);
* ``top1_missed`` — the share of well-formed answers that leave out
  the reference's nearest neighbour: it judges which ids came back,
  where ``score_gap`` judges only the scores beside them. The
  configuration's ``check.top1_missed_limit`` bounds it;
* ``score_gap`` — the widest gap between a served score and the
  reference's float64 score of the same id, as a share of the
  reference's k-th best distance for that query. The configuration's
  ``check.score_gap_limit`` bounds it.

``recall_at_10`` is reported beside these as an end-to-end metric with
its own bound; it is not a number of the check (see PERF.md).
"""
from __future__ import annotations

import numpy as np

from benchlib.reference import exact_scores

_BLOCK = 4096   # answers scored against the reference at a time


def _well_formed(ids: np.ndarray, scores: np.ndarray, k: int,
                 n: int) -> bool:
    return (ids.shape == (k,) and scores.shape == (k,)
            and bool(np.all((ids >= 0) & (ids < n)))
            and len(np.unique(ids)) == k
            and not np.any(np.diff(scores) > 0))


def judge(answers, query_idx, queries, x, truth_ids, truth_scores,
          k: int, limits: dict) -> dict:
    """``answers[i]`` is ``(ids, scores)`` or ``None`` for request ``i``,
    which asked for row ``query_idx[i]`` of ``queries``; ``truth_ids``
    and ``truth_scores`` are the reference's exact top-k of every row
    of ``queries``. Returns
    ``{"numbers": {name: (value, limit)}, "correct": bool}``."""
    n = x.shape[0]
    unanswered = sum(a is None for a in answers)
    rows, ids, scores = [], [], []
    for i, ans in enumerate(answers):
        if ans is None:
            continue
        a_ids = np.asarray(ans[0]).astype(np.int64)
        a_scores = np.asarray(ans[1], np.float64)
        if _well_formed(a_ids, a_scores, k, n):
            rows.append(int(query_idx[i]))
            ids.append(a_ids)
            scores.append(a_scores)
    malformed = len(answers) - unanswered - len(rows)
    missed = sum(int(truth_ids[r, 0]) not in a for r, a in zip(rows, ids))
    top1_missed = missed / len(rows) if rows else 1.0
    gap = 0.0
    for lo in range(0, len(rows), _BLOCK):
        r = np.asarray(rows[lo: lo + _BLOCK])
        ref = exact_scores(queries[r], x, np.stack(ids[lo: lo + _BLOCK]))
        scale = np.maximum(np.abs(truth_scores[r, k - 1]), 1e-12)
        err = np.abs(np.stack(scores[lo: lo + _BLOCK]) - ref)
        gap = max(gap, float(np.max(err / scale[:, None])))
    numbers = {
        "unanswered": (unanswered, 0),
        "malformed": (malformed, 0),
        "top1_missed": (top1_missed, limits["top1_missed_limit"]),
        "score_gap": (gap, limits["score_gap_limit"]),
    }
    correct = (unanswered == 0 and malformed == 0 and len(rows) > 0
               and top1_missed <= limits["top1_missed_limit"]
               and gap <= limits["score_gap_limit"])
    return {"numbers": numbers, "correct": correct}


def recall_at_k(answers, query_idx, truth_ids, k: int):
    """Mean recall@k of the answered requests against the exact top-k,
    or ``None`` when nothing was answered."""
    hits, judged = 0, 0
    for i, ans in enumerate(answers):
        if ans is None:
            continue
        got = set(int(v) for v in np.asarray(ans[0])[:k])
        hits += len(got & set(truth_ids[int(query_idx[i])][:k].tolist()))
        judged += 1
    return hits / (judged * k) if judged else None
