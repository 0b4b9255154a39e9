"""The plain reference: exact nearest neighbours by brute force.

Scores follow the served convention for L2, similarity
``s = -||q - x||^2`` (larger is nearer). The ground truth is computed on
the host in blocks: a float32 pass picks ``refine`` candidates per
query, and float64 distances order them exactly. Nothing here imports
the program.
"""
from __future__ import annotations

import numpy as np


def exact_scores(queries: np.ndarray, x: np.ndarray,
                 ids: np.ndarray) -> np.ndarray:
    """Float64 ``-||q_i - x[ids[i, j]]||^2`` for each row's ids (ids
    must be valid rows of ``x``)."""
    q = np.asarray(queries, np.float64)[:, None, :]
    v = np.asarray(x[ids], np.float64)
    return -np.sum((q - v) ** 2, axis=-1)


def exact_topk(queries: np.ndarray, x: np.ndarray, k: int, *,
               block: int = 1024, refine: int = 64):
    """``(ids [B, k] int64, scores [B, k] float64)`` best-first."""
    xf = np.asarray(x, np.float32)
    xn = np.sum(xf.astype(np.float64) ** 2, axis=1).astype(np.float32)
    refine = min(max(refine, k), xf.shape[0])
    out_ids, out_scores = [], []
    for lo in range(0, len(queries), block):
        qb = np.asarray(queries[lo: lo + block], np.float32)
        approx = 2.0 * qb @ xf.T - xn[None, :]      # -||q-x||^2 + ||q||^2
        cand = np.argpartition(-approx, refine - 1, axis=1)[:, :refine]
        s = exact_scores(qb, xf, cand)
        order = np.argsort(-s, axis=1, kind="stable")[:, :k]
        out_ids.append(np.take_along_axis(cand, order, axis=1))
        out_scores.append(np.take_along_axis(s, order, axis=1))
    return (np.concatenate(out_ids).astype(np.int64),
            np.concatenate(out_scores))


class Bf16BruteForce:
    """The control: the reference put in the program's place, computed
    one precision step below float32 — vectors and queries in bfloat16,
    products accumulated in float32, on the device. It answers through
    the same future surface as the program (``search`` /
    ``search_batch`` returning objects with ``result()`` and
    ``add_done_callback``), resolved before they are returned."""

    def __init__(self, x: np.ndarray):
        import jax
        import jax.numpy as jnp

        xb = jnp.asarray(x, jnp.bfloat16)
        xn = jnp.sum(xb.astype(jnp.float32) ** 2, axis=1)

        def topk(q, k):
            qb = q.astype(jnp.bfloat16)
            dots = jnp.dot(qb, xb.T, preferred_element_type=jnp.float32)
            qn = jnp.sum(qb.astype(jnp.float32) ** 2, axis=1)
            s = 2.0 * dots - qn[:, None] - xn[None, :]
            return jax.lax.top_k(s, k)

        self._topk = jax.jit(topk, static_argnums=1)

    def search_batch(self, queries: np.ndarray, k: int = 10):
        scores, ids = self._topk(np.asarray(queries, np.float32), k)
        scores, ids = np.asarray(scores), np.asarray(ids)
        return [_Done(ids[i].astype(np.int64), scores[i])
                for i in range(len(ids))]

    def search(self, query: np.ndarray, k: int = 10):
        return self.search_batch(np.asarray(query)[None, :], k)[0]


class _Done:
    """A resolved future carrying ``ids`` and ``scores``."""

    def __init__(self, ids, scores):
        self.ids = ids
        self.scores = scores

    def result(self, timeout=None):
        return self

    def add_done_callback(self, fn) -> None:
        fn(self)
