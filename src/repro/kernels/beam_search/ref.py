"""The fused bottom-layer beam walk: the batched XLA walk that every
backend serves (``beam_search_ref``) and its numpy twin.

The walk is Alg. 1 Search-Level with search factor ``ef`` on the bottom
layer, batched over a stack of graphs: every (graph, slot) pair runs the
EXACT per-query semantics of ``repro.core.hnsw._beam_search_bottom`` —
best-unexpanded selection by masked argmax (ties to the lowest beam
position), neighbour scoring through the graph's own distance
(float32 rows, or dequantize-int8 on the frozen grid of
``repro.core.quant.QuantParams``), visited-set masking, and a
``top_k``-ordered beam merge — but as ONE batched loop over all
``S * C`` rows instead of ``vmap``-of-``while_loop`` per shard.

Semantics shared by both implementations (XLA walk / numpy twin):
  * a row expands exactly one beam entry per iteration while it has any
    unexpanded entry and fewer than ``max_iters`` expansions; finished
    rows are frozen (their state never changes), so the batched loop is
    bit-identical to the per-query ``lax.while_loop`` it replaces;
  * neighbour slots < 0 are adjacency padding and never scored, never
    visited, never enter the beam;
  * the merged beam is sorted best-first with ``lax.top_k`` tie-breaking
    (equal scores keep the lower concatenation position: old beam before
    new neighbours);
  * output is (scores [S, C, ef'], node ids [S, C, ef']) best-first with
    ef' = min(ef, n), padded with (-inf, -1); node ids are LOCAL row
    indices of each graph — callers translate to external ids.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as M
from repro.kernels.quant_distance import quant_scores_np, quant_scores_ref


def _walk_ref(data: jnp.ndarray, bottom: jnp.ndarray, queries: jnp.ndarray,
              entries: jnp.ndarray, *, metric: str, ef: int, max_iters: int,
              scale: Optional[jnp.ndarray], zero: Optional[jnp.ndarray]):
    """Shared walk body; returns (scores, nodes, iters) stacked
    [S, C, ...] with ``iters`` = expansions actually executed per row
    (the roofline's analytic op counts use it)."""
    s, n, d = data.shape
    m0 = bottom.shape[2]
    c = queries.shape[1]
    ef = min(ef, n)
    bsz = s * c

    # flatten the graph stack once; per-row offsets turn local node
    # indices into rows of the flattened tables at gather time, so the
    # whole stack walks in ONE batched loop (no lax.map over shards)
    data_f = data.reshape(s * n, d)
    bottom_f = bottom.reshape(s * n, m0)
    q = queries.reshape(bsz, d).astype(jnp.float32)
    ent = entries.reshape(bsz).astype(jnp.int32)
    off = (jnp.arange(bsz, dtype=jnp.int32) // c) * n
    rows_idx = jnp.arange(bsz)

    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32).reshape(-1)
        zero = jnp.asarray(zero, jnp.float32).reshape(-1)

    def score_rows(rows: jnp.ndarray) -> jnp.ndarray:
        # [bsz, m, d] gathered rows -> [bsz, m]; vmapped row-wise so the
        # dot lowering matches ``score_nodes`` under the per-query walk
        # (bit-identical scores => bit-identical beam decisions)
        if scale is not None:
            return jax.vmap(lambda qv, rv: quant_scores_ref(
                qv[None, :], rv, scale, zero, metric=metric)[0])(q, rows)
        return jax.vmap(lambda qv, rv: M.similarity_matrix(
            qv[None, :], rv, metric)[0])(q, rows)

    visited = jnp.zeros((bsz, n), jnp.bool_).at[rows_idx, ent].set(True)
    beam_i = jnp.full((bsz, ef), -1, jnp.int32).at[:, 0].set(ent)
    e_scores = score_rows(data_f[ent + off][:, None, :])[:, 0]
    beam_s = jnp.full((bsz, ef), -jnp.inf,
                      jnp.float32).at[:, 0].set(e_scores)
    expanded = jnp.zeros((bsz, ef), jnp.bool_)
    iters = jnp.zeros((bsz,), jnp.int32)
    cols = jnp.arange(ef)[None, :]

    def cond(state):
        beam_s, beam_i, expanded, visited, iters, it = state
        live = jnp.logical_and(~expanded, beam_i >= 0)
        return jnp.logical_and(jnp.any(live), it < max_iters)

    def body(state):
        beam_s, beam_i, expanded, visited, iters, it = state
        live = jnp.logical_and(~expanded, beam_i >= 0)
        active = jnp.any(live, axis=1)                       # [bsz]
        # select the best unexpanded beam entry per row
        sel = jnp.where(live, beam_s, -jnp.inf)
        j = jnp.argmax(sel, axis=1)
        node = jnp.take_along_axis(beam_i, j[:, None], axis=1)[:, 0]
        marked = jnp.logical_or(expanded, jnp.logical_and(
            cols == j[:, None], active[:, None]))
        # gather + score its neighbours
        nbrs = bottom_f[jnp.clip(node, 0) + off]             # [bsz, m0]
        nbr_rows = jnp.clip(nbrs, 0)
        seen = jnp.take_along_axis(visited, nbr_rows, axis=1)
        valid = jnp.logical_and(
            jnp.logical_and(nbrs >= 0, ~seen), active[:, None])
        sims = jnp.where(
            valid, score_rows(data_f[nbr_rows + off[:, None]]), -jnp.inf)
        visited = visited.at[rows_idx[:, None], nbr_rows].max(
            jnp.logical_and(nbrs >= 0, active[:, None]))
        # merge into beam: top-ef of (beam ∪ neighbours)
        all_s = jnp.concatenate([beam_s, sims], axis=1)
        all_i = jnp.concatenate([beam_i, jnp.where(valid, nbrs, -1)],
                                axis=1)
        all_e = jnp.concatenate(
            [marked, jnp.zeros((bsz, m0), jnp.bool_)], axis=1)
        top_s, idx = jax.lax.top_k(all_s, ef)
        keep = active[:, None]
        return (jnp.where(keep, top_s, beam_s),
                jnp.where(keep, jnp.take_along_axis(all_i, idx, axis=1),
                          beam_i),
                jnp.where(keep, jnp.take_along_axis(all_e, idx, axis=1),
                          marked),
                visited, iters + active.astype(jnp.int32), it + 1)

    state = (beam_s, beam_i, expanded, visited, iters, jnp.int32(0))
    beam_s, beam_i, _, _, iters, _ = jax.lax.while_loop(cond, body, state)
    return (beam_s.reshape(s, c, ef), beam_i.reshape(s, c, ef),
            iters.reshape(s, c))


def beam_search_ref(data: jnp.ndarray, bottom: jnp.ndarray,
                    queries: jnp.ndarray, entries: jnp.ndarray, *,
                    metric: str, ef: int, max_iters: int,
                    scale: Optional[jnp.ndarray] = None,
                    zero: Optional[jnp.ndarray] = None):
    """Fused bottom-layer beam walk: one batched ``lax.while_loop``.

    Args:
      data: [S, n, d] graph rows — float32, or int8 codes when
        ``scale``/``zero`` are given (frozen-grid dequantize scoring).
      bottom: [S, n, M0] i32 bottom-layer adjacency, -1 padded.
      queries: [S, C, d] float32 (preprocessed) queries per graph slot.
      entries: [S, C] i32 bottom-layer entry node per slot (the greedy
        upper-layer descent stays outside — it is a few cheap steps).
      ef: beam width (clamped to n); max_iters: expansion bound per row.

    Returns (scores [S, C, ef'] f32, nodes [S, C, ef'] i32) best-first,
    (-inf, -1) padded, ef' = min(ef, n); nodes are graph-local rows.
    """
    scores, nodes, _ = _walk_ref(data, bottom, queries, entries,
                                 metric=metric, ef=ef, max_iters=max_iters,
                                 scale=scale, zero=zero)
    return scores, nodes


def beam_search_stats(data, bottom, queries, entries, *, metric: str,
                      ef: int, max_iters: int, scale=None, zero=None):
    """The same walk, also returns per-row expansion counts
    [S, C] i32 — the served op (``ops.beam_search(stats=True)``) reports
    them per launch, and ``benchmarks/roofline.py`` derives its analytic
    FLOP/byte counts from the expansions a workload actually executes."""
    return _walk_ref(jnp.asarray(data), jnp.asarray(bottom),
                     jnp.asarray(queries), jnp.asarray(entries),
                     metric=metric, ef=ef, max_iters=max_iters,
                     scale=scale, zero=zero)


def beam_search_np(data: np.ndarray, bottom: np.ndarray,
                   queries: np.ndarray, entries: np.ndarray, *,
                   metric: str, ef: int, max_iters: int,
                   scale: Optional[np.ndarray] = None,
                   zero: Optional[np.ndarray] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Numpy twin of :func:`beam_search_ref` (per-row Python loop; the
    independent host-side oracle the walk's tests compare against)."""
    data = np.asarray(data)
    bottom = np.asarray(bottom)
    queries = np.asarray(queries, np.float32)
    entries = np.asarray(entries)
    s, n, _ = data.shape
    m0 = bottom.shape[2]
    c = queries.shape[1]
    ef = min(ef, n)
    out_s = np.full((s, c, ef), -np.inf, np.float32)
    out_i = np.full((s, c, ef), -1, np.int32)
    for si in range(s):
        adj = bottom[si]
        codes = data[si]
        for ci in range(c):
            q = queries[si, ci]

            def score(rows_sel):
                if scale is not None:
                    return quant_scores_np(q[None, :], codes[rows_sel],
                                           scale, zero, metric=metric)[0]
                return M.similarity_matrix_np(
                    q[None, :], codes[rows_sel].astype(np.float32),
                    metric)[0]

            e = int(entries[si, ci])
            visited = np.zeros(n, bool)
            visited[e] = True
            beam_s = np.full(ef, -np.inf, np.float32)
            beam_i = np.full(ef, -1, np.int32)
            expanded = np.zeros(ef, bool)
            beam_s[0] = score(np.asarray([e]))[0]
            beam_i[0] = e
            for _ in range(max_iters):
                live = ~expanded & (beam_i >= 0)
                if not live.any():
                    break
                j = int(np.argmax(np.where(live, beam_s, -np.inf)))
                node = int(beam_i[j])
                expanded[j] = True
                nbrs = adj[node]
                rows_sel = np.clip(nbrs, 0, n - 1)
                valid = (nbrs >= 0) & ~visited[rows_sel]
                sims = np.where(valid, score(rows_sel),
                                -np.inf).astype(np.float32)
                visited[nbrs[nbrs >= 0]] = True
                all_s = np.concatenate([beam_s, sims])
                all_i = np.concatenate(
                    [beam_i, np.where(valid, nbrs, -1).astype(np.int32)])
                all_e = np.concatenate([expanded, np.zeros(m0, bool)])
                # stable descending sort == lax.top_k tie-breaking
                order = np.argsort(-all_s, kind="stable")[:ef]
                beam_s = all_s[order].astype(np.float32)
                beam_i = all_i[order]
                expanded = all_e[order]
            out_s[si, ci] = beam_s
            out_i[si, ci] = beam_i
    return out_s, out_i
