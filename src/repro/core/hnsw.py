"""Array-based HNSW: offline numpy construction + jit-able JAX search.

TPU adaptation (DESIGN.md §3): the original HNSW is a pointer-chasing walk
with hash-set visited tracking and binary heaps — none of which vectorise.
We keep the *algorithm* (Alg. 1 / Alg. 2 of the paper) but re-express it:

  * adjacency is a fixed-degree int32 array per level, padded with -1;
  * the search beam W is a pair of sorted (score, id) arrays of size ef;
  * candidate selection = masked argmax, beam merge = ``jax.lax.top_k`` over
    the concatenation of the old beam and the newly-scored neighbours;
  * the visited set is a per-query bitmask;
  * the whole walk is a ``lax.while_loop`` whose body does one beam expansion
    (gather M neighbours -> score -> merge), vmapped over the query batch so
    the neighbour scoring is matmul-shaped for the MXU.

Construction runs host-side in numpy (index building is an offline batch job
in the paper too); only search must be jit-able for serving.
"""
from __future__ import annotations

import dataclasses
import heapq
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import filters as F
from repro.core import metrics as M
from repro.kernels.beam_search import beam_search

NEG_INF = np.float32(-np.inf)


def shard_seed(base: int, shard: int) -> int:
    """Construction seed for sub-HNSW ``shard`` of an index seeded with
    ``base``. Every path that (re)builds a shard — the sequential build,
    the process-pool fan-out (``repro.build``), and incremental rebuilds
    (``repro.core.updates``) — must derive its seed here, so a shard's
    graph is bit-identical no matter which path produced it (the store's
    manifest checksums depend on it)."""
    return base + 1 + shard


# ---------------------------------------------------------------------------
# Graph container
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HNSWGraph:
    """An HNSW index in array form.

    Attributes:
      data:       [n, d] float32 item vectors (dataset order).
      ids:        [n] int64 external ids (global ids when this is a sub-HNSW).
      neighbors:  list over levels; level l is an int32 array [n, M_l] padded
                  with -1. Level 0 is the bottom layer with all items.
      levels:     [n] int32, highest level of each node.
      entry:      int, entry vertex (node with the highest level).
      metric:     similarity function name.
      tags:       optional [n] int64 metadata tag bitsets (dataset order,
                  aligned with ``ids``) for filtered search
                  (``repro.core.filters``); ``None`` == all zeros ==
                  item matches no non-empty filter.
    """

    data: np.ndarray
    ids: np.ndarray
    neighbors: List[np.ndarray]
    levels: np.ndarray
    entry: int
    metric: str
    tags: Optional[np.ndarray] = None

    def tags_or_zeros(self) -> np.ndarray:
        """The tag bitsets, materialising zeros for untagged graphs."""
        if self.tags is None:
            return np.zeros((self.n,), dtype=np.int64)
        return np.asarray(self.tags, dtype=np.int64)

    @property
    def n(self) -> int:
        return int(self.data.shape[0])

    @property
    def d(self) -> int:
        return int(self.data.shape[1])

    @property
    def max_level(self) -> int:
        return len(self.neighbors) - 1

    def device_arrays(self) -> "HNSWArrays":
        """Stack upper levels into one padded array for the JAX search."""
        m_upper = max([lv.shape[1] for lv in self.neighbors[1:]], default=1)
        if self.max_level >= 1:
            upper = np.full(
                (self.max_level, self.n, m_upper), -1, dtype=np.int32)
            for l in range(1, self.max_level + 1):
                lv = self.neighbors[l]
                upper[l - 1, :, : lv.shape[1]] = lv
        else:
            upper = np.full((1, self.n, m_upper), -1, dtype=np.int32)
        return HNSWArrays(
            data=jnp.asarray(self.data, jnp.float32),
            ids=jnp.asarray(self.ids, jnp.int32),
            bottom=jnp.asarray(self.neighbors[0], jnp.int32),
            upper=jnp.asarray(upper, jnp.int32),
            entry=jnp.asarray(self.entry, jnp.int32),
            num_upper_levels=jnp.asarray(self.max_level, jnp.int32),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HNSWArrays:
    """Device-resident arrays consumed by the jitted search.

    The graph container owns the *scoring* of its own rows
    (:meth:`score_nodes`): the beam search gathers node indices and asks
    the graph for similarities, so a compressed graph representation
    (:class:`QuantHNSWArrays`) plugs into the identical walk by
    overriding one method instead of forking the search.
    """

    data: jnp.ndarray        # [n, d] f32
    ids: jnp.ndarray         # [n] i32 external ids
    bottom: jnp.ndarray      # [n, M0] i32
    upper: jnp.ndarray       # [L, n, Mu] i32 (L >= 1; all -1 rows for absent)
    entry: jnp.ndarray       # scalar i32
    num_upper_levels: jnp.ndarray  # scalar i32

    def tree_flatten(self):
        children = (self.data, self.ids, self.bottom, self.upper,
                    self.entry, self.num_upper_levels)
        return children, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def score_nodes(self, q: jnp.ndarray, nodes: jnp.ndarray,
                    metric: str) -> jnp.ndarray:
        """Similarity of one query against graph rows.

        Args: q [d] f32; nodes [m] i32 row indices (pre-clipped to
        valid range — callers mask invalid slots on the result).
        Returns [m] f32 similarities (larger = more similar).
        """
        return M.similarity_matrix(q[None, :], self.data[nodes], metric)[0]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantHNSWArrays:
    """Int8-compressed twin of :class:`HNSWArrays`.

    ``data`` holds int8 codes on a per-dimension affine grid
    (``repro.core.quant.QuantParams``); scoring is asymmetric — the
    float32 query against dequantized rows, via the
    ``repro.kernels.quant_distance`` oracle semantics — so the identical
    beam-search walk runs over a ~4x smaller HBM vector payload. The
    adjacency/ids fields are bit-identical to the float graph's.
    """

    data: jnp.ndarray        # [n, d] int8 codes
    ids: jnp.ndarray         # [n] i32 external ids
    bottom: jnp.ndarray      # [n, M0] i32
    upper: jnp.ndarray       # [L, n, Mu] i32
    entry: jnp.ndarray       # scalar i32
    num_upper_levels: jnp.ndarray  # scalar i32
    scale: jnp.ndarray       # [d] f32 per-dimension step
    zero: jnp.ndarray        # [d] f32 per-dimension zero-point

    def tree_flatten(self):
        children = (self.data, self.ids, self.bottom, self.upper,
                    self.entry, self.num_upper_levels, self.scale,
                    self.zero)
        return children, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def score_nodes(self, q: jnp.ndarray, nodes: jnp.ndarray,
                    metric: str) -> jnp.ndarray:
        """Asymmetric quantized scoring: float32 ``q`` against the
        dequantized code rows (same signature/contract as
        ``HNSWArrays.score_nodes``)."""
        from repro.kernels.quant_distance import quant_scores_ref
        return quant_scores_ref(q[None, :], self.data[nodes], self.scale,
                                self.zero, metric=metric)[0]


# ---------------------------------------------------------------------------
# Construction (numpy, Alg. 2)
# ---------------------------------------------------------------------------


class _Builder:
    """Incremental HNSW builder (host-side)."""

    def __init__(self, d: int, metric: str, m: int, m_upper: int,
                 ef_construction: int, seed: int, capacity: int):
        self.metric = metric
        self.m0 = m
        self.mu = m_upper
        self.efc = ef_construction
        self.rng = np.random.default_rng(seed)
        self.ml = 1.0 / np.log(max(m, 2))
        self.data = np.zeros((capacity, d), dtype=np.float32)
        self.levels = np.zeros(capacity, dtype=np.int32)
        self.n = 0
        self.entry = -1
        self.max_level = -1
        # adjacency: list over levels of [capacity, M_l] int32
        self.adj: List[np.ndarray] = []

    def _ensure_level(self, level: int) -> None:
        while len(self.adj) <= level:
            m = self.m0 if len(self.adj) == 0 else self.mu
            self.adj.append(
                np.full((self.data.shape[0], m), -1, dtype=np.int32))

    def _search_layer(self, q: np.ndarray, entry_points: List[Tuple[float, int]],
                      level: int, ef: int) -> List[Tuple[float, int]]:
        """Alg. 1 Search-Level. Returns up to ef (sim, id) best-first."""
        visited = set()
        cand: List[Tuple[float, int]] = []   # max-heap via negated sim
        best: List[Tuple[float, int]] = []   # min-heap of (sim, id)
        for sim, node in entry_points:
            if node in visited:
                continue
            visited.add(node)
            heapq.heappush(cand, (-sim, node))
            heapq.heappush(best, (sim, node))
        adj = self.adj[level]
        while cand:
            neg_sim, node = heapq.heappop(cand)
            if -neg_sim < best[0][0] and len(best) >= ef:
                break
            nbrs = adj[node]
            nbrs = nbrs[nbrs >= 0]
            fresh = [v for v in nbrs if v not in visited]
            if not fresh:
                continue
            visited.update(fresh)
            fresh_arr = np.asarray(fresh, dtype=np.int64)
            sims = M.similarity_matrix_np(
                q[None, :], self.data[fresh_arr], self.metric)[0]
            for v, s in zip(fresh, sims):
                s = float(s)
                if len(best) < ef or s > best[0][0]:
                    heapq.heappush(cand, (-s, v))
                    heapq.heappush(best, (s, v))
                    if len(best) > ef:
                        heapq.heappop(best)
        return sorted(best, reverse=True)

    def _select_heuristic(self, q: np.ndarray,
                          cand: List[Tuple[float, int]], m: int) -> List[int]:
        """HNSW neighbour-selection heuristic (Malkov & Yashunin Alg. 4).

        Keeps a *diverse* neighbour set: candidate e joins only if it is
        more similar to q than to any already-selected neighbour. This keeps
        long-range edges between clusters — without it, well-separated
        clusters become disconnected graph components and recall collapses.
        Pruned candidates backfill remaining slots (keepPrunedConnections).
        """
        ordered = sorted(cand, reverse=True)
        selected: List[int] = []
        for sim, v in ordered:
            if len(selected) == m:
                break
            if selected:
                sims_to_sel = M.similarity_matrix_np(
                    self.data[v][None, :],
                    self.data[np.asarray(selected)], self.metric)[0]
                if np.any(sims_to_sel > sim):
                    continue
            selected.append(v)
        if len(selected) < m:
            chosen = set(selected)
            for _, v in ordered:
                if v not in chosen:
                    selected.append(v)
                    chosen.add(v)
                    if len(selected) == m:
                        break
        return selected

    def _connect(self, node: int, neighbors: List[int], level: int) -> None:
        m = self.m0 if level == 0 else self.mu
        adj = self.adj[level]
        adj[node, : len(neighbors[:m])] = neighbors[:m]
        # add reverse edges, pruning to degree m with the diversity heuristic
        for v in neighbors[:m]:
            row = adj[v]
            slot = np.where(row < 0)[0]
            if slot.size:
                row[slot[0]] = node
            else:
                cand_ids = np.append(row, node)
                sims = M.similarity_matrix_np(
                    self.data[v][None, :], self.data[cand_ids], self.metric)[0]
                keep = self._select_heuristic(
                    self.data[v], list(zip(sims.tolist(), cand_ids.tolist())), m)
                adj[v] = np.asarray(keep, dtype=np.int32)

    def add(self, x: np.ndarray) -> int:
        node = self.n
        self.data[node] = x
        level = int(-np.log(self.rng.uniform(low=1e-12, high=1.0)) * self.ml)
        self.levels[node] = level
        self._ensure_level(level)
        self.n += 1
        if self.entry < 0:
            self.entry = node
            self.max_level = level
            return node
        # greedy descent through layers above `level` (search factor 1)
        sim_e = float(M.similarity_matrix_np(
            x[None, :], self.data[self.entry][None, :], self.metric)[0, 0])
        eps = [(sim_e, self.entry)]
        for l in range(self.max_level, level, -1):
            eps = self._search_layer(x, eps, l, ef=1)[:1]
        # insert with beam efC in layers min(level, max_level)..0
        for l in range(min(level, self.max_level), -1, -1):
            found = self._search_layer(x, eps, l, ef=self.efc)
            m = self.m0 if l == 0 else self.mu
            nbrs = self._select_heuristic(x, found, m)
            self._connect(node, nbrs, l)
            eps = found
        if level > self.max_level:
            self.max_level = level
            self.entry = node
        return node


def build_hnsw(data: np.ndarray,
               metric: str = "l2",
               max_degree: int = 32,
               max_degree_upper: int = 16,
               ef_construction: int = 100,
               seed: int = 0,
               ids: Optional[np.ndarray] = None,
               tags: Optional[np.ndarray] = None) -> HNSWGraph:
    """Alg. 2: sequential-insert HNSW construction (host-side).

    ``tags`` ([n] int64 bitsets, dataset order) are carried as metadata —
    they never influence construction, so tagged and untagged builds of
    the same data are graph-identical.
    """
    data = np.ascontiguousarray(data, dtype=np.float32)
    n, d = data.shape
    if n == 0:
        return empty_hnsw(d, metric=metric, max_degree=max_degree)
    b = _Builder(d, metric, max_degree, max_degree_upper,
                 ef_construction, seed, capacity=n)
    for i in range(n):
        b.add(data[i])
    neighbors = [b.adj[l][:n] for l in range(len(b.adj))] or [
        np.full((n, max_degree), -1, dtype=np.int32)]
    if ids is None:
        ids = np.arange(n, dtype=np.int64)
    if tags is not None:
        tags = np.asarray(tags, dtype=np.int64)
    return HNSWGraph(
        data=data, ids=np.asarray(ids), neighbors=neighbors,
        levels=b.levels[:n], entry=b.entry, metric=metric, tags=tags)


def empty_hnsw(d: int, *, metric: str = "l2",
               max_degree: int = 32) -> HNSWGraph:
    """A zero-item sub-HNSW (entry = -1). Deleting every item of a shard
    leaves this — the shard keeps its routing slot (meta centers still
    label it) but contributes nothing: searches skip it, and the arena
    stacks it as a single pad row (id -1) that every merge filters."""
    return HNSWGraph(
        data=np.zeros((0, d), dtype=np.float32),
        ids=np.zeros((0,), dtype=np.int64),
        neighbors=[np.full((0, max_degree), -1, dtype=np.int32)],
        levels=np.zeros((0,), dtype=np.int32),
        entry=-1, metric=metric,
        tags=np.zeros((0,), dtype=np.int64))


# ---------------------------------------------------------------------------
# Search (JAX, Alg. 1)
# ---------------------------------------------------------------------------


def _score_one(q: jnp.ndarray, x: jnp.ndarray, metric: str) -> jnp.ndarray:
    """Similarity of one query against [m, d] candidates -> [m].

    Float-only helper; the walk itself scores through
    ``g.score_nodes`` so quantized graphs plug in transparently."""
    return M.similarity_matrix(q[None, :], x, metric)[0]


def _greedy_descend(g: HNSWArrays, q: jnp.ndarray, metric: str,
                    max_steps: int) -> jnp.ndarray:
    """Greedy walk through the upper layers (search factor 1). Returns the
    bottom-layer entry node for this query."""

    def level_step(carry, level_idx):
        node = carry
        # level_idx counts down is handled by caller ordering; adjacency
        # row of an absent node is all -1 so the walk is a no-op there.
        adj_l = jax.lax.dynamic_index_in_dim(
            g.upper, level_idx, axis=0, keepdims=False)  # [n, Mu]

        def walk_cond(state):
            cur, cur_sim, moved, steps = state
            return jnp.logical_and(moved, steps < max_steps)

        def walk_body(state):
            cur, cur_sim, _, steps = state
            nbrs = adj_l[cur]                                   # [Mu]
            valid = nbrs >= 0
            sims = jnp.where(
                valid, g.score_nodes(q, jnp.clip(nbrs, 0), metric),
                -jnp.inf)
            j = jnp.argmax(sims)
            better = sims[j] > cur_sim
            new_cur = jnp.where(better, nbrs[j], cur)
            new_sim = jnp.where(better, sims[j], cur_sim)
            return new_cur, new_sim, better, steps + 1

        sim0 = g.score_nodes(q, node[None], metric)[0]
        node, _, _, _ = jax.lax.while_loop(
            walk_cond, walk_body, (node, sim0, jnp.bool_(True), jnp.int32(0)))
        return node, ()

    # iterate levels from top (index L-1) down to 0 of `upper`
    num_levels = g.upper.shape[0]
    levels = jnp.arange(num_levels - 1, -1, -1, dtype=jnp.int32)
    # mask out levels above num_upper_levels (graph may be shallower)
    def masked_step(node, lvl):
        active = lvl < g.num_upper_levels
        new_node, _ = level_step(node, jnp.where(active, lvl, 0))
        return jnp.where(active, new_node, node), ()

    node, _ = jax.lax.scan(masked_step, g.entry.astype(jnp.int32), levels)
    return node


def _beam_search_bottom(g: HNSWArrays, q: jnp.ndarray, entry: jnp.ndarray,
                        metric: str, ef: int, max_iters: int):
    """Best-first beam search on the bottom layer (Alg. 1 Search-Level with
    search factor ef). Returns (scores [ef], node_ids [ef]) best-first."""
    n, m0 = g.bottom.shape
    ef = min(ef, n)

    visited = jnp.zeros((n,), dtype=jnp.bool_).at[entry].set(True)
    beam_ids = jnp.full((ef,), -1, dtype=jnp.int32).at[0].set(entry)
    beam_scores = jnp.full((ef,), -jnp.inf, jnp.float32).at[0].set(
        g.score_nodes(q, entry[None], metric)[0])
    expanded = jnp.zeros((ef,), dtype=jnp.bool_)

    def cond(state):
        beam_scores, beam_ids, expanded, visited, it = state
        has_unexpanded = jnp.any(jnp.logical_and(~expanded, beam_ids >= 0))
        return jnp.logical_and(has_unexpanded, it < max_iters)

    def body(state):
        beam_scores, beam_ids, expanded, visited, it = state
        # pick the best unexpanded beam entry
        sel_scores = jnp.where(jnp.logical_and(~expanded, beam_ids >= 0),
                               beam_scores, -jnp.inf)
        j = jnp.argmax(sel_scores)
        node = beam_ids[j]
        expanded = expanded.at[j].set(True)
        # gather + score its neighbours
        nbrs = g.bottom[node]                              # [M0]
        valid = jnp.logical_and(nbrs >= 0, ~visited[jnp.clip(nbrs, 0)])
        sims = jnp.where(
            valid, g.score_nodes(q, jnp.clip(nbrs, 0), metric), -jnp.inf)
        visited = visited.at[jnp.clip(nbrs, 0)].set(
            jnp.logical_or(visited[jnp.clip(nbrs, 0)], nbrs >= 0))
        # merge into beam: top-ef of (beam ∪ neighbours)
        all_scores = jnp.concatenate([beam_scores, sims])
        all_ids = jnp.concatenate([beam_ids, jnp.where(valid, nbrs, -1)])
        all_expanded = jnp.concatenate(
            [expanded, jnp.zeros((m0,), dtype=jnp.bool_)])
        top_scores, idx = jax.lax.top_k(all_scores, ef)
        return (top_scores, all_ids[idx], all_expanded[idx], visited, it + 1)

    state = (beam_scores, beam_ids, expanded, visited, jnp.int32(0))
    beam_scores, beam_ids, _, _, _ = jax.lax.while_loop(cond, body, state)
    return beam_scores, beam_ids


def search_one(g: HNSWArrays, q: jnp.ndarray, *, metric: str, k: int,
               ef: int, max_iters: int = 400, max_steps: int = 64,
               tag_words: Optional[jnp.ndarray] = None,
               filter_words: Optional[jnp.ndarray] = None):
    """One query against one graph: greedy descend through the upper
    layers, bottom-layer beam search, top-k, node -> external-id
    translation, (-1, -inf) padding when the graph is smaller than k.

    ``tag_words`` ([n, 2] i32 word-split bitsets) + ``filter_words``
    ([2] i32) apply the metadata alive-mask (``repro.core.filters``) on
    the walk's candidate emission — navigation stays unfiltered (a
    filtered beam would disconnect the graph), dead candidates become
    (-inf, -1) before the top-k, so a filtered query can never
    under-fill against live matches.

    This is THE per-query search core — ``hnsw_search`` (engine path) and
    the fused arena pipeline (``repro.core.arena.shard_search``) both
    call it, so their semantics cannot drift. Trace-time only (call
    under jit/vmap). Returns (ids [k] i32, scores [k] f32) best-first.
    """
    ef = max(ef, k)
    entry = _greedy_descend(g, q, metric, max_steps=max_steps)
    scores, nodes = _beam_search_bottom(g, q, entry, metric, ef, max_iters)
    if tag_words is not None and filter_words is not None:
        alive = F.alive_words(tag_words[jnp.clip(nodes, 0)], filter_words)
        scores = jnp.where(alive, scores, -jnp.inf)
        nodes = jnp.where(alive, nodes, -1)
    kk = min(k, scores.shape[0])
    top_scores, idx = jax.lax.top_k(scores, kk)
    top_nodes = nodes[idx]
    ext = jnp.where(top_nodes >= 0, g.ids[jnp.clip(top_nodes, 0)], -1)
    if kk < k:  # graph smaller than k: pad
        pad = k - kk
        ext = jnp.concatenate([ext, jnp.full((pad,), -1, jnp.int32)])
        top_scores = jnp.concatenate(
            [top_scores, jnp.full((pad,), -jnp.inf, jnp.float32)])
    return ext, top_scores


def search_batch(g: HNSWArrays, queries: jnp.ndarray, *, metric: str,
                 k: int, ef: int, max_iters: int = 400,
                 max_steps: int = 64,
                 tag_words: Optional[jnp.ndarray] = None,
                 filter_words: Optional[jnp.ndarray] = None,
                 stats: bool = False):
    """Batched search through the fused beam-walk op
    (``repro.kernels.beam_search``): greedy upper-layer descent per query
    (cheap, stays in XLA), then ONE fused bottom-layer walk for the whole
    batch (one batched XLA ``while_loop`` on every backend).

    Bit-identical to ``vmap(search_one)``: the op freezes finished rows
    so the shared loop matches the per-query ``while_loop``, and its
    scoring lowers to the same per-row dots as ``score_nodes``. Trace-
    time only (call under jit). Returns (ids [B, k], scores [B, k])
    best-first with (-1, -inf) padding.

    ``tag_words`` ([n, 2] i32) + ``filter_words`` ([B, 2] i32, one
    filter per query) route the metadata alive-mask through the fused
    op — candidates whose bitset misses the filter come back (-inf, -1)
    before the top-k here (same contract as ``search_one``).

    ``stats=True`` also returns each query's bottom-layer expansion
    count [B] i32.
    """
    ef = max(ef, k)
    entries = jax.vmap(
        lambda qv: _greedy_descend(g, qv, metric, max_steps=max_steps))(
            queries)
    scale = getattr(g, "scale", None)
    zero = getattr(g, "zero", None)
    scores, nodes, iters = beam_search(
        g.data[None], g.bottom[None], queries[None], entries[None],
        metric=metric, ef=ef, max_iters=max_iters, scale=scale, zero=zero,
        tag_words=None if tag_words is None else tag_words[None],
        filter_words=None if filter_words is None else filter_words[None],
        stats=True)
    scores, nodes = scores[0], nodes[0]                # [B, ef']
    kk = min(k, scores.shape[1])
    top_scores, idx = jax.lax.top_k(scores, kk)
    top_nodes = jnp.take_along_axis(nodes, idx, axis=1)
    ext = jnp.where(top_nodes >= 0, g.ids[jnp.clip(top_nodes, 0)], -1)
    if kk < k:  # graph smaller than k: pad
        b = queries.shape[0]
        pad = k - kk
        ext = jnp.concatenate(
            [ext, jnp.full((b, pad), -1, jnp.int32)], axis=1)
        top_scores = jnp.concatenate(
            [top_scores, jnp.full((b, pad), -jnp.inf, jnp.float32)],
            axis=1)
    if stats:
        return ext, top_scores, iters[0]
    return ext, top_scores


@partial(jax.jit, static_argnames=("metric", "k", "ef", "max_iters",
                                   "impl", "stats"))
def hnsw_search(g: HNSWArrays, queries: jnp.ndarray, *, metric: str,
                k: int, ef: int = 100, max_iters: int = 400,
                impl: str = "fused",
                tag_words: Optional[jnp.ndarray] = None,
                filter_words: Optional[jnp.ndarray] = None,
                stats: bool = False):
    """Batched HNSW search (Alg. 1).

    Args:
      g: device arrays of one HNSW graph.
      queries: [B, d] float32.
      k: neighbours to return.
      ef: bottom-layer search factor (l in the paper).
      max_iters: hard bound on beam expansions (while_loop trip bound).
      impl: "fused" (default) runs the whole batch through the fused
        beam-walk op; "loop" keeps the per-query vmapped ``while_loop``
        (the roofline's baseline). Results are identical.
      tag_words / filter_words: optional metadata alive-mask — [n, 2]
        i32 item tag words and [B, 2] i32 per-query filter words
        (``repro.core.filters.split_tag_words``); a query whose filter
        words are zero runs unfiltered.
      stats: also return each query's bottom-layer expansion count
        ([B] i32; their maximum is the walk loop's trip count). Fused
        walk only.

    Returns:
      (ids [B, k] int32 external ids (-1 pad), scores [B, k] f32) best-first,
      then the expansion counts when ``stats``.
    """
    if impl == "fused":
        return search_batch(g, queries, metric=metric, k=k, ef=ef,
                            max_iters=max_iters,
                            tag_words=tag_words, filter_words=filter_words,
                            stats=stats)
    if stats:
        raise ValueError("stats=True needs impl='fused'")
    if tag_words is None or filter_words is None:
        return jax.vmap(lambda q: search_one(
            g, q, metric=metric, k=k, ef=ef, max_iters=max_iters))(queries)
    return jax.vmap(lambda q, fw: search_one(
        g, q, metric=metric, k=k, ef=ef, max_iters=max_iters,
        tag_words=tag_words, filter_words=fw))(queries, filter_words)


def search_numpy(graph: HNSWGraph, queries: np.ndarray, k: int,
                 ef: int = 100, *, filter_tags=None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side reference search (used during index building, Alg. 3 line 8,
    and as an oracle in tests).

    ``filter_tags`` (scalar int64, or [B] per query) applies the
    metadata alive-mask of ``repro.core.filters`` on the walk's
    candidate set — the same navigate-unfiltered / emit-filtered
    contract as the device paths.
    """
    b = _Builder.__new__(_Builder)  # reuse _search_layer without re-init
    b.metric = graph.metric
    b.data = graph.data
    b.adj = graph.neighbors
    nq = queries.shape[0]
    out_ids = np.full((nq, k), -1, dtype=np.int64)
    out_scores = np.full((nq, k), -np.inf, dtype=np.float32)
    if graph.n == 0:
        return out_ids, out_scores
    filters = None
    if filter_tags is not None:
        filters = np.broadcast_to(
            np.asarray(filter_tags, dtype=np.int64), (nq,))
        tags = graph.tags_or_zeros()
    for i, q in enumerate(np.asarray(queries, dtype=np.float32)):
        sim_e = float(M.similarity_matrix_np(
            q[None, :], graph.data[graph.entry][None, :], graph.metric)[0, 0])
        eps = [(sim_e, graph.entry)]
        for l in range(graph.max_level, 0, -1):
            eps = b._search_layer(q, eps, l, ef=1)[:1]
        found = b._search_layer(q, eps, 0, ef=max(ef, k))
        if filters is not None and filters[i] != 0:
            found = [(s, v) for s, v in found
                     if F.alive_np(tags[v], filters[i])]
        for j, (s, v) in enumerate(found[:k]):
            out_ids[i, j] = graph.ids[v]
            out_scores[i, j] = s
    return out_ids, out_scores
