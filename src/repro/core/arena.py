"""ShardArena — the single canonical device form of a PyramidIndex.

Every consumer of a built index (the single-host reference path, the
threaded serving engine, the SPMD ``shard_map`` program) used to carry its
own device representation: per-shard ``HNSWArrays`` uploads with per-shard
jit compiles here, a stacked array pytree there. The arena unifies them:

  * all w sub-HNSWs are stacked on a leading shard axis, equal-padded with
    isolated nodes (all -1 neighbours, id -1, zero vector) that the walk
    can never reach nor return;
  * it is built ONCE per index (``PyramidIndex.arena()`` memoises) and
    shared by every engine/executor/search path — one HBM copy, and one
    jit compile for all shards because every shard view has equal shapes;
  * ``arena_search`` is the fused route -> per-shard capacity-bounded beam
    search (vmapped over the shard axis) -> dedup-top-k merge pipeline,
    entirely on device, with the merge running as the ``merge_topk``
    Pallas kernel.

The per-stage helpers (``shard_search``, ``scatter_partials``) are the
building blocks the SPMD path wraps in ``shard_map`` — the three search
paths differ only in *where* the stages run, never in what they compute.

A :class:`QuantizedShardArena` is the int8-compressed twin
(``index.arena(dtype="int8")``): same stacked layout, ~4x smaller HBM
vector payload, asymmetric float32-query x int8-database distances
(``repro.kernels.quant_distance``) inside the identical pipeline.
Callers that want float-path recall rerank the top ``rerank_factor * k``
candidates exactly (``repro.core.quant.exact_rerank_np``) — see
``search_single_host(quantize=True)``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hnsw as H
from repro.core.router import route_queries
from repro.kernels.beam_search import beam_search
from repro.kernels.merge_topk import merge_topk


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardArena:
    """All w sub-HNSWs stacked on a leading shard axis.

    Padding: graphs are padded to the max sub-dataset size with isolated
    nodes (all -1 neighbours, id -1, zero vector) which can never be
    reached by the walk nor returned (ids filtered by the merge).
    """

    data: jnp.ndarray     # [w, n_pad, d]
    ids: jnp.ndarray      # [w, n_pad] (-1 pad)
    bottom: jnp.ndarray   # [w, n_pad, M0]
    upper: jnp.ndarray    # [w, L, n_pad, Mu]
    entry: jnp.ndarray    # [w]
    num_upper_levels: jnp.ndarray  # [w]

    def __post_init__(self):
        self._views: Dict[int, H.HNSWArrays] = {}

    def tree_flatten(self):
        return (self.data, self.ids, self.bottom, self.upper, self.entry,
                self.num_upper_levels), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def num_shards(self) -> int:
        return self.data.shape[0]

    @property
    def vector_nbytes(self) -> int:
        """Bytes of the vector payload (what quantization compresses;
        adjacency/ids are common to both arena forms)."""
        return int(self.data.nbytes)

    @property
    def total_nbytes(self) -> int:
        return int(sum(leaf.nbytes for leaf in self.tree_flatten()[0]))

    def shard(self, i) -> H.HNSWArrays:
        """Uncached view of shard ``i`` (safe on traced values, e.g.
        inside ``shard_map``/``vmap`` where ``i`` indexes local slots)."""
        return H.HNSWArrays(
            data=self.data[i], ids=self.ids[i], bottom=self.bottom[i],
            upper=self.upper[i], entry=self.entry[i],
            num_upper_levels=self.num_upper_levels[i])

    def as_graph(self) -> H.HNSWArrays:
        """Reinterpret already-sliced leaves as one graph — for use
        inside ``vmap``/``lax.map`` over the shard axis, where every
        leaf has lost its leading ``w`` dimension."""
        return H.HNSWArrays(
            data=self.data, ids=self.ids, bottom=self.bottom,
            upper=self.upper, entry=self.entry,
            num_upper_levels=self.num_upper_levels)

    def shard_view(self, i: int) -> H.HNSWArrays:
        """Memoised concrete view of shard ``i``: every executor replica
        serving the shard shares ONE set of device arrays (host-side use
        only — never call with traced operands)."""
        if i not in self._views:
            self._views[i] = self.shard(i)
        return self._views[i]

    @classmethod
    def from_index(cls, index) -> "ShardArena":
        """Stack ``index.subs`` into one equal-padded device structure.

        Builds the stacked buffers host-side straight from the
        ``HNSWGraph`` fields (same layout as ``device_arrays``) so the
        arena costs ONE device upload — no per-shard upload/download
        round trip. Prefer ``index.arena()`` (memoised) over calling
        this directly.
        """
        st = _stack_host(index)
        return cls(
            data=jnp.asarray(st["data"]), ids=jnp.asarray(st["ids"]),
            bottom=jnp.asarray(st["bottom"]),
            upper=jnp.asarray(st["upper"]),
            entry=jnp.asarray(st["entry"]),
            num_upper_levels=jnp.asarray(st["num_upper_levels"]))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedShardArena:
    """Int8-compressed arena: same stacked layout as :class:`ShardArena`
    but ``data`` holds codes on a per-dimension affine grid
    (``repro.core.quant.QuantParams``) — the HBM vector payload shrinks
    ~4x, which is what lets a device serve a dataset its HBM could not
    hold in float32.

    ``scale``/``zero`` are the GLOBAL grid tiled per shard ([w, d]), so
    every leaf is shard-leading — the SPMD program shards all leaves
    over the ``model`` axis with one spec, and ``vmap``/``lax.map`` over
    the shard axis map the whole pytree uniformly. Quantization happens
    host-side at build, so no float32 copy of the vectors ever reaches
    the device.
    """

    data: jnp.ndarray     # [w, n_pad, d] int8 codes
    ids: jnp.ndarray      # [w, n_pad] (-1 pad)
    bottom: jnp.ndarray   # [w, n_pad, M0]
    upper: jnp.ndarray    # [w, L, n_pad, Mu]
    entry: jnp.ndarray    # [w]
    num_upper_levels: jnp.ndarray  # [w]
    scale: jnp.ndarray    # [w, d] f32 (global grid, tiled per shard)
    zero: jnp.ndarray     # [w, d] f32

    def __post_init__(self):
        self._views: Dict[int, H.QuantHNSWArrays] = {}

    def tree_flatten(self):
        return (self.data, self.ids, self.bottom, self.upper, self.entry,
                self.num_upper_levels, self.scale, self.zero), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def num_shards(self) -> int:
        return self.data.shape[0]

    @property
    def vector_nbytes(self) -> int:
        return int(self.data.nbytes + self.scale.nbytes
                   + self.zero.nbytes)

    @property
    def total_nbytes(self) -> int:
        return int(sum(leaf.nbytes for leaf in self.tree_flatten()[0]))

    def shard(self, i) -> H.QuantHNSWArrays:
        return H.QuantHNSWArrays(
            data=self.data[i], ids=self.ids[i], bottom=self.bottom[i],
            upper=self.upper[i], entry=self.entry[i],
            num_upper_levels=self.num_upper_levels[i],
            scale=self.scale[i], zero=self.zero[i])

    def as_graph(self) -> H.QuantHNSWArrays:
        return H.QuantHNSWArrays(
            data=self.data, ids=self.ids, bottom=self.bottom,
            upper=self.upper, entry=self.entry,
            num_upper_levels=self.num_upper_levels, scale=self.scale,
            zero=self.zero)

    def shard_view(self, i: int) -> H.QuantHNSWArrays:
        if i not in self._views:
            self._views[i] = self.shard(i)
        return self._views[i]

    @classmethod
    def from_index(cls, index, params) -> "QuantizedShardArena":
        """Quantize ``index.subs`` onto ``params``' grid and stack.

        The codes are produced host-side from the float graph data
        (``QuantParams.quantize`` row by shard), so building a quantized
        arena never uploads a float32 copy of the vectors — the device
        only ever sees int8. Prefer ``index.arena(dtype="int8")``
        (memoised) over calling this directly.
        """
        st = _stack_host(index, quantize=params.quantize)
        w = st["data"].shape[0]
        scale = np.tile(params.scale[None, :], (w, 1))
        zero = np.tile(params.zero[None, :], (w, 1))
        return cls(
            data=jnp.asarray(st["data"]), ids=jnp.asarray(st["ids"]),
            bottom=jnp.asarray(st["bottom"]),
            upper=jnp.asarray(st["upper"]),
            entry=jnp.asarray(st["entry"]),
            num_upper_levels=jnp.asarray(st["num_upper_levels"]),
            scale=jnp.asarray(scale), zero=jnp.asarray(zero))


def _stack_host(index, quantize=None) -> Dict[str, np.ndarray]:
    """Stack ``index.subs`` into equal-padded host arrays (the shared
    body of both ``from_index`` builders). ``quantize`` maps each
    shard's [n, d] float rows to its stored dtype (int8 codes for the
    quantized arena); pad rows stay zero in either dtype — they are
    unreachable (no neighbours, id -1), so their code values are inert.
    """
    subs = index.subs
    # an all-deleted shard has n == 0: give it one pad row (id -1, no
    # neighbours) so the walk lands on an inert slot the merges filter
    n_pad = max(1, max(g.n for g in subs))
    l_pad = max(1, max(g.max_level for g in subs))
    mu = max([lv.shape[1] for g in subs for lv in g.neighbors[1:]],
             default=1)
    m0 = max(g.neighbors[0].shape[1] for g in subs)
    d = subs[0].d
    w = len(subs)

    data = np.zeros((w, n_pad, d),
                    np.int8 if quantize is not None else np.float32)
    ids = np.full((w, n_pad), -1, np.int32)
    bottom = np.full((w, n_pad, m0), -1, np.int32)
    upper = np.full((w, l_pad, n_pad, mu), -1, np.int32)
    entry = np.zeros((w,), np.int32)
    nul = np.zeros((w,), np.int32)
    for i, g in enumerate(subs):
        n = g.n
        data[i, :n] = quantize(g.data) if quantize is not None else g.data
        ids[i, :n] = g.ids
        bottom[i, :n, : g.neighbors[0].shape[1]] = g.neighbors[0]
        for lvl in range(1, g.max_level + 1):
            lv = g.neighbors[lvl]
            upper[i, lvl - 1, :n, : lv.shape[1]] = lv
        entry[i] = int(g.entry) if n else 0  # empty shard: enter pad row
        nul[i] = int(g.max_level)
    return {"data": data, "ids": ids, "bottom": bottom, "upper": upper,
            "entry": entry, "num_upper_levels": nul}


# ---------------------------------------------------------------------------
# Fused pipeline stages (shared by arena_search and the SPMD wrapper)
# ---------------------------------------------------------------------------


def shard_search(arena: ShardArena, mask: jnp.ndarray, queries: jnp.ndarray,
                 *, metric: str, k: int, ef: int, capacity: int,
                 max_iters: int = 400, shard_axis: str = "kernel",
                 tag_words: Optional[jnp.ndarray] = None,
                 filter_words: Optional[jnp.ndarray] = None):
    """Capacity-bounded beam search mapped over the shard axis.

    Each shard drains its <= ``capacity`` assigned queries from ``mask``
    (``jnp.nonzero(..., size=C)`` = static-shape queue draining; overflow
    and empty slots point at the dummy row B and are invalidated).

    Args:
      arena: the shards to search — all of them (local slice inside SPMD).
      mask: [B, w_arena] bool routing mask aligned with ``arena``.
      queries: [B, d] preprocessed queries.
      shard_axis: "kernel" (default) runs every (shard, slot) pair
        through ONE fused beam-walk op (``repro.kernels.beam_search``,
        a batched XLA walk on every backend). It retires the old
        backend split ("map" on CPU, "vmap" on TPU) behind one
        strategy: all w * C rows walk in one loop whose trip count is
        the global max. "vmap" / "map" keep the per-query
        ``while_loop`` batched / sequentially mapped over the shard axis
        (the roofline's measured baselines; "map"'s per-shard early
        termination keeps it the fastest multi-shard path on CPU — see
        API.md "Fused beam search" for the honest numbers — but it is w
        sequential dispatches instead of one).
      tag_words / filter_words: optional metadata alive-mask
        (``repro.core.filters``): [w, n_pad, 2] i32 item tag words
        aligned with the arena stacking (``PyramidIndex.tags_arena``)
        and [B, 2] i32 per-query filter words. Dead candidates leave
        each shard as (-inf, -1) — the per-shard partials are already
        filtered BEFORE the cross-shard merge, so a filtered query
        fills its k from live matches only.

    Returns (qidx [w, C] i32, ids [w, C, k] i32, scores [w, C, k] f32).

    Works identically over a float :class:`ShardArena` and a
    :class:`QuantizedShardArena` — every strategy maps the arena
    *pytree* (every leaf is shard-leading); the quantized arena routes
    its frozen grid into the dequantize-scoring variant of the walk, so
    the representation-specific distance is preserved.
    """
    b = queries.shape[0]
    # per-slot filter words follow the same queue-drain gather as the
    # queries: a dummy row of zero words absorbs invalid slots, so
    # overflow/empty slots always walk unfiltered (their results are
    # invalidated below anyway)
    fw_pad = None
    if tag_words is not None and filter_words is not None:
        fw_pad = jnp.concatenate(
            [filter_words.astype(jnp.int32),
             jnp.zeros((1, 2), jnp.int32)], axis=0)          # [B+1, 2]

    if shard_axis == "kernel":
        # drain each shard's queue, then walk ALL (shard, slot) rows in
        # one fused op — same math as vmap(search_one) per slot
        qidx = jax.vmap(
            lambda col: jnp.nonzero(col, size=capacity, fill_value=b)[0])(
                mask.T)                                      # [w, C]
        slot_valid = qidx < b
        qs = queries[jnp.clip(qidx, 0, b - 1)]               # [w, C, d]
        entries = jax.vmap(lambda sl, qrow: jax.vmap(
            lambda qv: H._greedy_descend(
                sl.as_graph(), qv, metric, max_steps=64))(qrow))(
                    arena, qs)                               # [w, C]
        scale = getattr(arena, "scale", None)
        efb = max(ef, k)
        scores, nodes = beam_search(
            arena.data, arena.bottom, qs, entries, metric=metric,
            ef=efb, max_iters=max_iters,
            scale=None if scale is None else scale[0],
            zero=None if scale is None else arena.zero[0],
            tag_words=tag_words,
            filter_words=None if fw_pad is None else fw_pad[qidx])
        kk = min(k, scores.shape[-1])
        top_scores, idx = jax.lax.top_k(scores, kk)
        top_nodes = jnp.take_along_axis(nodes, idx, axis=2)
        ids_out = jax.vmap(lambda ids_s, tn: jnp.where(
            tn >= 0, ids_s[jnp.clip(tn, 0)], -1))(arena.ids, top_nodes)
        if kk < k:  # shards smaller than k: pad
            w = qidx.shape[0]
            pad = k - kk
            ids_out = jnp.concatenate(
                [ids_out, jnp.full((w, capacity, pad), -1, jnp.int32)],
                axis=2)
            top_scores = jnp.concatenate(
                [top_scores,
                 jnp.full((w, capacity, pad), -jnp.inf, jnp.float32)],
                axis=2)
        ids_out = jnp.where(slot_valid[:, :, None], ids_out, -1)
        scores_out = jnp.where(
            slot_valid[:, :, None], top_scores, -jnp.inf)
        return qidx.astype(jnp.int32), ids_out, scores_out

    def one_shard(arena_slice, shard_mask, tw=None):
        g = arena_slice.as_graph()
        qidx = jnp.nonzero(shard_mask, size=capacity, fill_value=b)[0]
        slot_valid = qidx < b
        qs = queries[jnp.clip(qidx, 0, b - 1)]               # [C, d]
        if tw is None:
            ids_out, scores_out = jax.vmap(lambda qv: H.search_one(
                g, qv, metric=metric, k=k, ef=ef,
                max_iters=max_iters))(qs)
        else:
            ids_out, scores_out = jax.vmap(
                lambda qv, f: H.search_one(
                    g, qv, metric=metric, k=k, ef=ef,
                    max_iters=max_iters, tag_words=tw,
                    filter_words=f))(qs, fw_pad[qidx])
        ids_out = jnp.where(slot_valid[:, None], ids_out, -1)
        scores_out = jnp.where(slot_valid[:, None], scores_out, -jnp.inf)
        return qidx.astype(jnp.int32), ids_out, scores_out

    if fw_pad is None:
        if shard_axis == "map":
            return jax.lax.map(lambda t: one_shard(*t), (arena, mask.T))
        return jax.vmap(one_shard)(arena, mask.T)
    if shard_axis == "map":
        return jax.lax.map(lambda t: one_shard(*t),
                           (arena, mask.T, tag_words))
    return jax.vmap(one_shard)(arena, mask.T, tag_words)


def scatter_partials(qidx: jnp.ndarray, ids: jnp.ndarray,
                     scores: jnp.ndarray, b: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter per-shard partials back to query rows.

    Args: qidx [w, C], ids [w, C, k], scores [w, C, k] (the dummy row b
    absorbs invalid slots and is sliced off).
    Returns (scores [B, w*k] f32, ids [B, w*k] i32) ready for the merge.
    """
    w, _, k = ids.shape
    out_s = jnp.full((b + 1, w, k), -jnp.inf, jnp.float32)
    out_i = jnp.full((b + 1, w, k), -1, jnp.int32)
    shard_col = jnp.arange(w)[:, None]          # broadcast against [w, C]
    out_s = out_s.at[qidx, shard_col].set(scores)
    out_i = out_i.at[qidx, shard_col].set(ids)
    return out_s[:b].reshape(b, w * k), out_i[:b].reshape(b, w * k)


def _search_scatter_merge(arena: ShardArena, mask: jnp.ndarray,
                          queries: jnp.ndarray, *, metric: str, k: int,
                          ef: int, capacity: int, max_iters: int,
                          use_kernel: bool, shard_axis: str,
                          tag_words=None, filter_words=None):
    """The shared post-routing pipeline body: shard_search -> scatter ->
    dedup merge. Both jitted entry points delegate here. With
    ``tag_words``/``filter_words`` the per-shard partials arrive already
    alive-masked (pre-merge filtering), so the merge needs no extra
    mask."""
    b = queries.shape[0]
    qidx, ids, scores = shard_search(
        arena, mask, queries, metric=metric, k=k, ef=ef,
        capacity=capacity, max_iters=max_iters, shard_axis=shard_axis,
        tag_words=tag_words, filter_words=filter_words)
    flat_s, flat_i = scatter_partials(qidx, ids, scores, b)
    top_s, top_i = merge_topk(flat_s, flat_i, k=k, use_kernel=use_kernel)
    return top_i, top_s


@functools.partial(jax.jit, static_argnames=(
    "metric", "k", "ef", "branching_factor", "capacity", "max_iters",
    "naive", "use_kernel", "shard_axis"))
def _fused_routed(arena: ShardArena, meta: H.HNSWArrays,
                  part_of_center: jnp.ndarray, queries: jnp.ndarray, *,
                  metric: str, k: int, ef: int, branching_factor: int,
                  capacity: int, max_iters: int, naive: bool,
                  use_kernel: bool, shard_axis: str,
                  tag_words=None, filter_words=None):
    """route -> shard_search -> scatter -> merge, one jitted program."""
    b = queries.shape[0]
    w = arena.data.shape[0]
    if naive:
        mask = jnp.ones((b, w), dtype=jnp.bool_)
    else:
        mask, _ = route_queries.__wrapped__(
            meta, part_of_center, queries, metric=metric,
            branching_factor=branching_factor, num_shards=w,
            ef=max(64, branching_factor))
    top_i, top_s = _search_scatter_merge(
        arena, mask, queries, metric=metric, k=k, ef=ef,
        capacity=capacity, max_iters=max_iters, use_kernel=use_kernel,
        shard_axis=shard_axis, tag_words=tag_words,
        filter_words=filter_words)
    return top_i, top_s, mask


@functools.partial(jax.jit, static_argnames=(
    "metric", "k", "ef", "capacity", "max_iters", "use_kernel",
    "shard_axis"))
def _fused_masked(arena: ShardArena, mask: jnp.ndarray,
                  queries: jnp.ndarray, *, metric: str, k: int, ef: int,
                  capacity: int, max_iters: int, use_kernel: bool,
                  shard_axis: str, tag_words=None, filter_words=None):
    """shard_search -> scatter -> merge with a caller-provided mask."""
    return _search_scatter_merge(
        arena, mask, queries, metric=metric, k=k, ef=ef,
        capacity=capacity, max_iters=max_iters, use_kernel=use_kernel,
        shard_axis=shard_axis, tag_words=tag_words,
        filter_words=filter_words)


def arena_search(arena: ShardArena, meta: H.HNSWArrays,
                 part_of_center: jnp.ndarray, queries: jnp.ndarray, *,
                 metric: str, k: int, ef: int = 100,
                 branching_factor: int = 4,
                 capacity: Optional[int] = None,
                 capacity_factor: float = 2.0, max_iters: int = 400,
                 naive: bool = False, use_kernel: bool = True,
                 mask: Optional[jnp.ndarray] = None,
                 shard_axis: Optional[str] = None,
                 tag_words: Optional[jnp.ndarray] = None,
                 filter_words: Optional[jnp.ndarray] = None):
    """Fused distributed search over a device-resident arena (Alg. 4).

    Routes through the replicated meta-HNSW, beam-searches the <= K
    routed shards per query under a per-shard capacity bound, and merges
    partials with the dedup-top-k kernel — one jitted program, no host
    round-trips between the stages.

    Args:
      queries: [B, d] *preprocessed* queries (see ``M.preprocess_queries``).
      capacity: per-shard query slots; defaults to
        ``ceil(B * K / w * capacity_factor)`` (B when ``naive``) — the
        paper's throughput mechanism realised as a FLOP bound.
      naive: search every shard (the HNSW-naive baseline of Sec. III).
      mask: optional precomputed [B, w] routing mask; skips the routing
        stage (the reference path uses this to guarantee zero drops).
      shard_axis: "kernel" | "vmap" | "map" shard-axis strategy (see
        :func:`shard_search`); defaults to "kernel" — ONE strategy on
        every backend, retiring the old CPU "map" special case.
      use_kernel: merge with the ``merge_topk`` Pallas kernel on TPU
        (False forces its jnp oracle).
      tag_words / filter_words: optional metadata alive-mask (see
        :func:`shard_search`): routing stays filter-blind, the per-shard
        walk emits only alive candidates, the merge fills k from those.
        Callers size ``ef``/``k`` for low selectivity via
        ``repro.core.filters.inflation`` (``search_single_host`` does).

    Returns (ids [B, k] i32, scores [B, k] f32, mask [B, w] bool).
    """
    b = queries.shape[0]
    w = arena.num_shards
    if shard_axis is None:
        shard_axis = "kernel"
    if capacity is None:
        if naive:
            capacity = b
        else:
            capacity = int(np.ceil(
                b * branching_factor / w * capacity_factor))
    capacity = max(1, min(b, int(capacity)))
    if mask is not None:
        ids, scores = _fused_masked(
            arena, jnp.asarray(mask), queries, metric=metric, k=k, ef=ef,
            capacity=capacity, max_iters=max_iters, use_kernel=use_kernel,
            shard_axis=shard_axis, tag_words=tag_words,
            filter_words=filter_words)
        return ids, scores, mask
    return _fused_routed(
        arena, meta, part_of_center, queries, metric=metric, k=k, ef=ef,
        branching_factor=branching_factor, capacity=capacity,
        max_iters=max_iters, naive=naive, use_kernel=use_kernel,
        shard_axis=shard_axis, tag_words=tag_words,
        filter_words=filter_words)
