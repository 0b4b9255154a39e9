"""Distributed query processing (Alg. 4) as an SPMD JAX program.

The paper's Kafka topic-per-sub-HNSW dispatch becomes capacity-bounded
dispatch over the ``model`` mesh axis (DESIGN.md §3):

  * the w sub-HNSWs live in ONE device-resident :class:`ShardArena`
    (``repro.core.arena``), sharded over ``model`` (each device owns
    w / |model| shards);
  * every device routes the (replicated) query batch through the
    replicated meta-HNSW, picks the <= C queries assigned to *its* shards
    (``jnp.nonzero(..., size=C)`` = static-shape queue draining), searches
    its local sub-HNSWs, and
  * partial results are combined with an ``all_gather`` + scatter +
    ``merge_topk`` dedup merge — the coordinator merge of Alg. 4 line 9.

Per-shard work drops from B queries (HNSW-naive) to C ≈ B·K/w — the paper's
throughput mechanism, realised as a FLOP reduction instead of queue load.

All three search paths (this SPMD program, ``search_single_host``, the
serving engine) are thin orchestrations of the same arena building blocks
— ``shard_search`` / ``scatter_partials`` / ``merge_topk`` — so they
cannot drift apart in merge or dedup semantics. ``search_single_host`` is
the single-host entry point used by tests, examples and CPU benchmarks;
``search_single_host_python`` preserves the pre-arena per-shard Python
loop as an independent oracle (and the "before" side of the fused-merge
microbench in ``benchmarks/fig7_throughput.py``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.common.config import PyramidConfig
from repro.core import filters as F
from repro.core import hnsw as H
from repro.core import metrics as M
from repro.core import quant as Q
from repro.core.arena import (QuantizedShardArena, ShardArena,
                              arena_search, scatter_partials,
                              shard_search)
from repro.core.meta_index import PyramidIndex
from repro.core.router import route_queries
from repro.kernels.merge_topk import merge_topk

# Back-compat aliases: StackedShards was promoted to
# ``repro.core.arena.ShardArena`` (same pytree layout and field order).
StackedShards = ShardArena


def stack_shards(index: PyramidIndex) -> ShardArena:
    """Deprecated alias for ``index.arena()`` (memoised; prefer that)."""
    return index.arena()


# ---------------------------------------------------------------------------
# Single-host path (fused arena pipeline)
# ---------------------------------------------------------------------------


def _pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def search_single_host(index: PyramidIndex, queries: np.ndarray, k: int, *,
                       ef: Optional[int] = None,
                       branching_factor: Optional[int] = None,
                       naive: bool = False, quantize: bool = False,
                       rerank_factor: int = 4,
                       filter_tags=None):
    """Alg. 4 single-host entry point, on the fused arena pipeline.

    Routes on device, then runs ``arena_search`` with a precomputed mask
    and capacity = the *actual* max per-shard load — exact reference
    semantics (no capacity drops) while still bounding per-shard work.
    The batch is padded to a power of two and the capacity to a multiple
    of 32 (tighter: capacity overshoot multiplies by w shards) so
    repeated calls with varying routing fan-out reuse the jit cache.

    naive=True searches every shard (the HNSW-naive baseline of Sec. III).
    quantize=True runs the pipeline over the int8 arena
    (``index.arena(dtype="int8")``): the beam search scores asymmetric
    float32-query x int8-database distances, returns the top
    ``rerank_factor * k`` candidates, and an exact float32 rerank
    against ``index.rerank_table()`` keeps the k best — recall@10 stays
    within 1% of the float path (see ``tests/test_quant.py``) while the
    device vector payload shrinks ~4x.

    ``filter_tags`` (scalar int64, or [B] per query) runs metadata-
    filtered kNN (``repro.core.filters``): the alive-mask is applied on
    device at the walk's candidate emission — pre-merge, never
    post-filter-then-under-fill — and the candidate budget
    (``ef``/per-shard k/``rerank_factor``) auto-inflates by
    1/selectivity (capped) so thin filters keep filling k.

    Returns (ids [B, k], scores [B, k], mask [B, w]); with
    ``quantize=True`` the scores are exact float32 similarities.
    """
    cfg = index.config
    ef = ef or cfg.ef_search
    kb = branching_factor or cfg.branching_factor
    metric = "ip" if cfg.is_mips else cfg.metric
    q = M.preprocess_queries(queries, cfg.metric)
    b = q.shape[0]
    w = index.num_shards
    arena = index.arena("int8" if quantize else "float32")

    tag_words = None
    filters_np = None
    inflate = 1
    if filter_tags is not None:
        filters_np = np.broadcast_to(
            np.asarray(filter_tags, dtype=np.int64), (b,)).copy()
        if np.any(filters_np != 0):
            tag_words = index.tags_arena()
            # size the candidate budget for the thinnest filter in the
            # batch (the filter-selectivity rerank rule, see API.md)
            sel = min(F.selectivity_np(index.tags_host(), int(f))
                      for f in np.unique(filters_np))
            inflate = F.inflation(sel)
        else:
            filters_np = None

    k_search = (k * rerank_factor if quantize else k) * inflate
    ef = max(ef * inflate, k_search)

    if naive:
        mask = np.ones((b, w), dtype=bool)
    else:
        mask_j, _ = route_queries(
            index.meta_arrays(), jnp.asarray(index.part_of_center),
            jnp.asarray(q), metric=metric, branching_factor=kb,
            num_shards=w, ef=max(64, kb))
        mask = np.asarray(mask_j)

    bp = _pow2(b)
    qp = q
    mp = mask
    fp = filters_np
    if bp > b:   # pad with the first query, routed nowhere
        qp = np.concatenate([q, np.repeat(q[:1], bp - b, axis=0)])
        mp = np.concatenate(
            [mask, np.zeros((bp - b, w), dtype=bool)])
        if fp is not None:   # pad rows run unfiltered (routed nowhere)
            fp = np.concatenate([fp, np.zeros(bp - b, np.int64)])
    max_load = int(mp.sum(axis=0).max())
    capacity = min(bp, max(32, -(-max_load // 32) * 32))

    filter_words = None
    if fp is not None:
        filter_words = jnp.asarray(F.filter_words(fp))
    ids, scores, _ = arena_search(
        arena, None, None, jnp.asarray(qp), metric=metric, k=k_search,
        ef=ef, capacity=capacity, mask=jnp.asarray(mp),
        tag_words=tag_words, filter_words=filter_words)
    if quantize:
        table_ids, table_vecs = index.rerank_table()
        out_ids, out_scores = Q.exact_rerank_np(
            q, np.asarray(ids)[:b], k, table_ids=table_ids,
            table_vecs=table_vecs, metric=metric)
        return out_ids, out_scores, mask
    return (np.asarray(ids)[:b, :k].astype(np.int64),
            np.asarray(scores)[:b, :k], mask)


def search_single_host_python(index: PyramidIndex, queries: np.ndarray,
                              k: int, *, ef: Optional[int] = None,
                              branching_factor: Optional[int] = None,
                              naive: bool = False):
    """Pre-arena reference: per-shard Python loop + host heap-free merge.

    Kept as an independent oracle for the fused pipeline (parity tests)
    and as the "before" baseline of the fig7 merge microbench, so it
    reproduces the pre-arena cost profile faithfully: each shard is
    uploaded as its own [n_i]-shaped ``device_arrays()`` per call (no
    shared arena, per-shard jit shapes). Same return contract as
    :func:`search_single_host`.
    """
    cfg = index.config
    ef = ef or cfg.ef_search
    kb = branching_factor or cfg.branching_factor
    metric = "ip" if cfg.is_mips else cfg.metric
    q = M.preprocess_queries(queries, cfg.metric)
    b = q.shape[0]
    w = index.num_shards

    if naive:
        mask = np.ones((b, w), dtype=bool)
    else:
        mask_j, _ = route_queries(
            index.meta_arrays(), jnp.asarray(index.part_of_center),
            jnp.asarray(q), metric=metric, branching_factor=kb,
            num_shards=w, ef=max(64, kb))
        mask = np.asarray(mask_j)

    all_scores = np.full((b, w, k), -np.inf, np.float32)
    all_ids = np.full((b, w, k), -1, np.int64)
    for s in range(w):
        sel = np.where(mask[:, s])[0]
        if sel.size == 0 or index.subs[s].n == 0:
            continue
        arrs = index.subs[s].device_arrays()   # pre-arena: private upload
        kk = min(k, index.subs[s].n)
        padded = _pow2(sel.size)   # pad for jit-cache reuse across fan-outs
        qs = q[sel]
        if padded > sel.size:
            qs = np.concatenate(
                [qs, np.repeat(qs[:1], padded - sel.size, axis=0)])
        ids, scores = H.hnsw_search(
            arrs, jnp.asarray(qs), metric=metric, k=kk, ef=ef)
        all_ids[sel, s, :kk] = np.asarray(ids)[: sel.size]
        all_scores[sel, s, :kk] = np.asarray(scores)[: sel.size]

    out_ids, out_scores = python_loop_merge(
        all_scores.reshape(b, -1), all_ids.reshape(b, -1), k)
    return out_ids, out_scores, mask


def python_loop_merge(flat_scores: np.ndarray, flat_ids: np.ndarray,
                      k: int):
    """The pre-arena per-query Python dedup merge (argsort + ``set``).

    Kept verbatim as the "before" side of the merge microbench — the
    fused pipeline replaces it with the ``merge_topk`` kernel.
    Dedupes replicated ids (MIPS replication may return one item twice).
    """
    b = flat_scores.shape[0]
    order = np.argsort(-flat_scores, axis=1)
    out_ids = np.full((b, k), -1, np.int64)
    out_scores = np.full((b, k), -np.inf, np.float32)
    for i in range(b):
        seen = set()
        j = 0
        for idx in order[i]:
            v = int(flat_ids[i, idx])
            if v < 0 or v in seen:
                continue
            seen.add(v)
            out_ids[i, j] = v
            out_scores[i, j] = flat_scores[i, idx]
            j += 1
            if j == k:
                break
    return out_ids, out_scores


# ---------------------------------------------------------------------------
# SPMD path (thin shard_map wrapper over the arena building blocks)
# ---------------------------------------------------------------------------


def make_pyramid_search_fn(mesh: Mesh, cfg: PyramidConfig, *, k: int,
                           batch: int, ef: Optional[int] = None,
                           max_iters: int = 400, naive: bool = False,
                           model_axis: str = "model",
                           data_axis: Optional[str] = None,
                           quantize: bool = False,
                           rerank_factor: int = 4,
                           index: Optional[PyramidIndex] = None):
    """Builds the jitted SPMD search step for a given mesh.

    The returned fn has signature
      fn(arena: ShardArena, meta: HNSWArrays, part_of_center [m],
         queries [B, d]) -> (ids [B, k], scores [B, k])
    with ``arena`` sharded over ``model`` on its leading (shard) axis and
    meta replicated. Capacity C = ceil(B * K / w * capacity_factor)
    (C = B for the naive baseline).

    When ``data_axis`` is given, the query batch is sharded over it (each
    data slice is an independent replica group serving its slice — the
    paper's replication axis) and ``batch`` must be the PER-REPLICA batch.

    With ``quantize=True`` the fn expects a ``QuantizedShardArena``
    (every leaf is shard-leading, so the same ``P(model_axis)`` sharding
    applies) and the on-device program searches/merges the top
    ``rerank_factor * k`` quantized candidates; the exact float32 rerank
    then runs host-side against ``index.rerank_table()`` — the
    full-precision copy lives with the coordinator (the paper's shared
    storage), never in device HBM — so ``index`` is required and the
    wrapper returns numpy ``(ids [B, k] int64, scores [B, k] f32)``.
    """
    metric = "ip" if cfg.is_mips else cfg.metric
    ef = ef or cfg.ef_search
    k_inner = k * rerank_factor if quantize else k
    ef = max(ef, k_inner)
    if quantize and index is None:
        raise ValueError(
            "make_pyramid_search_fn(quantize=True) needs index= for the "
            "exact float32 rerank table")
    w = cfg.num_shards
    n_model = mesh.shape[model_axis]
    assert w % n_model == 0, (w, n_model)
    w_local = w // n_model
    if naive:
        capacity = batch
    else:
        capacity = int(np.ceil(
            batch * cfg.branching_factor / w * cfg.capacity_factor))
        capacity = max(1, min(batch, capacity))

    def spmd(arena: ShardArena, meta: H.HNSWArrays,
             part_of_center: jnp.ndarray, queries: jnp.ndarray):
        my = jax.lax.axis_index(model_axis)
        b = queries.shape[0]

        if naive:
            mask = jnp.ones((b, w), dtype=jnp.bool_)
        else:
            mask, _ = route_queries.__wrapped__(
                meta, part_of_center, queries, metric=metric,
                branching_factor=cfg.branching_factor, num_shards=w,
                ef=max(64, cfg.branching_factor))

        # per-shard search on this device's local slice of the arena
        local_mask = jax.lax.dynamic_slice_in_dim(
            mask, my * w_local, w_local, axis=1)
        qidx, ids, scores = shard_search(
            arena, local_mask, queries, metric=metric, k=k_inner,
            ef=max(ef, k_inner), capacity=capacity, max_iters=max_iters,
            shard_axis="kernel")

        # coordinator merge: gather partials from all shards, then the
        # same scatter + dedup merge as the fused single-host pipeline,
        # through the jnp merge: the Pallas merge has not been compiled
        # inside this shard_map program yet (ROADMAP S7)
        qidx = jax.lax.all_gather(qidx, model_axis, tiled=True)    # [w, C]
        ids = jax.lax.all_gather(ids, model_axis, tiled=True)  # [w, C, k]
        scores = jax.lax.all_gather(scores, model_axis, tiled=True)
        flat_s, flat_i = scatter_partials(qidx, ids, scores, b)
        top_scores, top_ids = merge_topk(flat_s, flat_i, k=k_inner,
                                         use_kernel=False)
        return top_ids, top_scores

    qspec = P(data_axis) if data_axis else P()
    if quantize:
        arena_spec = QuantizedShardArena(
            data=P(model_axis), ids=P(model_axis), bottom=P(model_axis),
            upper=P(model_axis), entry=P(model_axis),
            num_upper_levels=P(model_axis), scale=P(model_axis),
            zero=P(model_axis))
    else:
        arena_spec = ShardArena(
            data=P(model_axis), ids=P(model_axis), bottom=P(model_axis),
            upper=P(model_axis), entry=P(model_axis),
            num_upper_levels=P(model_axis))
    fn = shard_map(
        spmd, mesh=mesh,
        in_specs=(
            arena_spec,
            H.HNSWArrays(P(), P(), P(), P(), P(), P()),  # replicated meta
            P(),
            qspec,
        ),
        out_specs=(qspec, qspec),
        check_vma=False)
    jfn = jax.jit(fn)
    if not quantize:
        return jfn

    def reranked(arena, meta, part_of_center, queries):
        cand_ids, _ = jfn(arena, meta, part_of_center, queries)
        # resolve the table at CALL time (it is memoised on the index
        # and dropped by invalidate_device_cache): a caller that
        # add_items-ed and rebuilt the arena between calls must not
        # rerank new ids against a stale snapshot — they would silently
        # drop to (-1, -inf)
        table_ids, table_vecs = index.rerank_table()
        return Q.exact_rerank_np(
            np.asarray(queries), np.asarray(cand_ids), k,
            table_ids=table_ids, table_vecs=table_vecs, metric=metric)

    return reranked
