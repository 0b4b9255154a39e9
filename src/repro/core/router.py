"""Query -> sub-HNSW routing (Alg. 4 lines 4-6).

Routing searches the (replicated, small) meta-HNSW for the query's top-K
meta neighbours and marks the partitions containing them. This is exactly
top-K expert routing: downstream we reuse the same capacity-based dispatch
machinery as the MoE layers (DESIGN.md §3/§4).
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Set, Tuple

import jax
import jax.numpy as jnp

from repro.core import hnsw as H

_EF_RAISED_WARNED: Set[Tuple[int, int]] = set()


def effective_ef(ef: int, branching_factor: int) -> int:
    """The beam width routing actually searches with: the meta search
    cannot return K = ``branching_factor`` neighbours from a narrower
    beam, so ``ef`` is raised to K when the caller's value is smaller.
    Exposed so serving surfaces (``ServingEngine.stats()['routing']``)
    can report the real value instead of the requested one."""
    return max(ef, branching_factor)


@functools.partial(jax.jit, static_argnames=("metric", "branching_factor",
                                             "num_shards", "ef"))
def _route_queries(meta: H.HNSWArrays, part_of_center: jnp.ndarray,
                   queries: jnp.ndarray, *, metric: str,
                   branching_factor: int, num_shards: int,
                   ef: int = 64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    k = branching_factor
    meta_ids, _ = H.hnsw_search(meta, queries, metric=metric, k=k,
                                ef=max(ef, k))
    parts = part_of_center[jnp.clip(meta_ids, 0)]          # [B, K]
    parts = jnp.where(meta_ids >= 0, parts, -1)
    onehot = jax.nn.one_hot(
        jnp.clip(parts, 0), num_shards, dtype=jnp.bool_)
    onehot = jnp.logical_and(onehot, (parts >= 0)[..., None])
    return jnp.any(onehot, axis=1), meta_ids


def route_queries(meta: H.HNSWArrays, part_of_center: jnp.ndarray,
                  queries: jnp.ndarray, *, metric: str,
                  branching_factor: int, num_shards: int,
                  ef: int = 64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (mask [B, w] bool — shard s must serve query b,
    meta_ids [B, K] — the routed meta vertices).

    ``ef`` below ``branching_factor`` is raised to it (a K-wide result
    needs a K-wide beam); that used to happen silently — now it warns
    once per (ef, K) combination and the effective value is available
    via :func:`effective_ef` / the engine's ``stats()['routing']``.
    """
    eff = effective_ef(ef, branching_factor)
    if eff != ef and (ef, branching_factor) not in _EF_RAISED_WARNED:
        _EF_RAISED_WARNED.add((ef, branching_factor))
        warnings.warn(
            f"route_queries: requested ef={ef} is narrower than "
            f"branching_factor K={branching_factor}; searching the "
            f"meta-HNSW with effective ef={eff}",
            RuntimeWarning, stacklevel=2)
    return _route_queries(meta, part_of_center, queries, metric=metric,
                          branching_factor=branching_factor,
                          num_shards=num_shards, ef=eff)


# call sites already inside a jitted program (the fused arena pipeline,
# the SPMD shard_map body) trace the un-jitted core directly
route_queries.__wrapped__ = _route_queries.__wrapped__


def access_rate(mask: jnp.ndarray) -> float:
    """Fraction of sub-HNSWs touched per query (paper Fig. 5 metric)."""
    return float(jnp.mean(jnp.sum(mask, axis=1) / mask.shape[1]))


def refresh_centroids(index, *, seed: Optional[int] = None):
    """Recompute the routing layer from the CURRENT items (in place).

    Under sustained inserts/deletes the live data drifts away from the
    kmeans centroids frozen at build time and routing recall/balance
    decay. This re-runs the build-time routing stages — sample →
    kmeans++ → meta-HNSW → balanced min-cut partition → item
    reassignment — over today's vectors, then rebuilds every sub-HNSW
    through ``shard_seed`` (``w`` stays fixed; split/merge changes it,
    see ``repro.build.planner``). Deterministic given ``seed``
    (defaults to the config seed), so replay/recovery via the store
    reproduces the identical index. Expensive (a full rebuild minus
    preprocessing) — the maintenance compactor triggers it only when
    drift crosses its threshold, never on the serving path.
    """
    import numpy as np

    from repro.core.kmeans import kmeans
    from repro.core.meta_index import _assign_items, _sample
    from repro.core.partition import balance_stats, partition_graph

    cfg = index.config
    seed = cfg.seed if seed is None else seed
    live = [g for g in index.subs if g.n]
    if not live:
        return index
    x = np.concatenate([g.data for g in live])
    ids = np.concatenate([g.ids for g in live])
    # MIPS norm-replication stores one id in several shards: collapse
    # to one row per global id before re-partitioning
    _, first = np.unique(ids, return_index=True)
    first = np.sort(first)
    x, ids = x[first], ids[first]
    n = x.shape[0]
    m = min(cfg.meta_size, max(cfg.num_shards, n // 4))
    rng = np.random.default_rng(seed)
    sample = _sample(x, cfg.sample_size, rng)
    centers, counts = kmeans(sample, m, iters=cfg.kmeans_iters,
                             spherical=cfg.is_mips, seed=seed,
                             init="kmeans++")
    metric = "ip" if cfg.is_mips else cfg.metric
    meta = H.build_hnsw(np.asarray(centers, np.float32), metric=metric,
                        max_degree=cfg.max_degree,
                        max_degree_upper=cfg.max_degree_upper,
                        ef_construction=cfg.ef_construction, seed=seed)
    weights = np.asarray(counts, dtype=np.float64) + 1.0
    part_of_center = partition_graph(
        meta.neighbors[0], weights, cfg.num_shards, seed=seed)
    item_part = _assign_items(
        x, meta.device_arrays(), part_of_center, metric)
    for s in range(cfg.num_shards):
        sel = item_part == s
        index.subs[s] = H.build_hnsw(
            x[sel], metric=metric, max_degree=cfg.max_degree,
            max_degree_upper=cfg.max_degree_upper,
            ef_construction=cfg.ef_construction,
            seed=H.shard_seed(cfg.seed, s), ids=ids[sel])
    index.meta = meta
    index.part_of_center = part_of_center.astype(np.int32)
    index.build_stats["sub_sizes"] = [g.n for g in index.subs]
    index.build_stats["total_stored"] = sum(g.n for g in index.subs)
    index.build_stats["balance"], _ = balance_stats(
        weights, part_of_center, cfg.num_shards)
    index.build_stats["centroid_refreshes"] = 1 + int(
        index.build_stats.get("centroid_refreshes", 0))
    index.invalidate_device_cache()
    return index
