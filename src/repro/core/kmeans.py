"""Lloyd's k-means and spherical k-means (Alg. 3 line 4 / Alg. 5 line 5).

Two execution paths:
  * ``kmeans`` — single-host JAX (used by tests, small builds);
  * ``kmeans_distributed`` — shard_map over the data axis; each shard assigns
    its local rows (via the topk_distance kernel, k=1) and contributes
    per-center sums/counts through ``psum`` — the paper's "workers conduct
    distributed kmeans together" (Sec. III-A distributed workflow).

Spherical k-means (for MIPS, [35]) normalises centers to unit norm each
iteration and assigns by inner product.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.topk_distance import topk_similarity


def _init_centers(x: jnp.ndarray, m: int, seed: int, *,
                  method: str = "uniform") -> jnp.ndarray:
    """Initial centers.

    ``method="uniform"`` (the default) samples m *uniform random
    distinct* rows — it is NOT k-means++ (an older docstring overclaimed
    this). ``method="kmeans++"`` runs true D²-weighted seeding (Arthur &
    Vassilvitskii 2007): each next center is drawn with probability
    proportional to its squared distance from the nearest center so far.

    When ``m > n`` (more centers than rows — tiny samples do this)
    distinct sampling is impossible: all n rows are used and the
    remaining ``m - n`` slots are topped up with replacement so callers
    always get m centers (``_finish_update`` keeps duplicate/empty
    centers stable during iteration).
    """
    n = x.shape[0]
    key = jax.random.PRNGKey(seed)
    if method == "kmeans++":
        return _kmeanspp_init(x, m, key)
    if method != "uniform":
        raise ValueError(f"unknown init method {method!r}; "
                         "one of ('uniform', 'kmeans++')")
    if m > n:
        k1, k2 = jax.random.split(key)
        idx = jnp.concatenate([
            jax.random.permutation(k1, n),
            jax.random.choice(k2, n, shape=(m - n,), replace=True)])
    else:
        idx = jax.random.choice(key, n, shape=(m,), replace=False)
    return x[idx]


def _kmeanspp_init(x: jnp.ndarray, m: int, key) -> jnp.ndarray:
    """True k-means++ (D² sampling). O(m·n·d) — same complexity class as
    one Lloyd iteration, so enabling it roughly costs one extra iter."""
    n, d = x.shape
    key, k0 = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    centers = jnp.zeros((m, d), x.dtype).at[0].set(x[first])
    d2 = jnp.sum((x - x[first]) ** 2, axis=-1)

    def body(i, state):
        centers, d2, key = state
        key, kk = jax.random.split(key)
        total = jnp.sum(d2)
        # all-zero D² (m > #distinct rows): fall back to uniform so the
        # draw stays well-defined instead of dividing by zero
        probs = jnp.where(total > 0, d2 / jnp.maximum(total, 1e-30),
                          jnp.full((n,), 1.0 / n, x.dtype))
        idx = jax.random.choice(kk, n, p=probs)
        c = x[idx]
        centers = centers.at[i].set(c)
        d2 = jnp.minimum(d2, jnp.sum((x - c) ** 2, axis=-1))
        return centers, d2, key

    centers, _, _ = jax.lax.fori_loop(1, m, body, (centers, d2, key))
    return centers


def _assign(x: jnp.ndarray, centers: jnp.ndarray, metric: str) -> jnp.ndarray:
    """Nearest center per row ([n] int32). Uses the Pallas scan kernel."""
    _, ids = topk_similarity(x, centers, k=1, metric=metric)
    return ids[:, 0]


def _update(x, assign, m):
    one_hot = jax.nn.one_hot(assign, m, dtype=x.dtype)       # [n, m]
    sums = one_hot.T @ x                                      # [m, d]
    counts = jnp.sum(one_hot, axis=0)                         # [m]
    return sums, counts


def _finish_update(centers, sums, counts, spherical: bool):
    new = sums / jnp.maximum(counts[:, None], 1.0)
    new = jnp.where(counts[:, None] > 0, new, centers)  # keep empty centers
    if spherical:
        new = new / (jnp.linalg.norm(new, axis=-1, keepdims=True) + 1e-12)
    return new


@functools.partial(jax.jit, static_argnames=("m", "iters", "spherical"))
def _kmeans_jit(x, init_centers, *, m, iters, spherical):
    metric = "ip" if spherical else "l2"

    def body(centers, _):
        a = _assign(x, centers, metric)
        sums, counts = _update(x, a, m)
        return _finish_update(centers, sums, counts, spherical), counts

    centers, counts = jax.lax.scan(body, init_centers, None, length=iters)
    return centers, counts[-1]


def kmeans(x: np.ndarray, m: int, *, iters: int = 12, spherical: bool = False,
           seed: int = 0, init: str = "uniform"
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (centers [m, d] f32, counts [m] — size of each cluster).

    ``init`` selects the seeding: ``"uniform"`` (distinct random rows)
    or ``"kmeans++"`` (D²-weighted, see :func:`_init_centers`).
    """
    x = jnp.asarray(x, jnp.float32)
    if spherical:
        x = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
    centers0 = _init_centers(x, m, seed, method=init)
    if spherical:
        centers0 = centers0 / (
            jnp.linalg.norm(centers0, axis=-1, keepdims=True) + 1e-12)
    centers, counts = _kmeans_jit(x, centers0, m=m, iters=iters,
                                  spherical=spherical)
    return np.asarray(centers), np.asarray(counts)


def kmeans_distributed(x_global: jnp.ndarray, m: int, mesh: Mesh, *,
                       data_axis: str = "data", iters: int = 12,
                       spherical: bool = False, seed: int = 0,
                       init: str = "uniform"):
    """Distributed k-means: rows sharded over ``data_axis``.

    Per iteration each shard computes local assignments and psums the
    per-center statistics — identical math to ``kmeans`` (tested against it).
    """
    metric = "ip" if spherical else "l2"
    if spherical:
        x_global = x_global / (
            jnp.linalg.norm(x_global, axis=-1, keepdims=True) + 1e-12)
    init = _init_centers(x_global, m, seed, method=init)
    if spherical:
        init = init / (jnp.linalg.norm(init, axis=-1, keepdims=True) + 1e-12)

    other_axes = tuple(a for a in mesh.axis_names if a != data_axis)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(data_axis, None), P(None, None)),
        out_specs=(P(None, None), P(None)),
        check_vma=False)
    def step(x_local, centers):
        a = _assign(x_local, centers, metric)
        sums, counts = _update(x_local, a, m)
        sums = jax.lax.psum(sums, data_axis)
        counts = jax.lax.psum(counts, data_axis)
        return _finish_update(centers, sums, counts, spherical), counts

    centers = init
    counts = None
    step_j = jax.jit(step)
    for _ in range(iters):
        centers, counts = step_j(x_global, centers)
    del other_axes
    return centers, counts
