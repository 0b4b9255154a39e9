"""Pallas TPU kernel: blocked similarity scan with running top-k.

This is the compute hotspot of the paper (DESIGN.md §3): scoring a query
batch against a dense block of vectors shows up in
  * k-means assignment (Alg. 3 line 4 / Alg. 5 line 5),
  * partition assignment of every dataset item (Alg. 3 lines 7-10),
  * MIPS norm-replication top-r search (Alg. 5 line 14),
  * brute-force rerank of candidate sets during query processing.

TPU mapping: the database is streamed HBM -> VMEM in ``block_n`` row tiles;
the query tile stays VMEM-resident; the [block_q, block_n] similarity tile is
one MXU matmul; a running top-k accumulator lives in VMEM scratch across the
sequential database grid dimension. Top-k maintenance is k rounds of
masked-argmax (k is small and static), which avoids an in-kernel sort.

Grid: (q_blocks, db_blocks) with the db dimension sequential ("arbitrary")
so the scratch accumulator carries across database tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.0e38  # python float so the kernel doesn't capture a traced const


def _merge_topk(acc_scores, acc_ids, new_scores, new_ids, k: int):
    """k rounds of masked argmax over the concatenation -> new (scores, ids).

    acc_*: [bq, k]; new_*: [bq, bn]. Returns sorted-descending [bq, k].
    """
    cat_s = jnp.concatenate([acc_scores, new_scores], axis=1)  # [bq, k+bn]
    cat_i = jnp.concatenate([acc_ids, new_ids], axis=1)
    out_s = []
    out_i = []
    for _ in range(k):
        j = jnp.argmax(cat_s, axis=1)                          # [bq]
        rows = jax.lax.broadcasted_iota(jnp.int32, cat_s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, cat_s.shape, 1)
        sel = cols == j[:, None]
        out_s.append(jnp.max(jnp.where(sel, cat_s, NEG_INF), axis=1))
        out_i.append(jnp.max(jnp.where(sel, cat_i, -1), axis=1))
        cat_s = jnp.where(sel, NEG_INF, cat_s)
        del rows
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _topk_kernel(q_ref, db_ref, out_s_ref, out_i_ref,
                 acc_s_ref, acc_i_ref, *, k: int, metric: str,
                 block_n: int, total_n: int):
    db_idx = pl.program_id(1)
    num_db = pl.num_programs(1)

    @pl.when(db_idx == 0)
    def _init():
        acc_s_ref[...] = jnp.full_like(acc_s_ref, NEG_INF)
        acc_i_ref[...] = jnp.full_like(acc_i_ref, -1)

    q = q_ref[...].astype(jnp.float32)          # [bq, d]
    x = db_ref[...].astype(jnp.float32)         # [bn, d]

    if metric == "angular":
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-12)

    sims = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # [bq, bn] on the MXU
    if metric == "l2":
        sims = 2.0 * sims - jnp.sum(q * q, -1, keepdims=True) \
            - jnp.sum(x * x, -1)[None, :]

    # mask padded database rows (beyond total_n)
    base = db_idx * block_n
    local = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
    gids = base + local
    sims = jnp.where(gids < total_n, sims, NEG_INF)

    new_s, new_i = _merge_topk(
        acc_s_ref[...], acc_i_ref[...], sims, gids, k)
    acc_s_ref[...] = new_s
    acc_i_ref[...] = new_i

    @pl.when(db_idx == num_db - 1)
    def _flush():
        out_s_ref[...] = acc_s_ref[...]
        out_i_ref[...] = acc_i_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "block_q", "block_n", "interpret"))
def topk_similarity_pallas(queries: jnp.ndarray, database: jnp.ndarray, *,
                           k: int, metric: str = "l2", block_q: int = 128,
                           block_n: int = 512, interpret: bool = False):
    """Blocked top-k similarity scan. Returns (scores [B,k], ids [B,k])."""
    b, d = queries.shape
    n, d2 = database.shape
    assert d == d2, (d, d2)
    assert k <= block_n, "k must fit in one database block"

    block_q = min(block_q, max(8, b))
    pb = -(-b // block_q) * block_q
    pn = -(-n // block_n) * block_n
    qp = jnp.zeros((pb, d), queries.dtype).at[:b].set(queries)
    xp = jnp.zeros((pn, d), database.dtype).at[:n].set(database)

    grid = (pb // block_q, pn // block_n)
    kernel = functools.partial(
        _topk_kernel, k=k, metric=metric, block_n=block_n, total_n=n)

    out_s, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pb, k), jnp.float32),
            jax.ShapeDtypeStruct((pb, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qp, xp)
    return out_s[:b], out_i[:b]
