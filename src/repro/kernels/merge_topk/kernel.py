"""Pallas TPU kernel: dedup-top-k merge of per-shard partial results.

The coordinator combine of Alg. 4 line 9: each query's w*k partial
(score, id) pairs collapse to the k best with duplicate external ids
removed (MIPS replication can return one global id from two shards).

TPU mapping (same style as ``topk_distance``): the [block_q, m] partial
tile lives in VMEM (m = w*k is small); selection is k rounds of masked
argmax — after each round an *id-match mask* retires every entry carrying
the selected external id, which performs the dedup for free inside the
selection loop instead of as a separate host pass. Grid is 1-D over query
blocks, fully parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.0e38  # python float so the kernel doesn't capture a traced const


def _merge_kernel(s_ref, i_ref, out_s_ref, out_i_ref, *, k: int):
    s = s_ref[...]                                     # [bq, m]
    ids = i_ref[...]                                   # [bq, m]
    s = jnp.where(ids >= 0, s, NEG_INF)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    out_s = []
    out_i = []
    for _ in range(k):
        j = jnp.argmax(s, axis=1)                      # [bq]
        sel = cols == j[:, None]
        best_s = jnp.max(jnp.where(sel, s, NEG_INF), axis=1)
        best_i = jnp.max(jnp.where(sel, ids, -1), axis=1)
        alive = best_s > NEG_INF / 2  # rows with slots left this round
        best_i = jnp.where(alive, best_i, -1)
        out_s.append(jnp.where(alive, best_s, NEG_INF))
        out_i.append(best_i)
        # retire the selection AND every same-id duplicate (replication)
        dup = jnp.logical_and(ids == best_i[:, None], best_i[:, None] >= 0)
        s = jnp.where(jnp.logical_or(sel, dup), NEG_INF, s)
    out_s_ref[...] = jnp.stack(out_s, axis=1)
    out_i_ref[...] = jnp.stack(out_i, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "interpret"))
def merge_topk_pallas(scores: jnp.ndarray, ids: jnp.ndarray, *, k: int,
                      block_q: int = 128, interpret: bool = False):
    """Blocked dedup-top-k merge.

    Args:
      scores: [B, m] f32 partial scores (-inf empty).
      ids: [B, m] i32 external ids (-1 empty).
      k: entries to keep per query (k <= m).

    Returns (scores [B, k] f32, ids [B, k] i32); empty output slots carry
    (NEG_INF, -1) — ``ops.merge_topk`` normalises NEG_INF to -inf.
    """
    b, m = scores.shape
    assert ids.shape == (b, m), (ids.shape, scores.shape)
    assert k <= m, (k, m)

    block_q = min(block_q, max(8, b))
    pb = -(-b // block_q) * block_q
    sp = jnp.full((pb, m), NEG_INF, jnp.float32).at[:b].set(scores)
    ip = jnp.full((pb, m), -1, jnp.int32).at[:b].set(ids)

    kernel = functools.partial(_merge_kernel, k=k)
    out_s, out_i = pl.pallas_call(
        kernel,
        grid=(pb // block_q,),
        in_specs=[
            pl.BlockSpec((block_q, m), lambda i: (i, 0)),
            pl.BlockSpec((block_q, m), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pb, k), jnp.float32),
            jax.ShapeDtypeStruct((pb, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(sp, ip)
    return out_s[:b], out_i[:b]
