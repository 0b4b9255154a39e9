"""Public op: dedup-top-k merge (Pallas on TPU, jnp oracle elsewhere).

``merge_topk`` is THE coordinator merge — the fused arena pipeline, the
single-host reference path and the SPMD ``shard_map`` program all call it
(the serving engine's per-query host merge uses the numpy twin in
``ref.py``). Dispatch: compiled Pallas kernel on TPU; the jnp oracle
everywhere else — this is a production hot path, so off-TPU it should
run as compiled XLA rather than the interpret-mode kernel (which exists
for validation and is exercised directly by the kernel tests).
``use_kernel=False`` forces the oracle; the SPMD ``shard_map`` program
uses it, since the kernel has not been compiled inside that program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.merge_topk.kernel import merge_topk_pallas
from repro.kernels.merge_topk.ref import merge_topk_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def merge_impl() -> str:
    """Which implementation :func:`merge_topk` dispatches to on this
    backend (benchmark artifacts record this so the perf trajectory
    names what was actually measured)."""
    return "pallas-kernel" if _on_tpu() else "xla-oracle"


def merge_topk(scores: jnp.ndarray, ids: jnp.ndarray, *, k: int,
               alive=None, use_kernel: bool = True, block_q: int = 128):
    """k best entries per query with duplicate ids removed.

    Args:
      scores: [B, m] f32 flattened partial scores (-inf = empty slot).
      ids: [B, m] int external ids (-1 = empty slot).
      k: entries to keep; if k > m the inputs are padded up.
      alive: optional [B, m] bool alive-mask (metadata filters,
        tombstones): dead entries are demoted to the (-inf, -1) padding
        convention BEFORE the merge, so filtering can never under-fill
        the k live winners. Applied identically ahead of every
        implementation (kernel / oracle / numpy twin).
      use_kernel: False forces the jnp oracle.

    Returns (scores [B, k] f32 descending, ids [B, k] i32), (-inf, -1)
    padded — best-occurrence-wins on duplicate ids, ties broken by input
    position, identically in every implementation.
    """
    ids = ids.astype(jnp.int32)
    scores = scores.astype(jnp.float32)
    if alive is not None:
        scores = jnp.where(alive, scores, -jnp.inf)
        ids = jnp.where(alive, ids, -1)
    m = scores.shape[1]
    if k > m:
        pad = k - m
        scores = jnp.pad(scores, ((0, 0), (0, pad)),
                         constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    if not use_kernel or not _on_tpu():
        return merge_topk_ref(scores, ids, k=k)
    out_s, out_i = merge_topk_pallas(scores, ids, k=k, block_q=block_q)
    return jnp.where(out_i >= 0, out_s, -jnp.inf), out_i
