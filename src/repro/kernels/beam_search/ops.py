"""The fused beam-search op: the batched XLA walk of ``ref.py`` plus
the metadata alive-mask.

One implementation on every backend. The walk is a ``lax.while_loop``
over all (graph, slot) rows, so it traces anywhere a jitted function
does, ``shard_map`` bodies included.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.beam_search.ref import beam_search_stats


def _apply_filter(scores: jnp.ndarray, nodes: jnp.ndarray,
                  tag_words: jnp.ndarray, filter_words: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Metadata alive-mask on the walk's emitted candidates.

    The navigation beam runs unfiltered (masking mid-walk would
    disconnect the graph); here, after the walk, candidates whose tag
    bitset misses the query's filter are demoted to the (-inf, -1)
    padding convention, so downstream top-k and merges see them exactly
    like structural pad slots.

    tag_words: [S, n, 2] i32 word-split item bitsets; filter_words:
    [S, C, 2] i32 per-slot filters (zero words == no filtering).
    """
    from repro.core.filters import alive_words
    # [S, C, ef', 2] gather of the candidates' tag words, per graph slot
    cand = jax.vmap(lambda tw, nd: tw[jnp.clip(nd, 0)])(tag_words, nodes)
    alive = alive_words(cand, filter_words[:, :, None, :])
    return (jnp.where(alive, scores, -jnp.inf),
            jnp.where(alive, nodes, -1))


def beam_search(data: jnp.ndarray, bottom: jnp.ndarray,
                queries: jnp.ndarray, entries: jnp.ndarray, *,
                metric: str, ef: int, max_iters: int,
                scale: Optional[jnp.ndarray] = None,
                zero: Optional[jnp.ndarray] = None,
                tag_words: Optional[jnp.ndarray] = None,
                filter_words: Optional[jnp.ndarray] = None,
                stats: bool = False):
    """Fused bottom-layer beam walk over a stack of graphs.

    See ``ref.beam_search_ref`` for the shared shape/semantics contract:
    data [S, n, d] (f32, or int8 with scale/zero), bottom [S, n, M0],
    queries [S, C, d], entries [S, C] -> (scores [S, C, ef'],
    local nodes [S, C, ef']) best-first, (-inf, -1) padded.

    ``tag_words`` ([S, n, 2] i32) + ``filter_words`` ([S, C, 2] i32)
    apply the metadata alive-mask of ``repro.core.filters`` to the
    emitted candidates.

    ``stats=True`` also returns each row's expansion count [S, C] i32
    (the loop's trip count is their maximum).
    """
    out_s, out_i, iters = beam_search_stats(
        data, bottom, queries, entries, metric=metric, ef=ef,
        max_iters=max_iters, scale=scale, zero=zero)
    if tag_words is not None and filter_words is not None:
        out_s, out_i = _apply_filter(out_s, out_i, tag_words, filter_words)
    if stats:
        return out_s, out_i, iters
    return out_s, out_i
