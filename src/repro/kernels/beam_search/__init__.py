"""Fused bottom-layer beam walk: one batched XLA walk + numpy twin."""
from repro.kernels.beam_search.ops import beam_search
from repro.kernels.beam_search.ref import (beam_search_np, beam_search_ref,
                                           beam_search_stats)

__all__ = [
    "beam_search",
    "beam_search_np",
    "beam_search_ref",
    "beam_search_stats",
]
