"""Pallas TPU kernel: flash-decode — single-token GQA attention over a
long KV cache with online softmax.

Targets the memory-bound long-context decode identified in EXPERIMENTS.md
§Roofline (after the ring-cache work, reading the global-layer caches IS
the bottleneck): the cache is streamed HBM -> VMEM once in ``block_s`` row
tiles; running (max, sum, acc) live in VMEM scratch, so probabilities
never round-trip to HBM and the only cache traffic is the single
streaming read.

Grid: (B * KV, S_blocks), sequential in the S dimension (scratch carries
the online-softmax state). Each program handles all G = H/KV query heads
of one (batch row, kv head) pair — MXU-shaped [G, hd] x [hd, block_s].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.0e38


def _flash_decode_kernel(pos_ref, q_ref, k_ref, v_ref, out_ref,
                         m_ref, l_ref, acc_ref, *, block_s: int,
                         scale: float):
    s_idx = pl.program_id(1)
    num_s = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # [G, hd]
    k = k_ref[0].astype(jnp.float32)          # [block_s, hd]
    v = v_ref[0].astype(jnp.float32)          # [block_s, hd]
    pos = pos_ref[0]

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [G, block_s]
    kpos = s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(kpos <= pos, scores, NEG_INF)

    m_prev = m_ref[...]                        # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    # guard: all-masked block keeps m at NEG_INF; exp(NEG_INF-NEG_INF)
    # would be NaN, so rescale only when finite
    rescale = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
    p = jnp.exp(jnp.where(scores > NEG_INF / 2, scores - m_new, NEG_INF))
    l_new = l_ref[...] * rescale + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_ref[...] * rescale + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # [G, hd]

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(s_idx == num_s - 1)
    def _flush():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        pos: jnp.ndarray, *, block_s: int = 512,
                        interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, hd]; k, v: [B, S, KV, hd]; pos: [B] -> out [B, H, hd] f32."""
    b, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    groups = h // kvh
    block_s = min(block_s, s)
    ps = -(-s // block_s) * block_s
    if ps != s:
        pad = ps - s
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded rows are masked by kpos <= pos (pos < s always)
    # layout: one program per (b, kv head): q [B*KV, G, hd],
    # k/v [B*KV, S, hd]
    qr = q.reshape(b, kvh, groups, hd).reshape(b * kvh, groups, hd)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * kvh, ps, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * kvh, ps, hd)
    posr = jnp.repeat(pos, kvh)

    kernel = functools.partial(
        _flash_decode_kernel, block_s=block_s, scale=hd ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(b * kvh, ps // block_s),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, groups, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_s, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_s, hd), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, groups, hd), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, groups, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, 1), jnp.float32),
            pltpu.VMEM((groups, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(posr, qr, kr, vr)
    return out.reshape(b, kvh, groups, hd).reshape(b, h, hd)
