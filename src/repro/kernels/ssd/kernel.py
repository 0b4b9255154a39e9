"""Pallas TPU kernel: Mamba2 SSD chunk scan (state-space duality).

The SSD formulation is *designed* for matmul units: each chunk's output is
an intra-chunk [Q, Q] x [Q, P] matmul (MXU) plus a rank-N correction from
the running inter-chunk state. This kernel keeps the running state
[Hb, N, P] in VMEM scratch across the sequential chunk grid dimension, so
the recurrence never round-trips to HBM — the HBM traffic is exactly one
streaming read of (x, dt, B, C) and one write of y.

Grid: (B, H_blocks, n_chunks); chunks sequential ("arbitrary"), batch and
head blocks parallel. Head-major layouts keep BlockSpecs contiguous.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref,
                st_scratch, *, chunk: int):
    c_idx = pl.program_id(2)
    num_c = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        st_scratch[...] = jnp.zeros_like(st_scratch)

    x = x_ref[0, 0].astype(jnp.float32)       # [Hb, Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)     # [Hb, Q]
    a = a_ref[...].astype(jnp.float32)        # [Hb]
    bm = b_ref[0, 0].astype(jnp.float32)      # [Q, N]
    cm = c_ref[0, 0].astype(jnp.float32)      # [Q, N]

    da = dt * a[:, None]                      # [Hb, Q] (negative)
    cum = jnp.cumsum(da, axis=-1)             # [Hb, Q]
    # intra-chunk decay L[h, i, j] = exp(cum[i] - cum[j]) for i >= j
    diff = cum[:, :, None] - cum[:, None, :]
    q_iota = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 2)
    tri = q_iota >= k_iota
    decay_in = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)

    # scores[h, i, j] = (C_i . B_j) * L[h, i, j] * dt[h, j]
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    scores = cb[None] * decay_in * dt[:, None, :]                 # [Hb,Q,Q]
    # intra-chunk output: one [Q, Q] x [Q, P] matmul per head (MXU)
    ydt = jax.lax.dot_general(
        scores, x, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                       # [Hb,Q,P]

    # inter-chunk contribution from the carried state
    state = st_scratch[...]                                       # [Hb,N,P]
    cdec = jnp.exp(cum)                                           # [Hb, Q]
    yoff = jax.lax.dot_general(
        jnp.broadcast_to(cm[None], (state.shape[0],) + cm.shape),
        state, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                       # [Hb,Q,P]
    y = ydt + yoff * cdec[..., None]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S <- S * exp(sum da) + B^T (x * dt * decay_to_end)
    decay_to_end = jnp.exp(cum[:, -1:] - cum)                     # [Hb, Q]
    xw = x * (dt * decay_to_end)[..., None]                       # [Hb,Q,P]
    contrib = jax.lax.dot_general(
        jnp.broadcast_to(bm[None], (state.shape[0],) + bm.shape),
        xw, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                       # [Hb,N,P]
    chunk_decay = jnp.exp(cum[:, -1])                             # [Hb]
    st_scratch[...] = state * chunk_decay[:, None, None] + contrib

    @pl.when(c_idx == num_c - 1)
    def _flush():
        state_ref[0] = st_scratch[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_h", "interpret"))
def ssd_pallas(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
               b_mat: jnp.ndarray, c_mat: jnp.ndarray, *, chunk: int = 128,
               block_h: int = 8, interpret: bool = False):
    """SSD chunk scan. x [B,S,H,P], dt [B,S,H], a [H], b/c [B,S,N].

    Returns (y [B,S,H,P] f32, final_state [B,H,N,P] f32). S is padded to a
    chunk multiple internally (dt=0 padding is a no-op for the scan).
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0 => identity
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // q
    block_h = min(block_h, h)
    while h % block_h:
        block_h -= 1
    hb = h // block_h

    # head-major chunked layouts
    xh = jnp.moveaxis(x.reshape(bsz, nc, q, h, p), 3, 2)   # [B,C,H,Q,P]
    dth = jnp.moveaxis(dt.reshape(bsz, nc, q, h), 3, 2)    # [B,C,H,Q]
    bmc = b_mat.reshape(bsz, nc, q, n)
    cmc = c_mat.reshape(bsz, nc, q, n)

    kernel = functools.partial(_ssd_kernel, chunk=q)
    y, state = pl.pallas_call(
        kernel,
        grid=(bsz, hb, nc),
        in_specs=[
            pl.BlockSpec((1, 1, block_h, q, p),
                         lambda b, hh, c: (b, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, block_h, q),
                         lambda b, hh, c: (b, c, hh, 0)),
            pl.BlockSpec((block_h,), lambda b, hh, c: (hh,)),
            pl.BlockSpec((1, 1, q, n), lambda b, hh, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b, hh, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_h, q, p),
                         lambda b, hh, c: (b, c, hh, 0, 0)),
            pl.BlockSpec((1, block_h, n, p), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nc, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dth, a, bmc, cmc)
    y = jnp.moveaxis(y, 2, 3).reshape(bsz, nc * q, h, p)[:, :s]
    return y, state
