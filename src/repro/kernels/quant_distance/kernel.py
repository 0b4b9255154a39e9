"""Pallas TPU kernel: asymmetric float32-query x int8-database distances.

The quantized arena's distance scan: each grid step dequantizes one
[block_n, d] int8 tile in VMEM (one fused multiply-add on the VPU) and
scores a [block_q, d] float32 query tile against it on the MXU — the
int8 codes are what crosses HBM, so the scan moves ~4x fewer bytes than
the float path on the same memory-bandwidth-bound hot loop.

Grid is 2-D over (query blocks, database blocks), fully parallel; the
scale/zero vectors ride along replicated ([1, d] blocks). Metric
formulas mirror ``repro.core.metrics.similarity_matrix`` exactly
(including the angular epsilon) so kernel / jnp oracle / numpy twin
share one semantics — same three-implementation contract as
``merge_topk``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12  # angular epsilon, identical to repro.core.metrics


def _quant_distance_kernel(q_ref, c_ref, s_ref, z_ref, out_ref, *,
                           metric: str):
    q = q_ref[...]                                     # [bq, d] f32
    x = c_ref[...].astype(jnp.float32) * s_ref[...] + z_ref[...]  # [bn, d]
    dot = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [bq, bn]
    if metric == "l2":
        qn = jnp.sum(q * q, axis=-1, keepdims=True)
        xn = jnp.sum(x * x, axis=-1)
        out_ref[...] = 2.0 * dot - qn - xn[None, :]
    elif metric == "ip":
        out_ref[...] = dot
    elif metric == "angular":
        qn = jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True)) + _EPS
        xn = jnp.sqrt(jnp.sum(x * x, axis=-1)) + _EPS
        out_ref[...] = dot / (qn * xn[None, :])
    else:
        raise ValueError(f"unknown metric {metric!r}")


@functools.partial(jax.jit, static_argnames=("metric", "block_q",
                                             "block_n", "interpret"))
def quant_distance_pallas(q: jnp.ndarray, codes: jnp.ndarray,
                          scale: jnp.ndarray, zero: jnp.ndarray, *,
                          metric: str, block_q: int = 128,
                          block_n: int = 512, interpret: bool = False):
    """Blocked asymmetric distance scan.

    Args:
      q: [B, d] f32 preprocessed queries.
      codes: [n, d] int8 database codes.
      scale: [d] f32 per-dimension step.
      zero: [d] f32 per-dimension zero-point.

    Returns [B, n] f32 similarities. Padding rows/columns introduced for
    the block grid are computed-and-trimmed (pad queries are zeros, pad
    codes are zero codes); callers mask invalid rows themselves.
    """
    b, d = q.shape
    n = codes.shape[0]
    assert codes.shape == (n, d), (codes.shape, q.shape)

    block_q = min(block_q, max(8, b))
    block_n = min(block_n, max(8, n))
    pb = -(-b // block_q) * block_q
    pn = -(-n // block_n) * block_n
    qp = jnp.zeros((pb, d), jnp.float32).at[:b].set(q.astype(jnp.float32))
    cp = jnp.zeros((pn, d), jnp.int8).at[:n].set(codes)

    kernel = functools.partial(_quant_distance_kernel, metric=metric)
    out = pl.pallas_call(
        kernel,
        grid=(pb // block_q, pn // block_n),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pb, pn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(qp, cp, scale.reshape(1, d).astype(jnp.float32),
      zero.reshape(1, d).astype(jnp.float32))
    return out[:b, :n]
