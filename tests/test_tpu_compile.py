"""Ahead-of-time compiles of the main path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (tiling,
scoped VMEM, device memory). Nothing runs, so these say nothing about
results or times. The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.common.config import PyramidConfig
from repro.core.arena import ShardArena
from repro.core.distributed import make_pyramid_search_fn
from repro.core.hnsw import HNSWArrays
from repro.kernels.beam_search import beam_search_ref
from repro.kernels.merge_topk.kernel import merge_topk_pallas
from repro.kernels.topk_distance.kernel import topk_similarity_pallas

HBM_BYTES = 16 * 2 ** 30        # one v5e chip
D = 96                          # DEEP width
M0, MU = 32, 16                 # PyramidConfig's default HNSW degrees
EF, MAX_ITERS = 100, 400        # PyramidConfig.ef_search, walk bound


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _walk(one_chip, s, n, c, dtype):
    args = [_sds((s, n, D), dtype, one_chip),
            _sds((s, n, M0), jnp.int32, one_chip),
            _sds((s, c, D), jnp.float32, one_chip),
            _sds((s, c), jnp.int32, one_chip)]
    kw = {}
    if dtype == jnp.int8:
        kw = dict(scale=_sds((D,), jnp.float32, one_chip),
                  zero=_sds((D,), jnp.float32, one_chip))
    fn = jax.jit(functools.partial(beam_search_ref, metric="l2", ef=EF,
                                   max_iters=MAX_ITERS))
    return fn.lower(*args, **kw).compile()


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def test_walk_compiles_at_engine_shape(one_chip):
    # one executor: one shard of 2^20 float32 rows, a batch of 16
    # (ServingEngine's executor_batch default)
    compiled = _walk(one_chip, 1, 2 ** 20, 16, jnp.float32)
    assert _device_bytes(compiled) < HBM_BYTES


def test_walk_compiles_at_fused_arena_shape(one_chip):
    # the fused pipeline walks every (shard, slot) row at once: 16
    # shards of 2^20 int8 rows, 64 slots each, must fit one chip
    compiled = _walk(one_chip, 16, 2 ** 20, 64, jnp.int8)
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("b,n,k,metric", [
    (131_072, 256, 1, "l2"),   # k-means / item assignment to centres
    (256, 131_072, 4, "ip"),   # MIPS replication: centres over items
])
def test_topk_similarity_kernel_compiles(one_chip, b, n, k, metric):
    compiled = topk_similarity_pallas.lower(
        _sds((b, D), jnp.float32, one_chip),
        _sds((n, D), jnp.float32, one_chip), k=k, metric=metric).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_merge_topk_kernel_compiles(one_chip):
    # the fused merge: 256 queries x (16 shards x 10) partials -> top 10
    compiled = merge_topk_pallas.lower(
        _sds((256, 160), jnp.float32, one_chip),
        _sds((256, 160), jnp.int32, one_chip), k=10).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_spmd_search_compiles_on_four_devices(topo):
    w, n, levels, m, batch = 16, 2 ** 17, 4, 1024, 512
    cfg = PyramidConfig(metric="l2", num_shards=w, meta_size=m)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    fn = make_pyramid_search_fn(mesh, cfg, k=10, batch=batch)
    sharded = NamedSharding(mesh, P("model"))
    repl = NamedSharding(mesh, P())
    arena = ShardArena(
        data=_sds((w, n, D), jnp.float32, sharded),
        ids=_sds((w, n), jnp.int32, sharded),
        bottom=_sds((w, n, M0), jnp.int32, sharded),
        upper=_sds((w, levels, n, MU), jnp.int32, sharded),
        entry=_sds((w,), jnp.int32, sharded),
        num_upper_levels=_sds((w,), jnp.int32, sharded))
    meta = HNSWArrays(
        data=_sds((m, D), jnp.float32, repl),
        ids=_sds((m,), jnp.int32, repl),
        bottom=_sds((m, M0), jnp.int32, repl),
        upper=_sds((levels, m, MU), jnp.int32, repl),
        entry=_sds((), jnp.int32, repl),
        num_upper_levels=_sds((), jnp.int32, repl))
    compiled = fn.lower(arena, meta, _sds((m,), jnp.int32, repl),
                        _sds((batch, D), jnp.float32, repl)).compile()
    assert "all-gather" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES
