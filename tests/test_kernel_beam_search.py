"""beam_search: the fused XLA walk vs its numpy twin, adversarial
visited-mask cases, and integration parity of the paths that ride it
(hnsw_search impl="fused"/"loop", the arena shard_axis strategies,
search_single_host vs the python oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import PyramidConfig
from repro.core import hnsw as H
from repro.core import metrics as M
from repro.core.arena import arena_search
from repro.core.distributed import (search_single_host,
                                    search_single_host_python)
from repro.core.meta_index import build_pyramid_index
from repro.core.quant import QuantParams
from repro.kernels.beam_search import (beam_search, beam_search_np,
                                       beam_search_ref, beam_search_stats)

METRICS = ("l2", "ip", "angular")


def _random_case(s, n, d, c, m0, seed, quantized=False):
    """Arbitrary -1-padded adjacency over integer-grid vectors (exact in
    f32, so score comparisons tie-break identically in every impl)."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-8, 9, size=(s, n, d)).astype(np.float32)
    bottom = rng.integers(-1, n, size=(s, n, m0)).astype(np.int32)
    queries = rng.integers(-8, 9, size=(s, c, d)).astype(np.float32)
    entries = rng.integers(0, n, size=(s, c)).astype(np.int32)
    scale = zero = None
    if quantized:
        params = QuantParams.from_data(x.reshape(s * n, d))
        x = np.stack([params.quantize(x[i]) for i in range(s)])
        scale, zero = params.scale, params.zero
    return x, bottom, queries, entries, scale, zero


def _built_case(n, d, c, seed, metric, quantized=False):
    """A real HNSW graph (S=1 stack) with descend-produced entries."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    g = H.build_hnsw(x, metric=metric, max_degree=8, max_degree_upper=4,
                     ef_construction=40, seed=seed).device_arrays()
    queries = rng.normal(size=(c, d)).astype(np.float32)
    queries = np.asarray(M.preprocess_queries(queries, metric))
    entries = np.asarray(jax.vmap(
        lambda qv: H._greedy_descend(g, qv, metric, max_steps=64))(
            jnp.asarray(queries)))
    data = np.asarray(g.data)
    scale = zero = None
    if quantized:
        params = QuantParams.from_data(data)
        data = params.quantize(data)
        scale, zero = params.scale, params.zero
    return (data[None], np.asarray(g.bottom)[None], queries[None],
            entries[None], scale, zero)


def _two_way(x, bottom, queries, entries, scale, zero, *, metric, ef,
             max_iters=400):
    """The XLA walk against its numpy twin: exact ids, close scores."""
    kw = dict(metric=metric, ef=ef, max_iters=max_iters)
    sz = {} if scale is None else dict(scale=jnp.asarray(scale),
                                       zero=jnp.asarray(zero))
    s_r, n_r = beam_search_ref(
        jnp.asarray(x), jnp.asarray(bottom), jnp.asarray(queries),
        jnp.asarray(entries), **kw, **sz)
    s_n, n_n = beam_search_np(x, bottom, queries, entries, **kw,
                              scale=scale, zero=zero)
    np.testing.assert_array_equal(np.asarray(n_r), n_n)
    np.testing.assert_allclose(np.asarray(s_r), s_n, rtol=1e-5,
                               atol=1e-5)
    return s_n, n_n


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("quantized", (False, True))
def test_built_graph_three_way_parity(metric, quantized):
    case = _built_case(220, 12, 9, seed=3, metric=metric,
                       quantized=quantized)
    _two_way(*case, metric=metric, ef=24)


@pytest.mark.parametrize("metric", METRICS)
def test_random_stack_three_way_parity(metric):
    case = _random_case(3, 40, 6, 5, 4, seed=17)
    _two_way(*case, metric=metric, ef=8)


def test_revisit_cycle_blocked_by_visited_mask():
    """A ring: every expansion reaches back into already-visited nodes,
    so the visited mask is what keeps the beam duplicate-free."""
    n, m0 = 6, 3
    bottom = np.full((1, n, m0), -1, np.int32)
    for i in range(n):
        bottom[0, i] = [(i + 1) % n, (i + 2) % n, -1]
    x = np.arange(n, dtype=np.float32)[None, :, None] * np.ones(
        (1, n, 3), np.float32)
    queries = np.full((1, 2, 3), 2.0, np.float32)
    entries = np.array([[0, 3]], np.int32)
    s_n, n_n = _two_way(x, bottom, queries, entries, None, None,
                          metric="l2", ef=4)
    # the walk saturates the ring: no node may appear twice in a beam
    for row in n_n.reshape(-1, 4):
        real = row[row >= 0]
        assert len(set(real.tolist())) == len(real)


def test_duplicate_neighbour_slots_stay_in_parity():
    """Duplicate slots inside ONE adjacency row both pass the visited
    test (the test precedes the mark — same as the per-query walk), so
    the walk and its twin must admit them identically."""
    n, m0 = 6, 4
    bottom = np.full((1, n, m0), -1, np.int32)
    for i in range(n):
        bottom[0, i] = [(i + 1) % n, (i + 1) % n, (i + 2) % n, -1]
    x = np.arange(n, dtype=np.float32)[None, :, None] * np.ones(
        (1, n, 3), np.float32)
    queries = np.full((1, 2, 3), 2.0, np.float32)
    entries = np.array([[0, 3]], np.int32)
    _two_way(x, bottom, queries, entries, None, None, metric="l2",
               ef=4)


def test_isolated_entry_all_padding():
    # adjacency all -1: the beam is exactly the entry node
    x = np.ones((1, 5, 2), np.float32)
    bottom = np.full((1, 5, 3), -1, np.int32)
    queries = np.zeros((1, 3, 2), np.float32)
    entries = np.array([[4, 0, 2]], np.int32)
    s_n, n_n = _two_way(x, bottom, queries, entries, None, None,
                          metric="ip", ef=4)
    np.testing.assert_array_equal(n_n[0, :, 0], entries[0])
    assert (n_n[0, :, 1:] == -1).all()
    assert np.isneginf(s_n[0, :, 1:]).all()


def test_beam_ties_break_identically():
    # duplicate vectors => exactly equal scores; every impl must place
    # tied candidates in the same beam order (stable, lowest slot first)
    n = 8
    x = np.ones((1, n, 4), np.float32)          # all rows identical
    rng = np.random.default_rng(5)
    bottom = rng.integers(-1, n, size=(1, n, 3)).astype(np.int32)
    queries = np.ones((1, 4, 4), np.float32)
    entries = np.array([[0, 3, 5, 7]], np.int32)
    _two_way(x, bottom, queries, entries, None, None, metric="l2",
               ef=5)


def test_max_iters_bound_semantics():
    # the iteration bound truncates the walk identically everywhere,
    # including max_iters=0 (beam == entry only)
    case = _random_case(2, 30, 5, 4, 4, seed=23)
    for mi in (0, 1, 3):
        _two_way(*case, metric="l2", ef=6, max_iters=mi)


def test_ef_clamped_to_graph_size():
    case = _random_case(1, 10, 4, 3, 3, seed=9)
    s_n, n_n = _two_way(*case, metric="ip", ef=64)
    assert s_n.shape == (1, 3, 10)


def test_ops_dispatch_runs_off_tpu():
    # the public op is the one XLA walk on every backend: bit-identical
    # to beam_search_ref when no filter is given
    x, bottom, queries, entries, _, _ = _random_case(1, 20, 4, 3, 3,
                                                     seed=2)
    kw = dict(metric="l2", ef=6, max_iters=400)
    s_o, n_o = beam_search(jnp.asarray(x), jnp.asarray(bottom),
                           jnp.asarray(queries), jnp.asarray(entries),
                           **kw)
    s_r, n_r = beam_search_ref(jnp.asarray(x), jnp.asarray(bottom),
                               jnp.asarray(queries),
                               jnp.asarray(entries), **kw)
    np.testing.assert_array_equal(np.asarray(n_o), np.asarray(n_r))
    np.testing.assert_array_equal(np.asarray(s_o), np.asarray(s_r))


def test_stats_counts_expansions():
    x, bottom, queries, entries, _, _ = _random_case(1, 30, 4, 4, 3,
                                                     seed=13)
    _, _, iters = beam_search_stats(x, bottom, queries, entries,
                                    metric="l2", ef=6, max_iters=400)
    assert iters.shape == (1, 4)
    assert (np.asarray(iters) >= 1).all()
    _, _, iters1 = beam_search_stats(x, bottom, queries, entries,
                                     metric="l2", ef=6, max_iters=1)
    assert (np.asarray(iters1) == 1).all()


@pytest.mark.parametrize("metric", METRICS)
def test_hnsw_search_fused_matches_loop(metric):
    rng = np.random.default_rng(41)
    x = rng.normal(size=(300, 16)).astype(np.float32)
    g = H.build_hnsw(x, metric=metric, max_degree=8, max_degree_upper=4,
                     ef_construction=40, seed=1).device_arrays()
    q = jnp.asarray(M.preprocess_queries(
        rng.normal(size=(13, 16)).astype(np.float32), metric))
    ids_l, sc_l = H.hnsw_search(g, q, metric=metric, k=10, ef=32,
                                impl="loop")
    ids_f, sc_f = H.hnsw_search(g, q, metric=metric, k=10, ef=32,
                                impl="fused")
    np.testing.assert_array_equal(np.asarray(ids_l), np.asarray(ids_f))
    np.testing.assert_array_equal(np.asarray(sc_l), np.asarray(sc_f))


def _small_index(seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(12, 16))
    asg = rng.integers(0, 12, size=1500)
    x = (centers[asg] + 0.15 * rng.normal(size=(1500, 16))).astype(
        np.float32)
    cfg = PyramidConfig(metric="l2", num_shards=4, meta_size=48,
                        sample_size=500, branching_factor=2,
                        max_degree=8, max_degree_upper=4,
                        ef_construction=40, ef_search=48, kmeans_iters=4,
                        seed=0)
    return build_pyramid_index(x, cfg), x


@pytest.mark.parametrize("dtype", ("float32", "int8"))
def test_arena_kernel_strategy_matches_vmap_and_map(dtype):
    index, x = _small_index()
    arena = index.arena(dtype)
    meta = index.meta_arrays()
    poc = jnp.asarray(index.part_of_center)
    rng = np.random.default_rng(3)
    q = jnp.asarray(M.preprocess_queries(
        rng.normal(size=(24, 16)).astype(np.float32), "l2"))
    outs = {}
    for ax in ("kernel", "vmap", "map"):
        ids, sc, _ = arena_search(arena, meta, poc, q, metric="l2",
                                  k=10, ef=48, branching_factor=2,
                                  shard_axis=ax)
        outs[ax] = (np.asarray(ids), np.asarray(sc))
    for ax in ("vmap", "map"):
        np.testing.assert_array_equal(outs["kernel"][0], outs[ax][0])
        np.testing.assert_array_equal(outs["kernel"][1], outs[ax][1])


def test_single_host_matches_python_oracle_end_to_end():
    # recall@10 through the fused default must be bit-identical to the
    # pre-kernel per-shard python oracle at the default ef
    index, x = _small_index(seed=7)
    rng = np.random.default_rng(11)
    q = rng.normal(size=(16, 16)).astype(np.float32)
    ids_f, sc_f, _ = search_single_host(index, q, k=10)
    out_py = search_single_host_python(index, q, k=10)
    np.testing.assert_array_equal(np.asarray(ids_f),
                                  np.asarray(out_py[0]))
    np.testing.assert_allclose(np.asarray(sc_f), np.asarray(out_py[1]),
                               rtol=1e-5, atol=1e-5)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:       # container without hypothesis: the
    given = None          # deterministic cases above still run

if given is not None:

    @st.composite
    def walk_case(draw):
        s = draw(st.integers(1, 2))
        n = draw(st.integers(2, 24))
        d = draw(st.integers(1, 6))
        c = draw(st.integers(1, 4))
        m0 = draw(st.integers(1, 5))
        ef = draw(st.integers(1, 8))
        seed = draw(st.integers(0, 2 ** 31 - 1))
        metric = draw(st.sampled_from(("l2", "ip")))
        return s, n, d, c, m0, ef, seed, metric

    @settings(max_examples=25, deadline=None)
    @given(walk_case())
    def test_property_three_way_parity(case):
        s, n, d, c, m0, ef, seed, metric = case
        x, bottom, queries, entries, _, _ = _random_case(
            s, n, d, c, m0, seed)
        _two_way(x, bottom, queries, entries, None, None,
                   metric=metric, ef=ef)
