"""Metadata-filtered kNN: XLA walk/numpy twin parity, the sel-1.0
bit-identity contract, empty filters, tag persistence through the delta
log + compaction, the engine's filtered serving path, and the merge
alive-mask (tombstones must never crowd live results out of k)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import PyramidConfig
from repro.core import filters as F
from repro.core import hnsw as H
from repro.core import metrics as M
from repro.core.client import gather
from repro.core.distributed import search_single_host
from repro.core.meta_index import build_pyramid_index
from repro.core.updates import add_items, remove_items, set_item_tags
from repro.data.synthetic import query_set
from repro.kernels.beam_search import beam_search, beam_search_np
from repro.kernels.merge_topk.ref import merge_topk_np
from repro.serving.engine import ServingEngine
from repro.store import IndexStore

METRICS = ("l2", "ip", "angular")


def _make_index(metric: str, n=600, d=10, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    cfg = PyramidConfig(metric=metric, num_shards=3, meta_size=24,
                        sample_size=min(n, 400), branching_factor=2,
                        max_degree=10, max_degree_upper=5,
                        ef_construction=40, ef_search=60, kmeans_iters=5,
                        seed=seed)
    return x, build_pyramid_index(x, cfg)


def _random_tags(n, seed=3, bits=4):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 1 << bits, size=n).astype(np.int64)


# ---------------------------------------------------------------------------
# selectivity 1.0: a filter every item matches must be bit-identical to
# the unfiltered search — on every metric and every search path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", METRICS)
def test_sel1_bit_identical_fused_pipeline(metric):
    x, index = _make_index(metric)
    set_item_tags(index, np.arange(len(x)), np.ones(len(x), np.int64))
    q = query_set(x, 16, seed=1)
    ids_u, scores_u, _ = search_single_host(index, q, k=10)
    ids_f, scores_f, _ = search_single_host(index, q, k=10,
                                            filter_tags=1)
    np.testing.assert_array_equal(ids_f, ids_u)
    np.testing.assert_array_equal(scores_f, scores_u)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("impl", ("fused", "loop"))
def test_sel1_bit_identical_graph_paths(metric, impl):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(300, 8)).astype(np.float32)
    g = H.build_hnsw(x, metric=metric, max_degree=8, max_degree_upper=4,
                     ef_construction=40, seed=0,
                     tags=np.ones(len(x), np.int64))
    q = np.asarray(M.preprocess_queries(
        rng.normal(size=(8, 8)).astype(np.float32), metric))
    ga = g.device_arrays()
    tw = jnp.asarray(F.split_tag_words(g.tags_or_zeros()))
    fw = jnp.asarray(F.filter_words(np.ones(len(q), np.int64)))
    ids_u, scores_u = H.hnsw_search(ga, jnp.asarray(q), metric=metric,
                                    k=10, ef=60, impl=impl)
    ids_f, scores_f = H.hnsw_search(ga, jnp.asarray(q), metric=metric,
                                    k=10, ef=60, impl=impl,
                                    tag_words=tw, filter_words=fw)
    np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_u))
    np.testing.assert_array_equal(np.asarray(scores_f),
                                  np.asarray(scores_u))
    # numpy oracle obeys the same identity
    nids_u, nsc_u = H.search_numpy(g, q, 10, ef=60)
    nids_f, nsc_f = H.search_numpy(g, q, 10, ef=60, filter_tags=1)
    np.testing.assert_array_equal(nids_f, nids_u)
    np.testing.assert_array_equal(nsc_f, nsc_u)


def test_filtered_kernel_oracle_parity():
    """Non-trivial filters: the XLA walk with its on-device alive-mask
    agrees exactly with the numpy twin masked on the host, and every
    surviving candidate actually matches its slot's filter."""
    rng = np.random.default_rng(7)
    s, n, d, c, m0 = 2, 64, 6, 8, 6
    x = rng.integers(-8, 9, size=(s, n, d)).astype(np.float32)
    bottom = rng.integers(-1, n, size=(s, n, m0)).astype(np.int32)
    queries = rng.integers(-8, 9, size=(s, c, d)).astype(np.float32)
    entries = rng.integers(0, n, size=(s, c)).astype(np.int32)
    tags = rng.integers(1, 16, size=(s, n)).astype(np.int64)
    filters = rng.integers(0, 16, size=(s, c)).astype(np.int64)

    tw = jnp.asarray(F.split_tag_words(tags))
    fw = jnp.asarray(F.filter_words(filters))
    kw = dict(metric="l2", ef=16, max_iters=100)
    s_r, n_r = beam_search(
        jnp.asarray(x), jnp.asarray(bottom), jnp.asarray(queries),
        jnp.asarray(entries), tag_words=tw, filter_words=fw, **kw)
    s_n, n_n = beam_search_np(x, bottom, queries, entries, **kw)
    cand_tags = tags[np.arange(s)[:, None, None], np.clip(n_n, 0, None)]
    alive = (n_n >= 0) & F.alive_np(cand_tags, filters[:, :, None])
    s_n = np.where(alive, s_n, -np.inf)
    n_n = np.where(alive, n_n, -1)
    np.testing.assert_array_equal(np.asarray(n_r), n_n)
    np.testing.assert_allclose(np.asarray(s_r), s_n, rtol=1e-5,
                               atol=1e-5)
    nodes = np.asarray(n_r)
    for si in range(s):
        for ci in range(c):
            for v in nodes[si, ci]:
                if v >= 0:
                    assert F.alive_np(tags[si, v], filters[si, ci])


def test_filtered_results_match_filter_and_fill_k():
    x, index = _make_index("l2", n=800)
    tags = _random_tags(len(x))
    set_item_tags(index, np.arange(len(x)), tags)
    q = query_set(x, 12, seed=4)
    f = 0b0100     # ~50% selectivity under 4 random bits
    ids, scores, _ = search_single_host(index, q, k=10, filter_tags=f)
    alive = ids >= 0
    assert alive.all(), "moderate selectivity must fill k"
    assert F.alive_np(tags[ids[alive]], f).all()
    # sorted best-first
    assert (np.diff(np.asarray(scores), axis=1) <= 1e-5).all()


def test_sel0_empty_and_no_crash():
    x, index = _make_index("l2")
    tags = _random_tags(len(x), bits=4)   # bits 0..3 only
    set_item_tags(index, np.arange(len(x)), tags)
    q = query_set(x, 6, seed=5)
    unknown = np.int64(1) << 17           # no item carries this bit
    ids, scores, _ = search_single_host(index, q, k=10,
                                        filter_tags=unknown)
    assert (np.asarray(ids) == -1).all()
    assert np.isneginf(np.asarray(scores)).all()


# ---------------------------------------------------------------------------
# persistence: tags survive publish -> delta replay -> compaction
# ---------------------------------------------------------------------------


def test_tags_roundtrip_store_and_delta(tmp_path):
    x, index = _make_index("l2", n=400)
    tags = _random_tags(len(x))
    set_item_tags(index, np.arange(len(x)), tags)
    store = IndexStore(str(tmp_path / "store"))
    store.publish(index)   # publish attaches the delta log

    rng = np.random.default_rng(9)
    extra = rng.normal(size=(20, x.shape[1])).astype(np.float32)
    extra_tags = _random_tags(20, seed=11)
    add_items(index, extra, np.arange(1000, 1020), tags=extra_tags)
    set_item_tags(index, [0, 1], np.int64(1 << 9))
    remove_items(index, [2, 1005])

    loaded = store.load()
    want = index.tags_host()
    got = loaded.tags_host()
    # order within shards is deterministic (same build + same replay)
    np.testing.assert_array_equal(got, want)
    q = query_set(x, 8, seed=6)
    f = np.int64(1 << 9)
    ids_a, sc_a, _ = search_single_host(index, q, k=5, filter_tags=f)
    ids_b, sc_b, _ = search_single_host(loaded, q, k=5, filter_tags=f)
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_array_equal(sc_a, sc_b)


def test_untagged_delta_records_stay_untagged(tmp_path):
    """Inserting without tags must journal the pre-tag record format
    (no "tags" array) and keep the untagged fast path (`tags is None`)
    after replay."""
    x, index = _make_index("l2", n=300)
    store = IndexStore(str(tmp_path / "store"))
    store.publish(index)
    add_items(index, np.random.default_rng(0).normal(
        size=(8, x.shape[1])).astype(np.float32))
    loaded = store.load()
    assert all(g.tags is None for g in loaded.subs)
    assert not loaded.tags_host().any()


def test_compactor_folds_tags(tmp_path):
    from repro.store.maintenance import Compactor
    x, index = _make_index("l2", n=300)
    store = IndexStore(str(tmp_path / "store"))
    store.publish(index)
    comp = Compactor(store, store.load(), rebalance=False)
    rng = np.random.default_rng(1)
    comp.add_items(rng.normal(size=(10, x.shape[1])).astype(np.float32),
                   np.arange(2000, 2010),
                   tags=np.full(10, 1 << 5, np.int64))
    comp.set_item_tags(np.arange(2000, 2005), np.int64(1 << 6))
    assert comp.run_once(force=True) is not None
    loaded = store.load()
    tags = {}
    for g in loaded.subs:
        for i, gid in enumerate(np.asarray(g.ids)):
            tags[int(gid)] = int(g.tags_or_zeros()[i])
    assert tags[2001] == (1 << 6)    # set_item_tags assigns, not ORs
    assert tags[2007] == (1 << 5)


# ---------------------------------------------------------------------------
# serving: engine-side filtered search + pre-merge alive-mask
# ---------------------------------------------------------------------------


def test_engine_filtered_search_matches_single_host():
    x, index = _make_index("l2", n=800)
    tags = _random_tags(len(x))
    set_item_tags(index, np.arange(len(x)), tags)
    q = query_set(x, 10, seed=8)
    f = 0b0010
    want_ids, _, _ = search_single_host(index, q, k=10, filter_tags=f)
    eng = ServingEngine(index, hedge=False)
    try:
        got = gather(eng.submit(q, k=10, filter_tags=f), 60.0)
        # a mixed batch: filtered and unfiltered queries coexist
        mixed = gather(eng.submit(
            q, k=10,
            filter_tags=np.asarray([f, 0] * 5, np.int64)), 60.0)
    finally:
        eng.shutdown()
    for i, r in enumerate(got):
        assert F.alive_np(tags[r.ids], f).all()
        overlap = len(set(r.ids.tolist())
                      & set(np.asarray(want_ids[i]).tolist()))
        assert overlap >= 8, f"query {i}: {overlap}/10 vs single-host"
    for i, r in enumerate(mixed):
        if i % 2 == 0:
            assert F.alive_np(tags[r.ids], f).all()
        else:
            assert len(r.ids) == 10 and (r.ids >= 0).all()


def test_engine_unfiltered_untagged_and_sel0():
    x, index = _make_index("l2", n=400)   # untagged corpus
    q = query_set(x, 4, seed=2)
    eng = ServingEngine(index, hedge=False)
    try:
        plain = gather(eng.submit(q, k=5), 60.0)
        filt = gather(eng.submit(q, k=5, filter_tags=3), 60.0)
    finally:
        eng.shutdown()
    for r in plain:
        assert (r.ids >= 0).all()
    for r in filt:     # selectivity 0 on an untagged corpus: empty, fast
        assert len(r.ids) == 0


def test_merge_alive_mask_pre_merge():
    """A dead (tombstoned/filtered) candidate with the best score must
    not crowd a live candidate out of the merged top-k."""
    scores = np.asarray([[9.0, 5.0, 4.0, 3.0]], np.float32)
    ids = np.asarray([[7, 1, 2, 3]], np.int64)
    alive = np.asarray([[False, True, True, True]])
    s, i = merge_topk_np(scores, ids, k=3, alive=alive)
    np.testing.assert_array_equal(i[0], [1, 2, 3])
    np.testing.assert_array_equal(s[0], [5.0, 4.0, 3.0])
    # without the mask the dead id wins the top slot
    s2, i2 = merge_topk_np(scores, ids, k=3)
    assert i2[0, 0] == 7
