"""Parallel index construction (repro.build): the process-pool fan-out
must be bit-identical to the sequential build (the store-manifest
determinism gate from the acceptance criteria), and a crashed worker
must be retried without changing the result."""
import concurrent.futures
from concurrent.futures.process import BrokenProcessPool

import numpy as np
import pytest

from repro.build import (BuildError, build_pyramid_index_parallel,
                         build_subgraphs, plan_build)
from repro.build.planner import _default_pool
from repro.common.config import PyramidConfig
from repro.core.distributed import search_single_host
from repro.data.synthetic import clustered_vectors, query_set
from repro.store import IndexStore, content_checksum, graph_to_arrays

CFG = PyramidConfig(metric="l2", num_shards=4, meta_size=32,
                    sample_size=500, branching_factor=2, max_degree=10,
                    max_degree_upper=5, ef_construction=30, ef_search=40,
                    kmeans_iters=4)


@pytest.fixture(scope="module")
def data():
    return clustered_vectors(900, 12, 8, seed=0)


@pytest.fixture(scope="module")
def seq_index(data):
    return build_pyramid_index_parallel(data, CFG, workers=0)


def _checksums(index):
    return [content_checksum(graph_to_arrays(g)) for g in index.subs]


def test_parallel_build_is_bit_identical(data, seq_index, tmp_path):
    """Acceptance gate: a pool of 4 workers produces the same index as
    the sequential loop — same published manifest checksums."""
    par = build_pyramid_index_parallel(data, CFG, workers=4)
    assert par.build_stats["build_mode"] == "parallel"
    assert _checksums(seq_index) == _checksums(par)
    v_seq = IndexStore(str(tmp_path / "seq")).publish(seq_index)
    v_par = IndexStore(str(tmp_path / "par")).publish(par)
    m_seq = IndexStore(str(tmp_path / "seq")).reader(v_seq).manifest
    m_par = IndexStore(str(tmp_path / "par")).reader(v_par).manifest
    assert ([s["checksum"] for s in m_seq["shards"]]
            == [s["checksum"] for s in m_par["shards"]])
    assert m_seq["meta"]["checksum"] == m_par["meta"]["checksum"]
    # and the search results agree exactly
    q = query_set(data, 16, seed=3)
    ids_a, sc_a, _ = search_single_host(seq_index, q, k=5)
    ids_b, sc_b, _ = search_single_host(par, q, k=5)
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_array_equal(sc_a, sc_b)


def test_build_stats_record_fanout(data):
    par = build_pyramid_index_parallel(data, CFG, workers=2)
    st = par.build_stats
    assert st["build_workers"] == 2
    assert len(st["shard_build_s"]) == CFG.num_shards
    assert all(t > 0 for t in st["shard_build_s"])
    assert st["subgraphs_wall_s"] > 0
    assert st["sub_sizes"] == [g.n for g in par.subs]


class _FlakyPool:
    """Injectable pool whose first ``fail_times`` submissions fail.

    Later submissions run the work inline, so the retry path is
    exercised deterministically without real process churn."""

    def __init__(self, fail_times: int, exc_factory=RuntimeError):
        self.fail_times = fail_times
        self.exc_factory = exc_factory
        self.calls = 0

    def submit(self, fn, *args):
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self.calls += 1
        if self.calls <= self.fail_times:
            fut.set_exception(self.exc_factory("injected worker crash"))
        else:
            fut.set_result(fn(*args))
        return fut

    def shutdown(self, wait=True, cancel_futures=False):
        pass


def test_worker_crash_is_retried(data, seq_index):
    plan = plan_build(data, CFG)
    subs, stats = build_subgraphs(
        plan, workers=2, pool_factory=lambda: _FlakyPool(1))
    assert stats["build_retries"] == 1
    assert [e["event"] for e in stats["build_timeline"]] == ["retry"]
    assert stats["build_timeline"][0]["via"] == "pool"
    for a, b in zip(seq_index.subs, subs):   # retry changed nothing
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.data, b.data)


def test_broken_pool_falls_back_inline(data, seq_index):
    plan = plan_build(data, CFG)
    subs, stats = build_subgraphs(
        plan, workers=2,
        pool_factory=lambda: _FlakyPool(1, exc_factory=BrokenProcessPool))
    assert stats["build_retries"] == 1
    assert stats["build_timeline"][0]["via"] == "inline"
    for a, b in zip(seq_index.subs, subs):
        np.testing.assert_array_equal(a.ids, b.ids)


class _BreaksOnResubmit:
    """The initial fan-out lands (first future fails with an ordinary
    error, the rest succeed); the *resubmit* then raises
    BrokenProcessPool from ``submit()`` itself (another worker died in
    between) — the fall-through-to-inline path."""

    def __init__(self, n_initial: int):
        self.n_initial = n_initial
        self.calls = 0

    def submit(self, fn, *args):
        self.calls += 1
        if self.calls > self.n_initial:
            raise BrokenProcessPool("pool broke before resubmit")
        fut: concurrent.futures.Future = concurrent.futures.Future()
        if self.calls == 1:
            fut.set_exception(RuntimeError("injected worker crash"))
        else:
            fut.set_result(fn(*args))
        return fut

    def shutdown(self, wait=True, cancel_futures=False):
        pass


def test_pool_breaking_during_resubmit_falls_back_inline(data, seq_index):
    plan = plan_build(data, CFG)
    subs, stats = build_subgraphs(
        plan, workers=2,
        pool_factory=lambda: _BreaksOnResubmit(CFG.num_shards))
    assert stats["build_retries"] >= 1
    assert stats["build_timeline"][0]["via"] == "inline"
    for a, b in zip(seq_index.subs, subs):
        np.testing.assert_array_equal(a.ids, b.ids)


def test_retry_budget_exhaustion_raises(data):
    plan = plan_build(data, CFG)
    with pytest.raises(BuildError, match="retries"):
        build_subgraphs(plan, workers=2, max_retries=1,
                        pool_factory=lambda: _FlakyPool(100))


def test_workers_default_caps_at_shards(data):
    # workers=None must pick something sane and still build correctly
    idx = build_pyramid_index_parallel(data, CFG, workers=None)
    assert idx.num_shards == CFG.num_shards
    assert idx.build_stats["build_workers"] <= CFG.num_shards


def test_pool_workers_pin_jax_to_cpu(monkeypatch):
    """A build worker must never claim the accelerator its parent
    holds: the pool's initializer pins the worker's JAX to the CPU
    before any task runs, whatever the inherited environment says."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    pool = _default_pool(1)
    try:
        got = pool.submit(
            eval, "__import__('jax').config.jax_platforms").result(
                timeout=120)
    finally:
        pool.shutdown()
    assert got == "cpu"
