"""The serving engine's query-path spans on a tiny index: every shard
copy's ``executor.queued`` span, the route and walk spans ending with
their results on the host, and the walk's expansion counter.
"""
import collections
import threading
import time

import jax
import numpy as np
import pytest

from repro.common.config import PyramidConfig
from repro.core import hnsw as H
from repro.core.meta_index import build_pyramid_index
from repro.data.synthetic import clustered_vectors, query_set
from repro.kernels.beam_search import beam_search_stats
from repro.obs import Tracer
from repro.serving import engine as E


@pytest.fixture(scope="module")
def tiny_index():
    x = clustered_vectors(1200, 12, 12, seed=0)
    cfg = PyramidConfig(metric="l2", num_shards=4, meta_size=48,
                        sample_size=600, branching_factor=2,
                        max_degree=12, max_degree_upper=6,
                        ef_construction=40, ef_search=50, kmeans_iters=6)
    return x, build_pyramid_index(x, cfg)


class _OnHost:
    """Stands in for a device result: records when (and on which
    thread) it is copied to the host."""

    def __init__(self, value, log):
        self.value, self.log = value, log

    def __array__(self, dtype=None, copy=None):
        self.log.append((threading.current_thread().name,
                         time.monotonic()))
        return np.asarray(self.value, dtype=dtype)


def test_query_path_spans_and_walk_counter(tiny_index, monkeypatch):
    x, idx = tiny_index
    copied, calls = [], []
    walk, route = H.hnsw_search, E.route_queries

    def traced_walk(g, queries, **kw):
        out = walk(g, queries, **kw)
        if not kw.get("stats"):
            return out
        ids, scores, iters = out
        calls.append((threading.current_thread().name, time.monotonic(),
                      g, np.asarray(queries), kw, np.asarray(iters)))
        return _OnHost(ids, copied), _OnHost(scores, copied), iters

    def traced_route(*a, **kw):
        mask, shards = route(*a, **kw)
        return _OnHost(mask, copied), shards

    monkeypatch.setattr(H, "hnsw_search", traced_walk)
    monkeypatch.setattr(E, "route_queries", traced_route)
    tracer = Tracer()
    eng = E.ServingEngine(idx, replicas=1, hedge=False, executor_batch=4,
                          tracer=tracer)
    try:
        futs = eng.submit(query_set(x, 24, seed=5), k=10)
        assert all(len(f.result(timeout=120).ids) == 10 for f in futs)
    finally:
        eng.shutdown()
    spans = tracer.snapshot()
    by_id = {s.span_id: s for s in spans}
    roots = {s.attrs["qid"]: s for s in spans if s.name == "query"}
    dispatched = collections.Counter(
        (s.attrs["qid"], s.attrs["shard"]) for s in spans
        if s.name == "dispatch")
    queued = [s for s in spans if s.name == "executor.queued"]

    # one queued span per shard copy, parented to its query's root,
    # ending at or before the start of the batch that drained it
    assert collections.Counter(
        (s.attrs["qid"], s.attrs["shard"]) for s in queued) == dispatched
    assert len(dispatched) > len(roots) == 24
    for s in queued:
        assert s.attrs["attempt"] == 0
        assert s.parent_id == roots[s.attrs["qid"]].span_id
        batch = by_id[s.attrs["batch_span"]]
        assert batch.name == "executor.batch"
        assert batch.attrs["shard"] == s.attrs["shard"]
        assert "queries" not in batch.attrs
        assert s.t0 <= s.t1 <= batch.t0

    def copied_inside(span):
        return sum(thread == span.thread and span.t0 <= t <= span.t1
                   for thread, t in copied)

    # route and walk spans end once their results are on the host
    routes = [s for s in spans if s.name == "coordinator.route"]
    assert routes and all(copied_inside(s) == 1 for s in routes)
    walks = [s for s in spans if s.name == "kernel.beam_walk"]
    assert walks and all(copied_inside(s) == 2 for s in walks)

    # expansions / trips: the same rows walked by the reference walk
    for thread, t, g, vecs, kw, iters in calls:
        (span,) = [s for s in walks
                   if s.thread == thread and s.t0 <= t <= s.t1]
        entries = jax.vmap(lambda q: H._greedy_descend(
            g, q, kw["metric"], max_steps=64))(vecs)
        _, _, ref = beam_search_stats(
            g.data[None], g.bottom[None], vecs[None], entries[None],
            metric=kw["metric"], ef=kw["ef"], max_iters=400,
            scale=getattr(g, "scale", None), zero=getattr(g, "zero", None))
        ref = np.asarray(ref)[0]
        np.testing.assert_array_equal(ref, iters)
        n = span.attrs["batch"]
        assert span.attrs["expansions"] == int(ref[:n].sum())
        assert span.attrs["trips"] == int(ref.max())
