"""Unit tests for the observability layer (``repro.obs``): metrics
registry rendering/snapshot semantics, tracer causality + Chrome
export, the stats HTTP server, the LatencyTracker edge cases the
hedging machinery depends on, and compactor counter parity.
"""
import glob
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from repro.common.utils import nearest_rank
from repro.obs import (LATENCY_BUCKETS, MetricsRegistry, NULL_TRACER,
                       StatsServer, Tracer, validate_chrome_trace)
from repro.serving.engine import LatencyTracker


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_counter_inc_and_render():
    reg = MetricsRegistry()
    c = reg.counter("widgets_total", "widgets made")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    text = reg.render_prometheus()
    assert "# TYPE widgets_total counter" in text
    assert "# HELP widgets_total widgets made" in text
    assert "widgets_total 3.5" in text


def test_labeled_counter_children_are_cached():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "hits", labelnames=("shard",))
    a = c.labels(shard="0")
    b = c.labels(shard="0")
    assert a is b                      # hot path: no per-call allocation
    a.inc(3)
    c.labels(shard="1").inc()
    text = reg.render_prometheus()
    assert 'hits_total{shard="0"} 3' in text
    assert 'hits_total{shard="1"} 1' in text


def test_gauge_set_and_lazy_fn():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    assert "depth 7" in reg.render_prometheus()
    reg.gauge("lazy_depth", "scraped lazily", fn=lambda: 42)
    reg.gauge("lazy_by", "labeled lazy", labelnames=("shard",),
              fn=lambda: {("0",): 1.5, ("1",): 2.5})
    text = reg.render_prometheus()
    assert "lazy_depth 42" in text
    assert 'lazy_by{shard="0"} 1.5' in text
    assert 'lazy_by{shard="1"} 2.5' in text


def test_histogram_cumulative_buckets_and_sum():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text       # cumulative
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text
    assert "lat_seconds_sum 6.05" in text
    assert LATENCY_BUCKETS == tuple(sorted(LATENCY_BUCKETS))


def test_registration_is_idempotent_and_typechecked():
    reg = MetricsRegistry()
    a = reg.counter("again_total", "x")
    b = reg.counter("again_total", "x")
    assert a is b                       # hot-swapped engines re-register
    with pytest.raises(ValueError):
        reg.gauge("again_total", "x")   # same name, different kind
    reg.counter("lbl_total", "x", labelnames=("shard",))
    with pytest.raises(ValueError):
        reg.counter("lbl_total", "x", labelnames=("replica",))


def test_disabled_registry_is_inert():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("noop_total", "x")
    c.inc(99)
    c.labels(shard="0").inc()
    assert c.value == 0.0
    reg.histogram("h", "x").observe(1.0)
    reg.gauge("g", "x").set(5)
    assert reg.render_prometheus().strip() == ""
    assert reg.snapshot() == {}


def test_snapshot_is_json_serializable():
    reg = MetricsRegistry()
    reg.counter("a_total", "x", labelnames=("shard",)).labels(
        shard="0").inc()
    reg.histogram("b_seconds", "x").observe(0.2)
    reg.gauge("c", "x").set(1)
    payload = json.loads(json.dumps(reg.snapshot()))
    assert payload["a_total"]["type"] == "counter"
    assert payload["b_seconds"]["series"][0]["count"] == 1


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_nesting_supplies_parent():
    tr = Tracer()
    with tr.span("outer") as outer:
        with tr.span("inner"):
            pass
    spans = {s.name: s for s in tr.snapshot()}
    assert spans["inner"].parent_id == outer.span_id
    assert spans["outer"].parent_id is None
    assert spans["inner"].t1 >= spans["inner"].t0


def test_explicit_parent_crosses_threads():
    tr = Tracer()
    root = tr.start("query", qid=7)

    def other():
        tr.instant("hedge.redispatch", parent=root.span_id, qid=7)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    tr.end(root)
    by_name = {s.name: s for s in tr.snapshot()}
    hedge = by_name["hedge.redispatch"]
    assert hedge.parent_id == root.span_id
    assert hedge.thread != by_name["query"].thread
    assert hedge.t0 == hedge.t1         # instant: zero duration


def test_ring_buffer_caps_span_history():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant("tick", i=i)
    kept = tr.snapshot()
    assert len(kept) == 4
    assert [s.attrs["i"] for s in kept] == [6, 7, 8, 9]   # oldest drop


def test_injected_clock_makes_timestamps_deterministic():
    ticks = iter(float(t) for t in range(100))
    tr = Tracer(clock=lambda: next(ticks))
    with tr.span("a"):
        pass
    (span,) = tr.snapshot()
    assert (span.t0, span.t1) == (1.0, 2.0)   # 0.0 is the origin


def test_chrome_trace_schema_and_causality_args():
    tr = Tracer()
    with tr.span("parent") as p:
        with tr.span("child", shard=3):
            pass
    tr.instant("mark")
    payload = tr.chrome_trace()
    validate_chrome_trace(payload)
    events = {e["name"]: e for e in payload["traceEvents"]}
    assert events["child"]["args"]["parent_id"] == p.span_id
    assert events["child"]["args"]["shard"] == 3
    assert events["child"]["ph"] == "X"
    assert events["mark"]["ph"] == "i"
    assert events["thread_name"]["ph"] == "M"


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "pid": 1,
                              "tid": 1, "ts": 0.0}]})    # X without dur
    with pytest.raises(ValueError):
        validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "B", "pid": 1,
                              "tid": 1, "ts": 0.0}]})    # unsupported ph


def test_null_tracer_is_inert_but_usable():
    with NULL_TRACER.span("x", a=1) as s:
        s.set(b=2)                      # must not pollute shared attrs
        assert s.span_id is None
        assert s.attrs == {}
    NULL_TRACER.instant("y")
    NULL_TRACER.end(NULL_TRACER.start("z"))
    assert NULL_TRACER.snapshot() == []


def test_disabled_tracer_records_nothing_until_enabled():
    tr = Tracer(enabled=False)
    with tr.span("a"):
        pass
    assert tr.snapshot() == []
    tr.enabled = True                   # the obs-overhead gate's toggle
    with tr.span("b"):
        pass
    assert [s.name for s in tr.snapshot()] == ["b"]


def _host_events(log_dir):
    """``{name: [stats dict, ...]}`` of the profile's host-plane events."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(dict(ev.stats))
    return events


def test_scoped_spans_reach_the_profiler_host_plane(tmp_path):
    import jax
    tr, off = Tracer(), Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("coordinator.route", n=2) as route:
            with tr.span("kernel.beam_walk", qid=7) as walk:
                walk.set(trips=3)
        with off.span("off.span"):
            pass
        with NULL_TRACER.span("null.span"):
            pass
        root = tr.start("query", qid=7)     # start()/end(): no annotation
        tr.end(root)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (w,) = events["kernel.beam_walk"]
    assert w["span_id"] == walk.span_id
    assert w["parent_id"] == route.span_id
    assert (w["qid"], w["trips"]) == (7, 3)
    (r,) = events["coordinator.route"]
    assert (r["span_id"], r["n"]) == (route.span_id, 2)
    assert "parent_id" not in r
    for name in ("off.span", "null.span", "query"):
        assert name not in events
    assert off.snapshot() == []
    by_name = {s.name: s for s in tr.snapshot()}
    assert set(by_name) == {"coordinator.route", "kernel.beam_walk",
                            "query"}
    q = by_name["query"]
    assert q.t1 >= q.t0 and q.attrs == {"qid": 7}
    assert by_name["kernel.beam_walk"].attrs == {"qid": 7, "trips": 3}
    r_span = by_name["coordinator.route"]
    assert r_span.t0 <= by_name["kernel.beam_walk"].t0 <= r_span.t1


# ---------------------------------------------------------------------------
# StatsServer
# ---------------------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


def test_stats_server_serves_metrics_stats_healthz():
    reg = MetricsRegistry()
    reg.counter("served_total", "x").inc(3)
    with StatsServer(reg, host="127.0.0.1", port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        assert "served_total 3" in _get(f"{base}/metrics")
        srv.add_stats_provider(
            "engine", lambda: {"qps": np.float64(1.5),
                               "shards": np.arange(2)})
        stats = json.loads(_get(f"{base}/stats"))
        assert stats["engine"] == {"qps": 1.5, "shards": [0, 1]}
        assert "ok" in _get(f"{base}/healthz")
    srv.stop()                          # idempotent


# ---------------------------------------------------------------------------
# LatencyTracker edge cases (the hedge machinery's quantile source)
# ---------------------------------------------------------------------------


def test_tracker_window_evicts_at_exactly_window():
    t = LatencyTracker(window=8, min_samples=1)
    for _ in range(8):
        t.observe(0, 1.0)
    assert t.quantile(0, 100.0) == 1.0
    assert t.snapshot()[0]["n"] == 8
    t.observe(0, 2.0)                   # 9th sample evicts the oldest
    assert t.snapshot()[0]["n"] == 8    # still exactly `window`
    assert t.quantile(0, 100.0) == 2.0


def test_tracker_min_samples_boundary():
    t = LatencyTracker(window=64, min_samples=8)
    for _ in range(7):
        t.observe(1, 0.5)
    assert t.quantile(1, 99.0) is None      # 7 < min_samples
    t.observe(1, 0.5)
    assert t.quantile(1, 99.0) == 0.5       # exactly min_samples
    assert t.quantile(2, 99.0) is None      # untouched shard


def test_tracker_quantile_matches_numpy_inverted_cdf():
    rng = np.random.default_rng(5)
    t = LatencyTracker(window=256, min_samples=1)
    xs = rng.exponential(0.01, size=100)
    for v in xs:
        t.observe(0, float(v))
    for q in (1.0, 50.0, 90.0, 99.0, 100.0):
        want = float(np.percentile(xs, q, method="inverted_cdf"))
        assert t.quantile(0, q) == want
        assert nearest_rank(sorted(xs.tolist()), q) == want


def test_tracker_concurrent_observe_and_snapshot():
    t = LatencyTracker(window=128, min_samples=1)
    stop = threading.Event()
    errors = []

    def writer(shard):
        i = 0
        while not stop.is_set():
            t.observe(shard, 0.001 * (i % 50 + 1))
            i += 1

    def reader():
        try:
            while not stop.is_set():
                t.quantile(0, 99.0)
                t.snapshot()
        except Exception as e:          # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(s,))
               for s in (0, 1)] + [threading.Thread(target=reader)]
    for th in threads:
        th.start()
    import time
    time.sleep(0.3)
    stop.set()
    for th in threads:
        th.join(timeout=10)
    assert not errors
    snap = t.snapshot()
    assert snap[0]["n"] <= 128 and snap[1]["n"] <= 128
    assert t.quantile(0, 50.0) is not None


# ---------------------------------------------------------------------------
# Compactor counter parity (registry IS the bookkeeping)
# ---------------------------------------------------------------------------


def test_compactor_counters_match_stats(tmp_path):
    from repro.common.config import PyramidConfig
    from repro.core.meta_index import build_pyramid_index
    from repro.data.synthetic import clustered_vectors
    from repro.store import Compactor, IndexStore

    x = clustered_vectors(400, 8, 4, seed=0)
    cfg = PyramidConfig(metric="l2", num_shards=2, meta_size=16,
                        sample_size=200, branching_factor=2,
                        max_degree=8, max_degree_upper=4,
                        ef_construction=30, ef_search=30, kmeans_iters=4)
    store = IndexStore(str(tmp_path / "store"))
    store.publish(build_pyramid_index(x, cfg))
    reg, tr = MetricsRegistry(), Tracer()
    comp = Compactor(store, store.load(), rebalance=False,
                     registry=reg, tracer=tr)
    comp.add_items(np.random.default_rng(1).normal(
        size=(6, 8)).astype(np.float32))
    comp.run_once(force=True)
    stats = comp.stats()
    prom = reg.render_prometheus()
    assert f"pyramid_maintenance_cycles_total {stats['cycles']}" in prom
    assert (f"pyramid_maintenance_folded_records_total "
            f"{stats['folded_records']}") in prom
    assert f"pyramid_maintenance_swaps_total {stats['swaps']}" in prom
    names = {s.name for s in tr.snapshot()}
    assert {"compaction.cycle", "compaction.fold",
            "compaction.commit"} <= names
    cycle = next(s for s in tr.snapshot()
                 if s.name == "compaction.cycle")
    fold = next(s for s in tr.snapshot() if s.name == "compaction.fold")
    assert fold.parent_id == cycle.span_id


# ---------------------------------------------------------------------------
# serve --trace-out writes a schema-valid Chrome trace
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_trace_out_is_schema_valid(tmp_path):
    from repro.launch.serve import main as serve_main

    out = tmp_path / "trace.json"
    serve_main(argv=["--tokens", "3", "--batch", "1",
                     "--prompt-len", "4", "--trace-out", str(out)])
    doc = json.loads(out.read_text())
    validate_chrome_trace(doc)
    names = {ev["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "X"}
    assert "serve.prefill" in names
    assert "serve.decode_step" in names
