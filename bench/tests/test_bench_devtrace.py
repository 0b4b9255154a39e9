"""The reduction from a profiler trace to device numbers, on a small
recorded v5e trace and on hand-made ones."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchlib import devtrace as D

DATA = Path(__file__).parent / "data" / "trace_v5e_excerpt.json"


def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "),
                                     "events": v}
                                    for k, v in lines.items()]}


def _trace(modules, ops=(), host=()):
    return {"planes": [
        _plane("/device:TPU:0", XLA_Modules=list(modules),
               XLA_Ops=list(ops)),
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": list(host)}]}]}


def test_busy_is_the_union_clipped_to_the_window():
    tr = _trace([["jit_a(1)", 0, 100], ["jit_a(1)", 50, 100],
                 ["jit_b(2)", 300, 100], ["jit_b(2)", 900, 500]],
                host=[[D.WINDOW_EVENT, 20, 1000]])
    r = D.reduce_trace(tr)
    # window [20, 1020]: busy [20,150] + [300,400] + [900,1020]
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((130 + 100 + 120) * 1e-9)
    assert r["programs"]["jit_a"][0] == 2
    assert r["programs"]["jit_a"][1] == pytest.approx((80 + 100) * 1e-9)
    assert r["programs"]["jit_b"] == [2, pytest.approx(220e-9)]


def test_idle_gaps_are_named_by_host_activity():
    tr = _trace([["jit_a(1)", 0, 100], ["jit_a(1)", 400, 100]],
                host=[[D.WINDOW_EVENT, 0, 500],
                      ["np.asarray(jax.Array)", 90, 400],
                      ["shard_args", 120, 200]])
    r = D.reduce_trace(tr)
    (name, seconds), = r["idle_gaps"]
    assert seconds == pytest.approx(300e-9)
    assert name == "np.asarray(jax.Array)"


def test_ops_are_attributed_to_their_program():
    tr = _trace([["jit_a(1)", 0, 100], ["jit_b(2)", 200, 100]],
                ops=[["%while.1", 10, 50], ["%fusion.2", 210, 30],
                     ["%fusion.2", 250, 30]],
                host=[[D.WINDOW_EVENT, 0, 300]])
    ops = dict(map(tuple, D.reduce_trace(tr)["device_ops"]))
    assert ops["jit_a:%while.1"] == pytest.approx(50e-9)
    assert ops["jit_b:%fusion.2"] == pytest.approx(60e-9)


def test_no_device_plane():
    tr = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert not D.has_device(tr)
    with pytest.raises(ValueError):
        D.reduce_trace(tr)


def test_recorded_v5e_trace_against_a_raster():
    tr = json.loads(DATA.read_text())
    r = D.reduce_trace(tr)
    w0, w1 = D.trace_window(tr)
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    # an independent busy count: mark every nanosecond some program ran
    grid = np.zeros(int(w1 - w0), bool)
    per_prog = {}
    for plane in tr["planes"]:
        if not plane["name"].startswith(D.DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            if line["name"] != "XLA Modules":
                continue
            for name, s, d in line["events"]:
                lo, hi = int(max(s, w0) - w0), int(min(s + d, w1) - w0)
                if hi > lo:
                    grid[lo:hi] = True
                    p = D.program_name(name)
                    per_prog[p] = per_prog.get(p, 0) + (hi - lo)
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-9, abs=5e-9)
    assert set(r["programs"]) == {"jit__route_queries", "jit_hnsw_search"}
    for p, ns in per_prog.items():
        assert r["programs"][p][1] == pytest.approx(ns * 1e-9, abs=5e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10


def test_program_and_op_names():
    assert D.program_name("jit_hnsw_search(1843)") == "jit_hnsw_search"
    assert D.op_name("%while.71 = (s32[2]) while(%x)") == "%while.71"
