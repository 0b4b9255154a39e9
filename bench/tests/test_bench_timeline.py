"""The five readers that time a query's phases from the program's spans,
on hand-made spans."""
import numpy as np
import pytest

from benchlib import spec, timeline
from benchlib.drive import Window
from benchlib.runner import Run

ROOT = spec.ROOT
READERS = ("queue_wait_ms.online", "route_call_ms.online",
           "walk_call_ms.online", "host_held_share.online",
           "walk_useful_share.batch")


class _Span:
    def __init__(self, name, sid, parent, t0, t1, **attrs):
        self.name, self.span_id, self.parent_id = name, sid, parent
        self.t0, self.t1, self.attrs = t0, t1, attrs

    @property
    def duration(self):
        return self.t1 - self.t0


def _run(spans, t_start=0.0, t_end=10.0):
    cell = spec.Cell("c.m", {"engine": {"executor_batch": 4}},
                     {"loop": "open"}, 1, ROOT / "bench", [], [])
    win = Window(t_start, t_end, np.zeros(1), np.zeros(1), np.zeros(1),
                 np.ones(1), np.arange(1), [None])
    return Run(cell, 1.0, win, 0.9, {}, {}, spans, None)


def _read(name, run):
    return spec.load_reader(name, ROOT / "bench")(run)


def test_call_and_queue_means_over_overlapping_walks():
    spans = [_Span("kernel.beam_walk", 1, None, 1.000, 1.010),
             _Span("kernel.beam_walk", 2, None, 1.004, 1.024),  # overlaps
             _Span("coordinator.route", 3, None, 0.990, 0.992),
             _Span("executor.queued", 4, 9, 1.0, 1.0005),
             _Span("executor.queued", 5, 9, 1.0, 1.0015),
             _Span("executor.queued", 6, 9, 1.0, None)]         # in flight
    run = _run(spans)
    assert _read("walk_call_ms.online", run) == pytest.approx(15.0)
    assert _read("route_call_ms.online", run) == pytest.approx(2.0)
    assert _read("queue_wait_ms.online", run) == pytest.approx(1.0)


def test_host_held_share_unions_device_calls_and_clips_to_window():
    spans = [
        # query A crosses the window's start: only [0, 4] counts; walks
        # overlap one another and cover [1, 2.5]
        _Span("query", 1, None, -2.0, 4.0),
        _Span("kernel.beam_walk", 2, 1, 1.0, 2.0),
        _Span("kernel.beam_walk", 3, 1, 1.5, 2.5),
        # query B overlaps A and has no device call open at all
        _Span("query", 4, None, 3.0, 5.0),
        # query C crosses the window's end; routing runs before its
        # root opens and a walk leaves the window
        _Span("coordinator.route", 5, None, 8.5, 9.0),
        _Span("query", 6, None, 9.0, 12.0),
        _Span("kernel.beam_walk", 7, 6, 9.5, 11.0),
        _Span("executor.queued", 8, 6, 9.0, 9.5),
    ]
    run = _run(spans)
    # held: [0, 1] + [2.5, 5] + [9, 9.5] = 4.0 of the window's 10 s
    assert timeline.open_share(run, "query", ("coordinator.route",
                                              "kernel.beam_walk")) == \
        pytest.approx(0.4)
    assert _read("host_held_share.online", run) == pytest.approx(0.4)
    # a routing call over a query's whole life leaves nothing held
    run = _run([_Span("query", 1, None, 2.0, 3.0),
                _Span("coordinator.route", 2, None, 1.0, 4.0)])
    assert _read("host_held_share.online", run) == 0.0


def test_walk_useful_share_over_launches():
    spans = [_Span("kernel.beam_walk", 1, None, 0, 1, batch=2,
                   expansions=30, trips=20),
             _Span("kernel.beam_walk", 2, None, 0, 1, batch=4,
                   expansions=50, trips=20)]
    # (30 + 50) / ((20 + 20) * 4 rows)
    assert _read("walk_useful_share.batch", _run(spans)) == \
        pytest.approx(0.5)


def test_readers_return_none_without_the_new_spans_or_attrs():
    # a program that has the old spans only: route, walk without the
    # counter, merge
    old = _run([_Span("coordinator.route", 1, None, 0, 0.001),
                _Span("kernel.beam_walk", 2, None, 0, 0.006, batch=1),
                _Span("merge", 3, None, 0.007, 0.008)])
    assert _read("walk_useful_share.batch", old) is None
    assert _read("queue_wait_ms.online", old) is None
    assert _read("host_held_share.online", old) is None   # no query root
    empty = _run([])
    for name in READERS:
        assert _read(name, empty) is None
    assert timeline.open_share(_run([_Span("query", 1, None, 0, 1)],
                                    t_start=5.0, t_end=5.0),
                               "query", ()) is None


def test_overlap_of_unions():
    a = timeline.union([(0, 2), (1, 3), (5, 6)])
    assert a == [[0, 3], [5, 6]]
    b = timeline.union([(2, 5.5)])
    assert timeline.overlap(a, b) == pytest.approx(1.5)
    assert timeline.overlap(a, []) == 0.0
