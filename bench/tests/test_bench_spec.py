"""A cell's configuration, mix and metrics are found by name, from files
alone; and the committed benchmark names only files that exist."""
import json
import re
import shutil
from pathlib import Path

import numpy as np
import pytest

from benchlib import peaks, readers, spec
from benchlib.drive import Window
from benchlib.runner import Run

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _tree(tmp_path: Path) -> Path:
    """A benchmark of one made-up cell, in files only."""
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "toy.json").write_text(json.dumps({"k": 3}))
    (b / "traffic" / "steady.json").write_text(
        json.dumps({"loop": "open", "rate_qps": 5}))
    (b / "metrics" / "answer.py").write_text(
        "def read(run):\n    return 42\n")
    (b / "metrics" / "nothing.layer.py").write_text(
        "def read(run):\n    return None\n")
    (b / "metrics" / "other.py").write_text(
        "def read(run):\n    return 1\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["bench"],
        "workloads": [{"name": "toy.steady", "config": "toy",
                       "traffic": "steady", "chips": 1}],
        "end_to_end": [
            {"name": "answer", "unit": "s", "better": "lower",
             "source": "host_clock"},
            {"name": "other", "unit": "s", "better": "lower",
             "source": "host_clock", "workloads": ["else.where"]}],
        "per_layer": [{"name": "nothing.layer", "unit": "ms",
                       "better": "lower", "source": "program_span",
                       "layer": "x", "moves": "answer"}]}))
    return tmp_path


def test_cell_is_found_by_name(tmp_path):
    cell = spec.load_cell("toy.steady", _tree(tmp_path))
    assert cell.config == {"k": 3}
    assert cell.traffic["rate_qps"] == 5
    assert [m.name for m in cell.end_to_end] == ["answer"]
    assert [m.name for m in cell.per_layer] == ["nothing.layer"]
    assert spec.read_metrics(cell.end_to_end, None) == {
        "answer": {"value": 42.0, "unit": "s"}}
    # a reader that finds nothing leaves its metric out
    assert spec.read_metrics(cell.per_layer, None) == {}


def test_unknown_names_are_errors(tmp_path):
    root = _tree(tmp_path)
    with pytest.raises(spec.SpecError):
        spec.load_cell("toy.bursty", root)
    (root / "bench" / "metrics" / "answer.py").unlink()
    with pytest.raises(spec.SpecError):
        spec.load_cell("toy.steady", root)


def test_a_new_cell_is_only_new_files(tmp_path):
    root = _tree(tmp_path)
    shutil.copy(root / "bench" / "traffic" / "steady.json",
                root / "bench" / "traffic" / "bursty.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "toy.bursty", "config": "toy",
                               "traffic": "bursty", "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.load_cell("toy.bursty", root).traffic["loop"] == "open"


def test_committed_benchmark_loads_every_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert {m.name for m in cell.end_to_end} >= {"setup_s",
                                                     "recall_at_10"}
        assert cell.per_layer
        assert cell.config["name"] == w["config"]
    names = ([w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
             + [c["name"] for c in bench["configs"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
    assert all(m["moves"] in {e["name"] for e in bench["end_to_end"]}
               for m in bench["per_layer"])


def _run(spans, traffic=None, trace=None):
    cell = spec.Cell("c.m", {"engine": {"executor_batch": 16}},
                     traffic or {"loop": "closed"}, 1, ROOT / "bench",
                     [], [])
    win = Window(0.0, 10.0, np.zeros(4), np.zeros(4), np.zeros(4),
                 np.array([1.0, 2.0, 11.0, np.nan]), np.arange(4),
                 [None] * 4)
    return Run(cell, 1.0, win, 0.9, {"redispatched": 2},
               {"redispatched": 5}, spans, trace)


class _Span:
    def __init__(self, name, sid, parent, t0, t1, **attrs):
        self.name, self.span_id, self.parent_id = name, sid, parent
        self.t0, self.t1, self.attrs = t0, t1, attrs

    @property
    def duration(self):
        return self.t1 - self.t0


def test_readers_on_spans_and_trace():
    spans = [_Span("merge", 1, None, 0.0, 0.004),
             _Span("rerank", 2, 1, 0.001, 0.003),
             _Span("merge", 3, None, 1.0, 1.002),
             _Span("kernel.beam_walk", 4, None, 0, 0.01, batch=4),
             _Span("kernel.beam_walk", 5, None, 0, 0.01, batch=12),
             _Span("dispatch", 6, None, 0, 0),
             _Span("dispatch", 7, None, 0, 0)]
    run = _run(spans, trace={"window_s": 2.0, "busy_s": 1.5,
                             "programs": {readers.WALK_PROGRAM: [4, 0.04]}})
    assert readers.self_ms(run, "merge") == pytest.approx(2.0)
    assert readers.self_ms(run, "rerank") == pytest.approx(2.0)
    assert readers.batch_fill(run) == pytest.approx(0.5)
    assert readers.redispatch_share(run) == pytest.approx(1.5)
    assert readers.program_ms(run, readers.WALK_PROGRAM) == \
        pytest.approx(10.0)
    assert readers.program_ms(run, readers.ROUTE_PROGRAM) is None
    assert readers.idle_share(run) == pytest.approx(0.25)
    assert readers.qps(run) == pytest.approx(0.2)
    assert readers.latency_ms(run, 50) is None     # closed loop
    assert readers.latency_ms(_run([], {"loop": "open"}), 50) == \
        pytest.approx(6500)


def test_peaks_are_published_and_unknown_is_an_error():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
