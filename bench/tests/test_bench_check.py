"""The check that decides ``correct``, driven through a whole run at a
tiny size on the CPU: a sound run passes; the control and each fault
the cells can have make it fail.

Faults (``benchlib.faults``): an answer altered where the walk
produces it, half of each batch left out, and the merge keeping half of
a query's shard partials. The control is the reference computed in
bfloat16 in the program's place (``bench/readings.py`` on the chip).
"""
import json
import shutil
import time
from pathlib import Path

import pytest

import numpy as np

from benchlib import check, faults, runner
from benchlib.reference import Bf16BruteForce

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The committed benchmark with both configurations cut to 1,024
    vectors over 4 shards and short mixes; one index shared by every
    run of this module."""
    root = tmp_path_factory.mktemp("bench_tiny")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    for name in ("deep96-f32", "deep96-int8"):
        p = root / "bench" / "configs" / f"{name}.json"
        c = json.loads(p.read_text())
        c.update(n=1024, n_queries=600)
        c["data"]["num_clusters"] = 8
        c["index"].update(num_shards=4, meta_size=32, sample_size=1024,
                          max_degree=8, max_degree_upper=4,
                          ef_construction=32, ef_search=48)
        p.write_text(json.dumps(c))
    for mix, upd in (("online", {"rate_qps": 40.0}),
                     ("batch", {"batch": 32, "callers": 2})):
        p = root / "bench" / "traffic" / f"{mix}.json"
        m = json.loads(p.read_text())
        m.update(upd)
        p.write_text(json.dumps(m))
    runner.prepare_environment(root / "bench" / ".cache")
    return root


def _run(root, cell, seed, close_wait_s=3.0, **kw):
    return runner.run_cell(cell, seed, 1.0, False,
                           t_process=time.monotonic(), root=root,
                           require_tpu=False, workers=0,
                           close_wait_s=close_wait_s,
                           log=lambda *a, **k: None, **kw)


@pytest.mark.parametrize("cell", ["deep96-f32.online", "deep96-int8.batch"])
def test_sound_run_is_correct(tiny, cell):
    res = _run(tiny, cell, 3000000021)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"
    assert 0.8 < res["metrics"]["recall_at_10"]["value"] <= 1.0


def test_control_is_not_correct(tiny):
    res = _run(tiny, "deep96-f32.online", 3000000022,
               client_factory=lambda index, x, engine, tracer:
               Bf16BruteForce(x))
    assert not res["correct"]
    gap = res["check"]["score_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", ["deep96-f32.online", "deep96-int8.batch"])
def test_altered_answer_is_not_correct(tiny, cell):
    with faults.planted("alter_id", 1024):
        res = _run(tiny, cell, 3000000023)
    assert not res["correct"]
    # the ids themselves are judged: on the quantized engine the host
    # rerank scores the altered id exactly, and the best one is gone
    top1 = res["check"]["top1_missed"]
    assert top1["value"] > top1["limit"]


def test_half_the_batch_left_out_is_not_correct(tiny):
    with faults.planted("half_batch", 1024):
        res = _run(tiny, "deep96-int8.batch", 3000000024, close_wait_s=1.0)
    assert not res["correct"]
    assert res["check"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("cell", ["deep96-f32.online", "deep96-int8.batch"])
def test_half_the_partials_dropped_is_not_correct(tiny, cell):
    with faults.planted("drop_half_partials", 1024):
        res = _run(tiny, cell, 3000000025)
    assert not res["correct"]
    top1 = res["check"]["top1_missed"]
    assert top1["value"] > top1["limit"]


def test_fault_is_unplanted_after_the_block(tiny):
    from repro.serving import engine as E
    walk, merge = E.Executor._search, E.merge_topk_np
    for name in faults.FAULTS:
        with faults.planted(name, 1024):
            pass
    assert (E.Executor._search, E.merge_topk_np) == (walk, merge)
    assert runner.run_open.__module__ == "benchlib.drive"


def test_top1_missed_counts_answers_without_the_nearest():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    q = x[:4] + 0.01
    d = ((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1)
    truth_ids = np.argsort(d, axis=1)[:, :3]
    truth_scores = -np.take_along_axis(d, truth_ids, 1)
    answers = [(truth_ids[i], truth_scores[i]) for i in range(4)]
    other = np.setdiff1d(np.arange(64), truth_ids[1])[0]
    ids = truth_ids[1].copy()
    ids[0] = other
    answers[1] = (ids[[1, 2, 0]], -d[1, ids[[1, 2, 0]]])
    v = check.judge(answers, np.arange(4), q, x, truth_ids, truth_scores,
                    3, {"score_gap_limit": 1e-3,
                        "top1_missed_limit": 0.2})
    assert v["numbers"]["top1_missed"] == (0.25, 0.2)
    assert v["numbers"]["score_gap"][0] < 1e-6
    assert not v["correct"]
