"""Rehearsal of ``chip_smoke.py`` on the CPU: every phase the chip run
makes, at a tiny size, through the same functions. The build goes
through the spawned worker pool, so the pool's JAX pinning runs too."""
import importlib.util
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def built(smoke):
    x, queries, truth = smoke.make_corpus(2048, 32, seed=0)
    cfg = smoke.deep_config(0, shards=4, meta_size=64)
    index, info = smoke.build_phase(x, cfg, workers=2)
    assert info["n"] == 2048 and info["d"] == smoke.DIM
    assert info["shards"] == 4 and info["workers"] == 2
    assert index.build_stats["build_mode"] == "parallel"
    return index, queries, truth


@pytest.mark.parametrize("quantize", (False, True))
def test_engine_phase(smoke, built, quantize):
    out = smoke.engine_phase(*built, quantize=quantize)
    assert out["ok"] and out["recall_at_10"] >= smoke.RECALL_FLOOR
    assert out["restarts"] == out["redispatched"] == 0
    assert out["expired_queries"] == 0 and not out["gave_up"]


def test_fused_phase(smoke, built):
    out = smoke.fused_phase(*built)
    assert out["ok"] and out["recall_at_10"] >= smoke.RECALL_FLOOR


def test_four_chip_phase_on_local_devices(smoke, built):
    # one CPU device here: the (1, 1) mesh runs the same SPMD program
    # and the same shard-placement check as the (1, 4) mesh on a chip
    out = smoke.four_chip_phase(*built)
    assert out["ok"] and out["devices"] >= 1


def test_recall_check_raises(smoke):
    with pytest.raises(AssertionError, match="failed its checks"):
        smoke._check({"phase": "x"}, False)


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
