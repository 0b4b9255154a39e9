"""The pause watch sees a thread that holds the interpreter lock, and
names it in its stack dump."""
import random
import time

from benchlib import pauses as P


def _floats(n):
    rng = random.Random(0)
    return [rng.random() for _ in range(n)]


def test_pause_is_seen_and_dumped(tmp_path):
    data = _floats(1_000_000)
    t0 = time.perf_counter()
    sorted(data)    # one C call that never lets go of the lock
    one_sort = time.perf_counter() - t0
    w = P.PauseWatch(tmp_path / "dump.txt", tick_s=0.01,
                     threshold_s=min(0.05, one_sort / 4))
    w.start()
    time.sleep(0.1)
    sorted(data)
    time.sleep(0.1)
    w.stop()
    s = w.summary(t0)
    assert s["count"] >= 1
    assert s["longest"][0][1] > w.threshold_s * 1e3
    assert "test_pause_is_seen_and_dumped" in (tmp_path / "dump.txt").read_text()


def test_no_pause_leaves_no_dump(tmp_path):
    w = P.PauseWatch(tmp_path / "dump.txt", tick_s=0.01, threshold_s=0.5)
    w.start()
    time.sleep(0.1)
    w.stop()
    w.stop()
    assert w.summary(0.0) == {"count": 0, "longest": [], "dump": None}


def test_host_counters_move_forward():
    a = P.host_counters()
    sorted(_floats(10_000))
    d = P.delta(a, P.host_counters())
    assert set(d) == set(a)
    assert all(v >= 0 for v in d.values())
