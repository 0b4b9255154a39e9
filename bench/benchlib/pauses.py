"""Pauses of the whole process while the window runs.

A thread wakes every ``tick_s``. When a wake-up comes more than
``threshold_s`` late, the process was paused: a thread held the
interpreter lock through a long call, or the host did not run the
process. Each pause is kept with when it began, how long it lasted and
the CPU seconds the process spent over it: near none means the process
was not run; more means some thread ran. While a pause lasts,
``faulthandler``'s watchdog, a C thread that needs no interpreter lock,
writes every thread's Python stack to ``dump_path`` once each
``tick_s + threshold_s``: the thread that is not waiting is the one that
held the lock.

``host_counters`` reads what the host says of the process and its
group: involuntary context switches, page faults, CPU-quota throttling
and pressure stall time, so that a window's deltas show whether the
host took the CPU away.
"""
from __future__ import annotations

import faulthandler
import resource
import threading
import time
from pathlib import Path


class PauseWatch:
    def __init__(self, dump_path: Path, tick_s: float = 0.025,
                 threshold_s: float = 0.2):
        self.dump_path = Path(dump_path)
        self.tick_s = tick_s
        self.threshold_s = threshold_s
        self.pauses = []      # (began, seconds, cpu seconds), monotonic
        self._stop = threading.Event()
        self._thread = None
        self._fh = None

    def start(self) -> None:
        self.dump_path.parent.mkdir(parents=True, exist_ok=True)
        self._fh = open(self.dump_path, "w")
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-pausewatch")
        self._thread.start()

    def _run(self) -> None:
        limit = self.tick_s + self.threshold_s
        last, last_cpu = time.monotonic(), time.process_time()
        while not self._stop.is_set():
            faulthandler.dump_traceback_later(limit, repeat=True,
                                              file=self._fh)
            time.sleep(self.tick_s)
            now, cpu = time.monotonic(), time.process_time()
            if now - last > limit:
                self.pauses.append((last, now - last, cpu - last_cpu))
            last, last_cpu = now, cpu
        faulthandler.cancel_dump_traceback_later()

    def stop(self) -> None:
        """Stop watching; the dump file is removed when no pause wrote
        to it. Stopping twice, or before starting, does nothing."""
        if self._thread is None or self._stop.is_set():
            return
        self._stop.set()
        self._thread.join()
        self._fh.close()
        if self.dump_path.stat().st_size == 0:
            self.dump_path.unlink()

    def summary(self, t0: float, top: int = 5) -> dict:
        """Count, and the longest ``top`` pauses as ``[began s after
        t0, ms, cpu ms]``, with the dump's path where one was written."""
        longest = sorted(self.pauses, key=lambda p: -p[1])[:top]
        return {"count": len(self.pauses),
                "longest": [[b - t0, s * 1e3, c * 1e3]
                            for b, s, c in longest],
                "dump": (str(self.dump_path) if self.dump_path.exists()
                         else None)}


def _read_kv(path: str) -> dict:
    """``key value`` lines of a cgroup or pressure file, as numbers;
    empty where the host has no such file."""
    out = {}
    try:
        with open(path) as f:
            for line in f:
                parts = line.split()
                if len(parts) == 2:
                    out[parts[0]] = int(parts[1])
                elif parts and parts[0] in ("some", "full"):
                    total = [p for p in parts if p.startswith("total=")]
                    if total:
                        out[parts[0]] = int(total[0][6:])
    except (OSError, ValueError):
        pass
    return out


def host_counters() -> dict:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"invol_ctx_switches": ru.ru_nivcsw, "major_faults": ru.ru_majflt,
           "minor_faults": ru.ru_minflt}
    cg = _read_kv("/sys/fs/cgroup/cpu.stat")
    for key in ("nr_throttled", "throttled_usec"):
        if key in cg:
            out["cgroup_" + key] = cg[key]
    for res in ("cpu", "memory", "io"):
        p = _read_kv(f"/proc/pressure/{res}")
        if "some" in p:
            out[f"pressure_{res}_some_us"] = p["some"]
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if k in before}
