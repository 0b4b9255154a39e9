"""From a profiler trace to device numbers.

Two steps, so that the second can be checked on a small recorded trace:

1. :func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler``
   wrote into a plain dict: planes, their lines, and events as
   ``[name, start_ns, duration_ns]``. Device planes keep their
   ``XLA Modules`` and ``XLA Ops`` lines; the host plane keeps every
   line.
2. :func:`reduce_trace` takes that dict and the traced window and gives
   busy and window seconds, device time per program, the device
   operations that took most time, and the longest idle gaps with what
   the host was doing in each.

Busy time is the union of the intervals in which a program ran on a
device (``XLA Modules``), clipped to the window and averaged over the
devices.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
WINDOW_EVENT = "bench.trace_window"
_DEVICE_LINES = ("XLA Modules", "XLA Ops")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PREFIX)
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in _DEVICE_LINES:
                continue
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def has_device(trace: dict) -> bool:
    return any(p["name"].startswith(DEVICE_PREFIX) for p in trace["planes"])


def program_name(module_event: str) -> str:
    """``jit_hnsw_search(1234)`` -> ``jit_hnsw_search``."""
    return re.sub(r"\(\d+\)$", "", module_event)


def op_name(op_event: str) -> str:
    """``%while.71 = (...) while(...)`` -> ``%while.71``."""
    return op_event.split(" = ", 1)[0].strip()


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def trace_window(trace: dict) -> Optional[Tuple[float, float]]:
    """``(start_ns, end_ns)`` of the harness's window annotation."""
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_EVENT:
                    return start, start + dur
    return None


def reduce_trace(trace: dict, window: Optional[Tuple[float, float]] = None,
                 top: int = 10) -> dict:
    """Device numbers of the window (default: the annotated one, else
    the span of all device events)."""
    devices = [p for p in trace["planes"]
               if p["name"].startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("the trace holds no device plane")
    window = window or trace_window(trace)
    if window is None:
        mods = [e for p in devices for e in _line(p, "XLA Modules")]
        window = (min(e[1] for e in mods), max(e[1] + e[2] for e in mods))
    w0, w1 = window
    busy_total = 0.0
    programs: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        clipped = []
        for name, start, dur in _line(plane, "XLA Modules"):
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            prog = programs.setdefault(program_name(name), [0, 0.0])
            prog[0] += 1
            prog[1] += (e - s) * 1e-9
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        mods = sorted((s, e, program_name(n)) for n, s, d in
                      _line(plane, "XLA Modules") for e in [s + d])
        j = 0
        for name, start, dur in sorted(_line(plane, "XLA Ops"),
                                       key=lambda ev: ev[1]):
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            while j + 1 < len(mods) and mods[j + 1][0] <= start:
                j += 1
            owner = (mods[j][2] if mods and mods[j][0] <= start
                     < mods[j][1] else "?")
            key = f"{owner}:{op_name(name)}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
    n_dev = len(devices)
    for prog in programs.values():
        prog[1] /= n_dev
    device_ops = sorted(([k, v / n_dev] for k, v in ops.items()),
                        key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n_dev * 1e-9,
        "programs": {k: [int(c), s] for k, (c, s) in programs.items()},
        "device_ops": device_ops,
        "idle_gaps": [[host_activity(trace, s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def host_activity(trace: dict, s: float, e: float) -> str:
    """The host event that covers most of ``[s, e]`` (the shortest such
    on a tie), or ``"no host event"``."""
    best, best_cover, best_dur = "no host event", 0.0, 0.0
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_EVENT:
                    continue
                cover = min(e, start + dur) - max(s, start)
                if cover > best_cover or (cover == best_cover > 0
                                          and dur < best_dur):
                    best, best_cover, best_dur = name, cover, dur
    return best
