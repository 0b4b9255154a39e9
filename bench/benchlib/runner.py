"""One run of one cell: set-up, warm-up, the measured window, the
traced slice, the check, and the result line."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from benchlib import check as C
from benchlib import pauses as P
from benchlib import spec as S
from benchlib import stats as ST
from benchlib import traffic as T
from benchlib.data import make_corpus
from benchlib.drive import Window, run_closed, run_open
from benchlib.reference import exact_topk

TRACE_AT_S = 10.0     # the profiler starts this far into the window...
TRACE_S = 2.0         # ...and records this long (shorter windows: a third)
CLOSE_WAIT_S = 60.0   # how long past the window's close answers may come


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: S.Cell
    setup_s: float
    window: Window
    recall: Optional[float]
    stats_before: dict
    stats_after: dict
    spans: list                  # program spans that began in the window
    trace: Optional[dict]        # devtrace.reduce_trace of the slice


def prepare_environment(cache_dir: Path) -> None:
    """Before JAX is imported: the compile cache inside the checkout
    unless ``JAX_COMPILATION_CACHE_DIR`` names one, every program
    cached, and no runtime logs written outside the checkout."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(cache_dir / "jax"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind})")
    return devs


class GcWatch:
    """The interpreter's garbage-collection pauses while ``armed``:
    count and longest pause per generation (a full collection holds
    every thread of the process, the program's included)."""

    def __init__(self):
        import gc
        self.armed = False
        self.pauses = {0: [0, 0.0], 1: [0, 0.0], 2: [0, 0.0]}
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        elif self.armed:
            p = self.pauses[info["generation"]]
            p[0] += 1
            p[1] = max(p[1], (time.monotonic() - self._t0) * 1e3)

    def close(self) -> None:
        import gc
        gc.callbacks.remove(self._on)


class CompileCounter:
    """Counts programs traced or compiled while ``armed``."""

    def __init__(self):
        from jax import monitoring
        self.armed = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event in (
                "/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/backend_compile_duration"):
            self.count += 1


def ground_truth(config: dict, x, queries, cache_dir: Path):
    """Exact top-k of the whole query set, computed once per checkout
    and cached beside the index."""
    from benchlib.system import index_key
    path = cache_dir / "truth" / f"{index_key(config)}.npz"
    if path.exists():
        with np.load(path) as z:
            return z["ids"], z["scores"]
    ids, scores = exact_topk(queries, x, config["k"])
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.stem + f".tmp{os.getpid()}.npz")
    np.savez(tmp, ids=ids, scores=scores)
    os.replace(tmp, path)
    return ids, scores


def warm_up(client, queries, mix: dict, k: int) -> None:
    """The cell's own shapes, and no others: single-query searches for
    an open mix, ``batch``-query calls from every caller for a closed
    one. Rows from the end of the set, twice over."""
    n = len(queries)
    for _ in range(2):
        if mix["loop"] == "open":
            futs = [client.search(queries[n - 1 - i], k)
                    for i in range(32)]
        else:
            futs = []
            for c in range(mix["callers"]):
                lo = n - (c + 1) * mix["batch"]
                futs += client.search_batch(
                    queries[lo: lo + mix["batch"]], k)
        for f in futs:
            f.result(timeout=600)


def _trace_slice(log_dir: Path, t_start: float,
                 seconds: float) -> threading.Thread:
    """Profile ``TRACE_S`` (or a third of a short window) starting
    ``TRACE_AT_S`` (or a third) into the window, on a thread of its
    own, annotated so the reduction finds the slice."""
    import jax
    at = min(TRACE_AT_S, seconds / 3)
    length = min(TRACE_S, seconds / 3)

    def body():
        time.sleep(max(0.0, t_start + at - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host runtime events, no Python
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.trace_window"):
                time.sleep(length)
        finally:
            jax.profiler.stop_trace()

    th = threading.Thread(target=body, name="bench-profiler")
    th.start()
    return th


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: Path = S.ROOT,
             require_tpu: bool = True, close_wait_s: float = CLOSE_WAIT_S,
             workers: Optional[int] = None,
             client_factory: Optional[Callable] = None,
             log=print) -> dict:
    """Run one cell and return its result line (a dict). Set-up is
    timed from ``t_process``. ``client_factory(index, x, engine,
    tracer)`` puts another searcher in the program's place (the
    control); by default the program serves."""
    from benchlib import system as SYS

    t_enter = time.monotonic()
    cell = S.load_cell(cell_name, root)
    config, mix = cell.config, cell.traffic
    k = config["k"]
    cache_dir = cell.bench_dir / ".cache"
    devs = devices_for(cell.chips, require_tpu)
    counter = CompileCounter()
    gcw = GcWatch()
    marks = {"start": t_enter, "devices": time.monotonic()}

    x, queries = make_corpus(config)
    marks["corpus"] = time.monotonic()
    index, built = SYS.load_or_build_index(
        config, x, cache_dir, workers=os.cpu_count() if workers is None
        else workers)
    marks["index"] = time.monotonic()
    tracer = SYS.make_tracer() if trace else None
    if client_factory is None:
        client = SYS.start_client(index, config["engine"], tracer)
    else:
        client = client_factory(index, x, config["engine"], tracer)
    marks["engine"] = time.monotonic()
    watch = P.PauseWatch(cache_dir / "pauses" / f"{cell_name}.{seed}.txt")
    try:
        warm_up(client, queries, mix, k)
        marks["warm"] = time.monotonic()
        stats_before = _stats(client)
        if mix["loop"] == "open":
            sched = T.open_schedule(mix, seconds, seed, len(queries))
        t_start = time.monotonic() + 0.05
        setup_s = t_start - t_process
        # each phase's seconds, from process start to the window
        phases, prev = {}, t_process
        for name, t in marks.items():
            phases[name + "_s"] = t - prev
            prev = t
        log(json.dumps({"setup": {
            "setup_s": setup_s, "index_built": built, "phases": phases,
            "cpu_s": time.process_time(),
            "shard_rows": SYS.shard_rows(index)}}), flush=True)
        log_dir = cache_dir / "trace" / cell_name
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            prof = _trace_slice(log_dir, t_start, seconds)
        host_before = P.host_counters()
        watch.start()
        counter.armed = gcw.armed = True
        if mix["loop"] == "open":
            win = run_open(client, queries, sched.due_s, sched.query_idx,
                           k, t_start, seconds, close_wait_s)
        else:
            win = run_closed(client, queries,
                             T.closed_order(seed, len(queries)),
                             mix["batch"], mix["callers"], k, t_start,
                             seconds, close_wait_s)
        counter.armed = gcw.armed = False
        watch.stop()
        host = P.delta(host_before, P.host_counters())
        gcw.close()
        if trace:
            prof.join()
        stats_after = _stats(client)
        spans = ([s for s in tracer.snapshot()
                  if win.t_start <= s.t0 <= win.t_end] if trace else [])
        peak = max(_peak_bytes(d) for d in devs[: cell.chips])
    finally:
        watch.stop()
        SYS.stop_client(client)
    reduced = None
    if trace:
        from benchlib import devtrace as D
        t0 = time.monotonic()
        loaded = D.load_xplane(D.find_xplane(str(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)
        if D.has_device(loaded):   # a CPU rehearsal traces no device
            reduced = D.reduce_trace(loaded)
            log(json.dumps({"trace": {
                "reduce_s": time.monotonic() - t0,
                "window_s": reduced["window_s"],
                "busy_s": reduced["busy_s"],
                "programs": reduced["programs"]}}), flush=True)

    late = (win.submitted - win.due) * 1e3
    call = (win.returned - win.submitted) * 1e3
    lat = ST.latencies_ms(win.due, win.done)
    log(json.dumps({"window": {
        "seconds": seconds, "t_start_monotonic": win.t_start,
        "requests": win.attempted,
        "answered": win.attempted - win.failed,
        "completed_in_window": int(np.sum(win.done <= win.t_end)),
        "latency_p99_ms": ST.percentile(lat, 99),
        "generator_late_p50_ms": ST.percentile(late, 50),
        "generator_late_max_ms": float(np.max(late)) if len(late) else 0.0,
        "call_p50_ms": ST.percentile(call, 50),
        "call_max_ms": float(np.nanmax(call)) if len(call) else 0.0,
        "call_max_at_s": (float(win.submitted[np.nanargmax(call)]
                                - win.t_start) if len(call) else 0.0),
        "overshoot": ST.overshoot(win) if mix["loop"] == "open" else None,
        "pauses": watch.summary(win.t_start),
        "host": host,
        "gc_count_max_ms": gcw.pauses,
        "compiles_in_window": counter.count}}), flush=True)
    log(json.dumps({"engine_before": stats_before,
                    "engine_after": stats_after}), flush=True)

    truth_ids, truth_scores = ground_truth(config, x, queries, cache_dir)
    verdict = C.judge(win.answers, win.query_idx, queries, x, truth_ids,
                      truth_scores, k, config["check"])
    recall = C.recall_at_k(win.answers, win.query_idx, truth_ids, k)
    run = Run(cell, setup_s, win, recall, stats_before,
              stats_after, spans, reduced)
    metrics = S.read_metrics(cell.per_layer if trace else cell.end_to_end,
                             run)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(verdict["correct"]),
              "attempted": win.attempted,
              "failed": win.failed + verdict["numbers"]["malformed"][0],
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    numbers = verdict["numbers"]
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, (v, lim) in numbers.items()}
    for name, (v, lim) in numbers.items():
        print(f"check {name} {v} limit {lim}", file=sys.stderr, flush=True)
    return result


def _stats(client) -> dict:
    stats = getattr(client, "stats", None)
    if stats is None:
        return {}
    from benchlib.system import engine_stats
    return engine_stats(client)


def _peak_bytes(device) -> int:
    try:
        return int((device.memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
    except Exception:    # a backend without memory statistics
        return 0
