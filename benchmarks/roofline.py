"""Kernel roofline: measured achieved vs peak bytes/s and FLOP/s for the
three search kernels (``beam_search``, ``quant_distance``,
``merge_topk``), plus the legacy dry-run roofline table when its
artifacts exist.

Peaks are *calibrated live* on whatever backend runs the benchmark (a
large jitted matmul for FLOP/s, a large jitted read+write for bytes/s)
so "fraction of peak" always compares against what this machine can
actually sustain, not a datasheet number. Per kernel we time the real
entry point wall-clock and divide analytic op counts by it:

  * ``beam_search`` — the fused arena strategy (``shard_axis="kernel"``)
    against the retired while-loop strategies on the same routed
    workload. FLOPs/bytes come from the expansion counts the walk
    actually executed (``beam_search_stats``), so the numerator is the
    algorithm's minimal work, not an implementation's traffic.
  * ``quant_distance`` — the asymmetric int8 scan.
  * ``merge_topk`` — the dedup top-k merge.

Writes ``BENCH_beam_kernel.json``. The kernel section ALWAYS runs (the
old module silently no-opped without dry-run artifacts — bench-smoke now
always gets rows); a non-quick ``main()`` exits nonzero if the rows are
empty or the fused beam kernel fails to beat the while-loop path at the
largest config.

PYTHONPATH=src python -m benchmarks.roofline [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.core import hnsw as H
from repro.core import metrics as M
from repro.core.arena import arena_search
from repro.core.quant import QuantParams
from repro.core.router import route_queries
from repro.kernels.beam_search import beam_search_stats
from repro.kernels.merge_topk import merge_topk
from repro.kernels.quant_distance import quant_scores

ART = os.environ.get("DRYRUN_ART", "artifacts/dryrun")
TOPK = C.TOPK


# ---------------------------------------------------------------------------
# Timing + peak calibration
# ---------------------------------------------------------------------------


def _best_time(fn: Callable[[], None], iters: int = 3,
               warmup: int = 1) -> float:
    """Best-of-N wall-clock of ``fn`` (fn must block on its result)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate_peaks(quick: bool = False) -> Dict[str, float]:
    """Sustained peak FLOP/s (large f32 matmul) and bytes/s (large
    read+write) on the current backend."""
    m = 512 if quick else 1024
    a = jnp.asarray(np.random.default_rng(0).normal(
        size=(m, m)).astype(np.float32))
    mm = jax.jit(lambda x, y: x @ y)
    t = _best_time(lambda: jax.block_until_ready(mm(a, a)))
    flops_per_s = 2.0 * m ** 3 / t

    n = (16 if quick else 64) * 2 ** 20 // 4   # f32 elements
    buf = jnp.zeros((n,), jnp.float32)
    touch = jax.jit(lambda x: x + 1.0)         # read n + write n
    t = _best_time(lambda: jax.block_until_ready(touch(buf)))
    bytes_per_s = 2.0 * n * 4 / t
    return {"backend": jax.default_backend(),
            "flops_per_s": flops_per_s, "bytes_per_s": bytes_per_s}


def _achieved(flops: float, model_bytes: float, seconds: float,
              peaks: Dict[str, float]) -> Dict[str, float]:
    af = flops / seconds
    ab = model_bytes / seconds
    return {
        "wall_s": round(seconds, 6),
        "achieved_flops_per_s": round(af, 1),
        "achieved_bytes_per_s": round(ab, 1),
        "frac_peak_flops": round(af / peaks["flops_per_s"], 4),
        "frac_peak_bytes": round(ab / peaks["bytes_per_s"], 4),
    }


# ---------------------------------------------------------------------------
# beam_search — fused strategy vs the while-loop strategies
# ---------------------------------------------------------------------------


def _beam_rows(quick: bool, peaks: Dict[str, float]) -> List[Dict]:
    configs = [(2_000, 64)] if quick else [(8_000, 128), (20_000, 256)]
    ef, kb = 80, 2
    rows = []
    for n_items, batch in configs:
        w = C.euclidean_workload(n=n_items, q=batch)
        index = C.build_index(w)
        arena = index.arena()
        meta = index.meta_arrays()
        poc = jnp.asarray(index.part_of_center)
        q = jnp.asarray(M.preprocess_queries(w.queries[:batch], w.metric))
        mask, _ = route_queries(meta, poc, q, metric=w.metric,
                                branching_factor=kb,
                                num_shards=index.num_shards,
                                ef=max(64, kb))
        mask = jnp.asarray(mask)
        load = int(np.max(np.asarray(mask).sum(axis=0)))
        capacity = min(batch, max(32, -(-load // 32) * 32))

        def timed(ax):
            def call():
                ids, sc, _ = arena_search(
                    arena, meta, poc, q, metric=w.metric, k=TOPK, ef=ef,
                    branching_factor=kb, capacity=capacity, mask=mask,
                    shard_axis=ax)
                jax.block_until_ready((ids, sc))
                return ids
            t = _best_time(call)
            return t, call()

        # two retired baselines: "vmap" is THE while-loop path (the
        # per-query lax.while_loop batched over every routed row — what
        # the fused walk replaces op-for-op, and the gate's baseline);
        # "map" is the old sequential CPU special case, measured and
        # reported because its per-shard early termination keeps it
        # competitive on CPU (see API.md) — it is retired for strategy
        # unification: it is w dispatches where "kernel" is one.
        t_fused, ids_fused = timed("kernel")
        t_loop, ids_loop = timed("vmap")
        t_map, _ = timed("map")
        rec = C.precision(np.asarray(ids_fused), w.true_ids[:batch])

        # analytic op counts from the expansions this workload executes:
        # the kernel-strategy prologue (queue drain + descend) feeds the
        # counting walk the exact rows the timed call walked
        qidx = jax.vmap(lambda col: jnp.nonzero(
            col, size=capacity, fill_value=batch)[0])(mask.T)
        qs = q[jnp.clip(qidx, 0, batch - 1)]
        entries = jax.vmap(lambda sl, qrow: jax.vmap(
            lambda qv: H._greedy_descend(
                sl.as_graph(), qv, w.metric, max_steps=64))(qrow))(
                    arena, qs)
        _, _, iters = beam_search_stats(
            arena.data, arena.bottom, qs, entries, metric=w.metric,
            ef=max(ef, TOPK), max_iters=400)
        e_total = int(np.asarray(iters).sum())
        n_rows = int(qidx.size)
        d = int(arena.data.shape[2])
        m0 = int(arena.bottom.shape[2])
        efc = min(max(ef, TOPK), int(arena.data.shape[1]))
        # distances dominate: 2d FLOPs per scored row, m0 rows per
        # expansion plus one entry score per walk
        flops = 2.0 * d * (e_total * m0 + n_rows)
        # minimal data movement of the walk: adjacency row + vector rows
        # per expansion, plus queries in and the beam out
        model_bytes = (e_total * m0 * (4.0 + 4.0 * d)
                       + n_rows * (4.0 * d + 8.0 * efc))
        row = {
            "n_items": n_items, "batch": batch, "ef": ef,
            "capacity": capacity, "impl": "xla-oracle",
            "expansions": e_total,
            "qps_fused": round(batch / t_fused, 1),
            "qps_loop": round(batch / t_loop, 1),
            "qps_map": round(batch / t_map, 1),
            "speedup_vs_loop": round(t_loop / t_fused, 3),
            "speedup_vs_map": round(t_map / t_fused, 3),
            "recall_at10": round(rec, 4),
            "flops": flops, "model_bytes": model_bytes,
            **_achieved(flops, model_bytes, t_fused, peaks),
        }
        rows.append(row)
        C.emit(f"kernel/beam_search/n{n_items}_b{batch}",
               1e6 * t_fused / batch,
               f"qps_fused={row['qps_fused']};qps_loop={row['qps_loop']};"
               f"qps_map={row['qps_map']};"
               f"speedup={row['speedup_vs_loop']};"
               f"frac_peak_flops={row['frac_peak_flops']};"
               f"frac_peak_bytes={row['frac_peak_bytes']}")
    return rows


# ---------------------------------------------------------------------------
# quant_distance + merge_topk
# ---------------------------------------------------------------------------


def _quant_rows(quick: bool, peaks: Dict[str, float]) -> List[Dict]:
    b, n = (64, 2_048) if quick else (256, 16_384)
    d = C.N_DIM
    rng = np.random.default_rng(4)
    x = rng.normal(size=(n, d)).astype(np.float32)
    params = QuantParams.from_data(x)
    codes = jnp.asarray(params.quantize(x))
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    scale, zero = jnp.asarray(params.scale), jnp.asarray(params.zero)

    t = _best_time(lambda: jax.block_until_ready(
        quant_scores(q, codes, scale, zero, metric="l2")))
    flops = 2.0 * b * n * d             # the b x n x d contraction
    model_bytes = n * d * 1.0 + b * d * 4.0 + b * n * 4.0 + 2 * d * 4.0
    row = {"b": b, "n": n, "d": d, "flops": flops,
           "model_bytes": model_bytes,
           **_achieved(flops, model_bytes, t, peaks)}
    C.emit(f"kernel/quant_distance/b{b}_n{n}", 1e6 * t,
           f"frac_peak_flops={row['frac_peak_flops']};"
           f"frac_peak_bytes={row['frac_peak_bytes']}")
    return [row]


def _merge_rows(quick: bool, peaks: Dict[str, float]) -> List[Dict]:
    b = 128 if quick else 1_024
    m = C.NUM_SHARDS * TOPK
    rng = np.random.default_rng(5)
    scores = rng.normal(size=(b, m)).astype(np.float32)
    ids = rng.integers(0, 5_000, size=(b, m)).astype(np.int32)
    ids[:, ::7] = -1
    scores[ids < 0] = -np.inf
    sj, ij = jnp.asarray(scores), jnp.asarray(ids)

    t = _best_time(lambda: jax.block_until_ready(
        merge_topk(sj, ij, k=TOPK)))
    flops = float(b * m * TOPK)         # k masked-argmax rounds over m
    model_bytes = b * (m * 8.0 + TOPK * 8.0)
    row = {"b": b, "m": m, "k": TOPK, "flops": flops,
           "model_bytes": model_bytes,
           **_achieved(flops, model_bytes, t, peaks)}
    C.emit(f"kernel/merge_topk/b{b}_m{m}", 1e6 * t,
           f"frac_peak_flops={row['frac_peak_flops']};"
           f"frac_peak_bytes={row['frac_peak_bytes']}")
    return [row]


# ---------------------------------------------------------------------------
# Legacy dry-run table (kept as a secondary section; never gates)
# ---------------------------------------------------------------------------


def _legacy_dryrun_rows() -> List:
    recs = []
    for path in sorted(glob.glob(os.path.join(ART, "*__pod.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    if not recs:
        C.emit("roofline/missing", 0.0,
               "no dryrun artifacts; kernel section above still ran")
        return []
    rows = []
    for r in recs:
        name = f"roofline/{r['arch']}/{r['shape']}"
        if r.get("skipped"):
            C.emit(name, 0.0,
                   "skipped=" + r["skipped"][:40].replace(",", ";"))
            continue
        rf = r["roofline"]
        total = rf["compute_s"] + rf["memory_s"] + rf["collective_s"]
        frac = rf[rf["dominant"]] / total if total else 0.0
        C.emit(
            name, total * 1e6,
            f"dominant={rf['dominant']};compute_s={rf['compute_s']:.2e};"
            f"memory_s={rf['memory_s']:.2e};"
            f"collective_s={rf['collective_s']:.2e};"
            f"useful_ratio={r['useful_compute_ratio']:.2f};"
            f"peak_GiB={r['memory'].get('peak_bytes', 0)/2**30:.1f}")
        rows.append((r["arch"], r["shape"], rf["dominant"], frac))
    return rows


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def run(quick: bool = False, out: Optional[str] = None) -> dict:
    peaks = calibrate_peaks(quick)
    C.emit("kernel/peaks", 0.0,
           f"backend={peaks['backend']};"
           f"flops_per_s={peaks['flops_per_s']:.3e};"
           f"bytes_per_s={peaks['bytes_per_s']:.3e}")
    kernels = {
        "beam_search": {"rows": _beam_rows(quick, peaks)},
        "quant_distance": {"rows": _quant_rows(quick, peaks)},
        "merge_topk": {"rows": _merge_rows(quick, peaks)},
    }
    big = kernels["beam_search"]["rows"][-1] if \
        kernels["beam_search"]["rows"] else None
    summary = {
        "largest_config": None if big is None else
        {"n_items": big["n_items"], "batch": big["batch"]},
        "speedup_largest": None if big is None else
        big["speedup_vs_loop"],
        "fused_beats_loop_largest":
        bool(big and big["speedup_vs_loop"] > 1.0),
    }
    payload = {"quick": quick, "peaks": peaks, "kernels": kernels,
               "summary": summary,
               "legacy_dryrun": _legacy_dryrun_rows()}
    C.write_bench(out, "beam_kernel", payload)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    payload = run(quick=args.quick, out=args.out)
    json.dump({"figure": "beam_kernel", **payload}, sys.stdout, indent=2)
    print()
    if not args.quick:
        rows = payload["kernels"]["beam_search"]["rows"]
        if not rows:
            print("ROOFLINE GATE FAILED: no beam_search rows",
                  file=sys.stderr)
            sys.exit(1)
        if not payload["summary"]["fused_beats_loop_largest"]:
            print("ROOFLINE GATE FAILED: fused beam kernel speedup "
                  f"{payload['summary']['speedup_largest']} <= 1.0 at "
                  "the largest config", file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
