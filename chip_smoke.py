"""Chip smoke test: drive the served search path once on one TPU chip and
check its answers.

    python3 chip_smoke.py [--seed N]        # one chip
    python3 chip_smoke.py --four-chips      # the SPMD path on 4 chips

The default run builds a DEEP-shaped index (big-ann-benchmarks DEEP:
d=96, float32, L2) of 131,072 vectors generated from ``--seed``, over 16
shards, with the parallel builder. It then serves 512 queries through
the public entry points, in one process:

  float  ``ServingEngine`` + ``PyramidClient`` over the float32 arena;
  int8   the same over the int8 arena, with the exact float32 rerank;
  fused  ``search_single_host``: route, walk and ``merge_topk`` on the
         device.

Each serving phase checks recall@10 against exact brute force (at least
0.90) and, for the engines, that no executor restarted, no work was
redispatched and no query expired. ``--four-chips`` runs only the SPMD
program (``make_pyramid_search_fn`` on a (1, 4) mesh, the arena sharded
w/4 shards per device) against ``search_single_host`` on one device.

Every phase prints one JSON line; compile and run seconds are for
information. Any failure raises and exits non-zero. The last line of a
passing run is ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_VECTORS = 131_072      # the smoke's corpus; a DEEP shard is far larger
DIM = 96                 # DEEP descriptors: 96-d float32 under L2
SHARDS = 16
N_QUERIES = 512
K = 10
RECALL_FLOOR = 0.90
FOUR_CHIP_RECALL_GAP = 0.01


def deep_config(seed: int, *, shards: int = SHARDS,
                meta_size: int = 1024):
    """The index configuration every phase serves. HNSW M=16 (bottom
    degree 16, upper 8) with ef_construction=60 keeps the host build of
    16 x 8,192 vectors to minutes; ef_search=100 and K=4 routed shards
    meet the recall floor."""
    from repro.common.config import PyramidConfig
    return PyramidConfig(
        metric="l2", num_shards=shards, meta_size=meta_size,
        sample_size=20_000, branching_factor=4, max_degree=16,
        max_degree_upper=8, ef_construction=60, ef_search=100,
        seed=seed)


def make_corpus(n: int, n_queries: int, seed: int):
    """DEEP-shaped vectors, queries drawn near them, and the exact
    top-K ids by brute force (numpy, independent of the search path)."""
    from repro.core.metrics import brute_force_topk
    from repro.data.synthetic import clustered_vectors, query_set
    x = clustered_vectors(n, DIM, max(8, n // 128), seed=seed)
    queries = query_set(x, n_queries, seed=seed + 1)
    truth, _ = brute_force_topk(queries, x, K, "l2")
    return x, queries, truth


def recall_at_k(ids, truth) -> float:
    ids = [set(int(v) for v in row if v >= 0) for row in ids]
    hits = sum(len(r & set(t.tolist())) for r, t in zip(ids, truth))
    return hits / truth.size


def build_phase(x, cfg, workers: int):
    from repro.build import build_pyramid_index_parallel
    t0 = time.perf_counter()
    index = build_pyramid_index_parallel(x, cfg, workers=workers)
    info = {"phase": "build", "n": int(x.shape[0]), "d": int(x.shape[1]),
            "shards": cfg.num_shards, "workers": workers,
            "build_s": time.perf_counter() - t0,
            "max_degree": cfg.max_degree,
            "ef_construction": cfg.ef_construction,
            "ef_search": cfg.ef_search,
            "branching_factor": cfg.branching_factor,
            "meta_size": cfg.meta_size}
    return index, info


def _wait_warm(client, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while not all(e["warmed"] for e in client.stats()["executors"].values()):
        if time.monotonic() > deadline:
            raise TimeoutError(f"executors not warm after {timeout_s}s")
        time.sleep(0.05)


def engine_phase(index, queries, truth, *, quantize: bool,
                 timeout_s: float = 600.0) -> dict:
    """Serve ``queries`` through ``ServingEngine`` + ``PyramidClient``."""
    from repro.core.client import PyramidClient, gather_arrays
    engine_kw = {"quantize": True, "rerank_factor": 4} if quantize else {}
    t0 = time.perf_counter()
    client = PyramidClient.from_index(index, **engine_kw)
    try:
        _wait_warm(client, timeout_s)
        t1 = time.perf_counter()
        ids, _ = gather_arrays(client.search_batch(queries, K), K,
                               timeout=timeout_s)
        t2 = time.perf_counter()
        stats = client.stats()
    finally:
        client.engine.shutdown()
    events = [e["event"] for e in stats["recovery_timeline"]]
    out = {"phase": "int8_engine" if quantize else "float_engine",
           "recall_at_10": recall_at_k(ids, truth),
           "restarts": stats["restarts"],
           "redispatched": stats["redispatched"],
           "expired_queries": stats["expired_queries"],
           "gave_up": "gave_up" in events,
           "compile_s": t1 - t0, "run_s": t2 - t1}
    _check(out, out["recall_at_10"] >= RECALL_FLOOR
           and out["restarts"] == 0 and out["redispatched"] == 0
           and out["expired_queries"] == 0 and not out["gave_up"])
    return out


def fused_phase(index, queries, truth) -> dict:
    """``search_single_host``: the fused route -> walk -> merge program."""
    from repro.core.distributed import search_single_host
    t0 = time.perf_counter()
    search_single_host(index, queries, K)
    t1 = time.perf_counter()
    ids, _, _ = search_single_host(index, queries, K)
    t2 = time.perf_counter()
    run_s = t2 - t1
    out = {"phase": "fused", "recall_at_10": recall_at_k(ids, truth),
           "compile_s": (t1 - t0) - run_s, "run_s": run_s}
    _check(out, out["recall_at_10"] >= RECALL_FLOOR)
    return out


def four_chip_phase(index, queries, truth) -> dict:
    """The SPMD program on every local device against
    ``search_single_host`` on one device, same index and queries."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import metrics as M
    from repro.core.distributed import (make_pyramid_search_fn,
                                        search_single_host)
    from repro.launch.mesh import make_local_mesh

    cfg = index.config
    mesh = make_local_mesh()
    n_model = mesh.shape["model"]
    arena = jax.device_put(index.arena(), NamedSharding(mesh, P("model")))
    replicated = NamedSharding(mesh, P())
    meta = jax.device_put(index.meta_arrays(), replicated)
    part_of_center = jax.device_put(jnp.asarray(index.part_of_center),
                                    replicated)
    per_device = cfg.num_shards // n_model
    for leaf in jax.tree.leaves(arena):
        shards = leaf.addressable_shards
        if (len({s.device for s in shards}) != n_model
                or any(s.data.shape[0] != per_device for s in shards)):
            raise AssertionError(
                f"arena leaf {leaf.shape} is not split {per_device} "
                f"shards per device: {[s.data.shape for s in shards]}")

    fn = make_pyramid_search_fn(mesh, cfg, k=K, batch=len(queries))
    q = jnp.asarray(M.preprocess_queries(queries, cfg.metric))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(arena, meta, part_of_center, q))
    t1 = time.perf_counter()
    ids, _ = jax.block_until_ready(fn(arena, meta, part_of_center, q))
    t2 = time.perf_counter()
    single_ids, _, _ = search_single_host(index, queries, K)
    run_s = t2 - t1
    spmd_recall = recall_at_k(np.asarray(ids), truth)
    single_recall = recall_at_k(single_ids, truth)
    out = {"phase": "four_chips", "devices": n_model,
           "shards_per_device": per_device,
           "recall_at_10": spmd_recall,
           "single_device_recall_at_10": single_recall,
           "compile_s": (t1 - t0) - run_s, "run_s": run_s}
    _check(out, spmd_recall >= RECALL_FLOOR
           and abs(spmd_recall - single_recall) <= FOUR_CHIP_RECALL_GAP)
    return out


def _check(out: dict, ok: bool) -> None:
    out["ok"] = bool(ok)
    if not ok:
        raise AssertionError(f"phase failed its checks: {json.dumps(out)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD path on a four-chip host")
    args = ap.parse_args(argv)

    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    x, queries, truth = make_corpus(N_VECTORS, N_QUERIES, args.seed)
    index, info = build_phase(x, deep_config(args.seed),
                              workers=os.cpu_count() or 1)
    print(json.dumps(info), flush=True)
    if args.four_chips:
        phases = [lambda: four_chip_phase(index, queries, truth)]
    else:
        phases = [
            lambda: engine_phase(index, queries, truth, quantize=False),
            lambda: engine_phase(index, queries, truth, quantize=True),
            lambda: fused_phase(index, queries, truth),
        ]
    for phase in phases:
        print(json.dumps(phase()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
