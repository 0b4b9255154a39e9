"""Index-building launcher (the paper's GraphConstructor, Sec. IV-A).

Builds with the parallel constructor (``repro.build``) and publishes a
versioned, checksummed store (``repro.store``) — the paper's "construct
in parallel across the cluster, persist to HDFS" flow:

PYTHONPATH=src python -m repro.launch.build_index \\
    --n 20000 --d 32 --metric l2 --shards 8 --workers 4 \\
    --out /tmp/pyramid_store

Serving then recovers from the store (``ServingEngine.from_store``) or
hot-swaps onto a fresh publish (``Brokers.replace_index(name, path)``).

``save_index`` / ``load_index`` remain as *deprecated* shims over the
store (``load_index`` still reads seed-era ``index.pkl`` pickles); new
code should use :class:`repro.store.IndexStore` directly.
"""
from __future__ import annotations

import argparse
import os
import pickle
import time
import warnings
from typing import Optional

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.config import PyramidConfig
from repro.core.meta_index import PyramidIndex
from repro.data.synthetic import clustered_vectors, norm_spread_vectors
from repro.obs import get_logger

log = get_logger(__name__)


def save_index(index: PyramidIndex, path: str) -> None:
    """Deprecated: publish a store version at ``path`` instead.

    Kept for source compatibility with the seed-era pickle API; now
    delegates to :meth:`repro.store.IndexStore.publish` (atomic,
    checksummed, versioned — no pickle is written). A legacy
    ``index.pkl`` in the same directory is moved aside so the old
    save/load round-trip cannot return the stale pickle."""
    warnings.warn(
        "save_index is deprecated: use "
        "repro.store.IndexStore(path).publish(index)",
        DeprecationWarning, stacklevel=2)
    from repro.store import IndexStore
    IndexStore(path).publish(index)
    pkl = os.path.join(path, "index.pkl")
    if os.path.exists(pkl):   # superseded by the publish above
        os.replace(pkl, pkl + ".migrated")


def load_index(path: str, *, version: Optional[str] = None) -> PyramidIndex:
    """Open the index at ``path``: a store root (latest published
    version + delta-log replay) or a legacy ``index.pkl`` pickle
    (deprecated migration path). A published store version always wins
    over a leftover pickle — it is the newer artifact."""
    from repro.store import IndexStore
    store = IndexStore(path)
    pkl = os.path.join(path, "index.pkl")
    # an explicit version request can never be served by the unversioned
    # pickle — fall through to the store, which raises if it's absent
    if version is None and os.path.exists(pkl) and not store.exists():
        warnings.warn(
            "loading a legacy pickle index; re-publish it with "
            "repro.store.IndexStore(path).publish(load_index(path)) — "
            "pickle support will be removed",
            DeprecationWarning, stacklevel=2)
        with open(pkl, "rb") as f:
            return pickle.load(f)
    return store.load(version=version)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--metric", default="l2",
                    choices=["l2", "ip", "angular"])
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--meta-size", type=int, default=256)
    ap.add_argument("--replication-r", type=int, default=0)
    ap.add_argument("--workers", type=int, default=None,
                    help="sub-HNSW build processes (default: "
                         "min(shards, cpu_count); 0 = sequential)")
    ap.add_argument("--data", default=None,
                    help=".npy file with the dataset (default: synthetic)")
    ap.add_argument("--out", default="/tmp/pyramid_store",
                    help="store root (a version is published under it)")
    ap.add_argument("--gc-keep", type=int, default=None,
                    help="after publishing, GC superseded versions "
                         "keeping this many")
    ap.add_argument("--quantize", action="store_true",
                    help="print the frozen int8 quantization grid. "
                         "(Every publish persists the grid in the "
                         "manifest, so ServingEngine.from_store(path, "
                         "quantize=True) always reopens without "
                         "re-deriving params and delta replay "
                         "requantizes appends on the identical grid; "
                         "this flag only surfaces it.)")
    args = ap.parse_args()

    if args.data:
        x = np.load(args.data).astype(np.float32)
    elif args.metric == "ip":
        x = norm_spread_vectors(args.n, args.d, 64)
    else:
        x = clustered_vectors(args.n, args.d, 64)

    cfg = PyramidConfig(
        metric=args.metric, num_shards=args.shards,
        meta_size=args.meta_size, sample_size=min(len(x), 10_000),
        replication_r=args.replication_r or (300 if args.metric == "ip"
                                             else 0))
    from repro.build import build_pyramid_index_parallel
    from repro.store import IndexStore
    t0 = time.time()
    index = build_pyramid_index_parallel(
        x, cfg, workers=args.workers, verbose=True)
    t_build = time.time() - t0
    if args.quantize:
        qp = index.quant_params()   # publish persists this frozen grid
        log.info(f"quantization grid: d={qp.d}, int8 "
              f"(vector payload shrinks ~4x in quantize=True engines)")
    store = IndexStore(args.out)
    t0 = time.time()
    vid = store.publish(index, keep=args.gc_keep)
    log.info(f"index built in {t_build:.1f}s "
          f"(mode={index.build_stats['build_mode']}, "
          f"workers={index.build_stats['build_workers']}); "
          f"published {vid} to {args.out} in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
