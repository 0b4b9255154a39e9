"""The system under test, as the benchmark drives it.

This is the one module here that imports the program (``repro``): the
index builder and store, the serving engine behind ``PyramidClient``,
and the program's span tracer. The built index is cached under
``bench/.cache/index/<key>``, where ``key`` hashes the configuration's
sizes and its ``data`` and ``index`` sections, so only a checkout's
first run builds, and configurations that serve one index share it.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np


def index_key(config: dict) -> str:
    keys = ("n", "dim", "metric", "n_queries", "data", "index")
    blob = json.dumps({k: config[k] for k in keys}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def pyramid_config(config: dict):
    from repro.common.config import PyramidConfig
    ix = config["index"]
    return PyramidConfig(
        metric=config["metric"], num_shards=ix["num_shards"],
        meta_size=ix["meta_size"], sample_size=ix["sample_size"],
        branching_factor=ix["branching_factor"],
        max_degree=ix["max_degree"],
        max_degree_upper=ix["max_degree_upper"],
        ef_construction=ix["ef_construction"],
        ef_search=ix["ef_search"], seed=ix["build_seed"])


def load_or_build_index(config: dict, x: np.ndarray, cache_dir: Path,
                        workers: int):
    """``(index, built)``: the cached index if this checkout has one,
    else a fresh build with ``workers`` processes, stored for the next
    run (under a temporary name first, renamed when complete)."""
    from repro.store import IndexStore

    root = cache_dir / "index" / index_key(config)
    if not (root / "versions").is_dir():
        from repro.build import build_pyramid_index_parallel
        index = build_pyramid_index_parallel(
            x, pyramid_config(config), workers=workers)
        tmp = root.with_name(root.name + f".tmp{os.getpid()}")
        shutil.rmtree(tmp, ignore_errors=True)
        IndexStore(str(tmp)).publish(index)
        root.parent.mkdir(parents=True, exist_ok=True)
        os.replace(tmp, root)
        built = True
    else:
        built = False
    index = IndexStore(str(root)).load(attach_delta=False)
    return index, built


def shard_rows(index) -> list:
    return [int(g.n) for g in index.subs]


def make_tracer():
    from repro.obs import Tracer
    return Tracer(capacity=1 << 21)


def start_client(index, engine: dict, tracer=None, warm_timeout_s=600.0):
    """A ``PyramidClient`` over a fresh ``ServingEngine`` with the
    configuration's engine settings, returned once every executor has
    compiled and run its walk."""
    from repro.core.client import PyramidClient
    kw = dict(engine)
    replicas = kw.pop("replicas", 1)
    client = PyramidClient.from_index(index, replicas=replicas,
                                      tracer=tracer, **kw)
    deadline = time.monotonic() + warm_timeout_s
    while not all(e["warmed"]
                  for e in client.stats()["executors"].values()):
        if time.monotonic() > deadline:
            client.engine.shutdown()
            raise TimeoutError(f"executors not warm after "
                               f"{warm_timeout_s}s")
        time.sleep(0.02)
    return client


def stop_client(client) -> None:
    """Shut down the engine behind a ``PyramidClient``; a searcher put
    in its place (the control) has none."""
    engine = getattr(client, "engine", None)
    if engine is not None:
        engine.shutdown()


def engine_stats(client) -> dict:
    s = client.stats()
    keep = ("submitted_queries", "restarts", "redispatched",
            "hedged_queries", "expired_queries", "access_rate")
    return {k: s[k] for k in keep}
