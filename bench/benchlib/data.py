"""The corpus and the query set, made from a configuration's seeds.

Copies of ``clustered_vectors`` and ``query_set`` from the program's
``repro.data.synthetic``, kept here so that no later change to the
program can move the data the benchmark measures on.
"""
from __future__ import annotations

import numpy as np


def clustered_vectors(n: int, d: int, num_clusters: int, *,
                      spread: float = 0.15, seed: int = 0) -> np.ndarray:
    """DEEP/SIFT-like descriptors: Gaussian clusters of similar norm."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(num_clusters, d))
    asg = rng.integers(0, num_clusters, size=n)
    x = centers[asg] + spread * rng.normal(size=(n, d))
    return x.astype(np.float32)


def query_set(x: np.ndarray, num_queries: int, *, noise: float = 0.02,
              seed: int = 1) -> np.ndarray:
    """Queries drawn near indexed items."""
    rng = np.random.default_rng(seed)
    idx = rng.choice(x.shape[0], size=num_queries, replace=True)
    return (x[idx] + noise * rng.normal(size=(num_queries, x.shape[1]))
            ).astype(np.float32)


def make_corpus(config: dict):
    """``(vectors [n, d] f32, queries [n_queries, d] f32)`` of a
    configuration: its sizes, and its ``data`` section's generator
    parameters and seeds."""
    data = config["data"]
    x = clustered_vectors(config["n"], config["dim"], data["num_clusters"],
                          spread=data["spread"], seed=data["data_seed"])
    q = query_set(x, config["n_queries"], noise=data["query_noise"],
                  seed=data["query_seed"])
    return x, q
