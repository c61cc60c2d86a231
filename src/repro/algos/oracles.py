"""Pure-python/numpy oracles for the three paper algorithms.

These implement the textbook *static* algorithms from scratch; every
dynamic result must equal the oracle run on the post-update edge set
(the paper's own correctness criterion: dynamic == static-on-new-graph).
"""
from __future__ import annotations

from typing import List, Set

import numpy as np

INF = np.int64(np.iinfo(np.int32).max // 2)


def edges_after_updates(n: int, edges: np.ndarray, weights: np.ndarray,
                        adds: np.ndarray, dels: np.ndarray):
    """Apply Δ to an edge set host-side: deletes before adds, the last
    weight wins among duplicate base edges and among duplicate adds, and
    the result is sorted by (src, dst).  Vectorized over int64 pair keys
    so it runs at graph scale."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    weights = np.asarray(weights).reshape(-1)
    adds = np.asarray(adds).reshape(-1, 3)
    dels = np.asarray(dels, np.int64).reshape(-1, 2)
    pairs = np.concatenate([edges, adds[:, :2].astype(np.int64), dels])
    if not len(edges) + len(adds):
        return np.zeros((0, 2), np.int64), np.zeros((0,), np.int32)
    lo = pairs.min()
    span = pairs.max() - lo + 1

    def key(p):
        return (p[:, 0] - lo) * span + (p[:, 1] - lo)

    base = key(edges)
    kept = ~np.isin(base, key(dels))
    k = np.concatenate([base[kept], key(adds[:, :2].astype(np.int64))])
    w = np.concatenate([weights[kept], adds[:, 2]]).astype(np.int32)
    # np.unique keeps the first occurrence: reverse so the LAST write wins
    uk, first = np.unique(k[::-1], return_index=True)
    e = np.stack([uk // span + lo, uk % span + lo], axis=1)
    return e, w[::-1][first]


def sssp_oracle(n: int, edges: np.ndarray, weights: np.ndarray,
                source: int) -> np.ndarray:
    """Bellman-Ford (no negative weights here, so it converges)."""
    dist = np.full(n, INF, dtype=np.int64)
    dist[source] = 0
    src = edges[:, 0] if len(edges) else np.zeros(0, np.int64)
    dst = edges[:, 1] if len(edges) else np.zeros(0, np.int64)
    w = weights.astype(np.int64)
    for _ in range(n):
        cand = dist[src] + w
        nd = dist.copy()
        np.minimum.at(nd, dst, np.where(dist[src] < INF, cand, INF))
        if np.array_equal(nd, dist):
            break
        dist = nd
    return np.minimum(dist, INF)


def pagerank_oracle(n: int, edges: np.ndarray, beta: float = 1e-3,
                    delta: float = 0.85, max_iter: int = 100) -> np.ndarray:
    pr = np.full(n, 1.0 / n, dtype=np.float64)
    src = edges[:, 0] if len(edges) else np.zeros(0, np.int64)
    dst = edges[:, 1] if len(edges) else np.zeros(0, np.int64)
    outdeg = np.zeros(n, dtype=np.int64)
    np.add.at(outdeg, src, 1)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0)
    for _ in range(max_iter):
        acc = np.zeros(n, dtype=np.float64)
        np.add.at(acc, dst, pr[src] * inv[src])
        val = (1.0 - delta) / n + delta * acc
        diff = np.abs(val - pr).sum()
        pr = val
        if diff <= beta:
            break
    return pr


def tc_oracle(n: int, edges: np.ndarray) -> int:
    """Paper's node-iterator count on a symmetrized edge set."""
    nbrs: List[Set[int]] = [set() for _ in range(n)]
    eset = set(map(tuple, edges.tolist()))
    for u, v in edges.tolist():
        nbrs[u].add(v)
    count = 0
    for v in range(n):
        for u in nbrs[v]:
            if u >= v:
                continue
            for w in nbrs[v]:
                if w <= v:
                    continue
                if (u, w) in eset:
                    count += 1
    return count


def symmetrize(edges: np.ndarray, weights: np.ndarray):
    e2 = np.concatenate([edges, edges[:, ::-1]], axis=0)
    w2 = np.concatenate([weights, weights], axis=0)
    key = e2[:, 0] * (e2.max() + 1) + e2[:, 1]
    _, idx = np.unique(key, return_index=True)
    return e2[idx], w2[idx]
