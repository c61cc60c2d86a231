"""The plain reference: numpy on the host, importing nothing of the program.

Copies of ``repro.algos.oracles`` (``edges_after_updates``,
``sssp_oracle``, ``pagerank_oracle``), kept here so that the yardstick
cannot move with the program.  ``dtype=`` runs the same arithmetic in a
lower precision, which is how the controls are made.
"""
from __future__ import annotations

import numpy as np

INF = np.int64(np.iinfo(np.int32).max // 2)


def edges_after_updates(edges: np.ndarray, weights: np.ndarray,
                        adds: np.ndarray, dels: np.ndarray):
    """Apply one net Δ to an edge set: deletes before inserts, the last
    weight wins among repeated keys, result sorted by (src, dst)."""
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


def sssp(n: int, edges: np.ndarray, weights: np.ndarray, source: int,
         dtype=np.int64) -> np.ndarray:
    """Bellman-Ford from ``source``; unreachable vertices read ``INF``.
    With a float ``dtype`` every sum is rounded to it; with a narrow
    integer one every sum saturates at its largest value, which then
    stands for unreachable."""
    integer = np.issubdtype(dtype, np.integer)
    inf = min(INF, np.iinfo(dtype).max) if integer else np.inf
    dist = np.full(n, inf, dtype=dtype)
    dist[source] = 0
    src, dst = edges[:, 0], edges[:, 1]
    w = weights.astype(np.int64 if integer else dtype)
    for _ in range(n):
        ds = dist[src]
        cand = np.where(ds < inf, np.minimum(ds + w, inf) if integer
                        else ds + w, inf).astype(dtype)
        nd = dist.copy()
        np.minimum.at(nd, dst, cand)
        if np.array_equal(nd, dist):
            break
        dist = nd
    out = dist.astype(np.float64)
    return np.where(out < inf, out, INF).astype(np.int64)


def pagerank(n: int, edges: np.ndarray, beta: float, delta: float,
             max_iter: int, dtype=np.float64) -> np.ndarray:
    """Pull power iteration from the uniform vector, stopped once the L1
    change is at most ``beta`` or after ``max_iter`` sweeps."""
    f = np.dtype(dtype).type
    pr = np.full(n, 1.0 / n, dtype=dtype)
    src, dst = edges[:, 0], edges[:, 1]
    outdeg = np.bincount(src, minlength=n)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0).astype(dtype)
    base, d = f((1.0 - delta) / n), f(delta)
    for _ in range(max_iter):
        acc = np.zeros(n, dtype=dtype)
        np.add.at(acc, dst, (pr[src] * inv[src]).astype(dtype))
        val = (base + d * acc).astype(dtype)
        diff = float(np.abs(val.astype(np.float64) - pr.astype(np.float64))
                     .sum())
        pr = val
        if diff <= beta:
            break
    return pr.astype(np.float64)
