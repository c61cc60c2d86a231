"""Static CSR graph representation + generators.

The paper (StarPlat) stores static graphs in CSR: ``offsets`` (n+1) and
``coordinates`` (E) plus ``weights`` for weighted graphs.  We keep the same
layout but additionally keep the explicit ``src`` array (sorted-COO view of
the same ordering), because every TPU lowering of ``forall (e in edges)``
is a segment reduction that wants both endpoints as flat vectors.

Rows are kept sorted by destination.  This is a deliberate deviation from
the paper's unsorted adjacencies: sorted rows give O(log deg) edge
membership via branchless binary search (see ``row_searchsorted``), which
the CUDA backend of the paper obtains only optionally ("binary search if
the neighbors are sorted").
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

INT = jnp.int32
# Weight used for missing/invalid lookups.
INF_W = np.int32(np.iinfo(np.int32).max // 2)


@dataclasses.dataclass(frozen=True)
class CSR:
    """Immutable static CSR (host-built, device arrays)."""

    n: int                      # static vertex count
    offsets: jax.Array          # (n+1,) int32, row starts
    src: jax.Array              # (E,) int32 sorted by (src, dst)
    dst: jax.Array              # (E,) int32
    w: jax.Array                # (E,) int32 edge weights

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


def build_csr(n: int, edges: np.ndarray, weights: np.ndarray | None = None,
              dedupe: bool = True) -> CSR:
    """Build a CSR from a (E, 2) int array of directed edges.

    Host-side (numpy): sorting and deduplication are one-off costs, the
    same way StarPlat's graph loader builds its CSR before processing.
    """
    edges = np.asarray(edges, dtype=np.int64)
    if edges.size == 0:
        edges = edges.reshape(0, 2)
    if weights is None:
        weights = np.ones((edges.shape[0],), dtype=np.int32)
    weights = np.asarray(weights, dtype=np.int32)
    key = edges[:, 0] * np.int64(n) + edges[:, 1]
    order = np.argsort(key, kind="stable")
    edges, weights, key = edges[order], weights[order], key[order]
    if dedupe and edges.shape[0]:
        keep = np.ones(edges.shape[0], dtype=bool)
        keep[1:] = key[1:] != key[:-1]
        edges, weights = edges[keep], weights[keep]
    src = edges[:, 0].astype(np.int32)
    dst = edges[:, 1].astype(np.int32)
    offsets = np.zeros(n + 1, dtype=np.int32)
    offsets[1:] = np.cumsum(np.bincount(src, minlength=n)[:n],
                            dtype=np.int32)
    return CSR(n=n, offsets=jnp.asarray(offsets), src=jnp.asarray(src),
               dst=jnp.asarray(dst), w=jnp.asarray(weights))


# ---------------------------------------------------------------------------
# Branchless per-row binary search (vectorized over queries).
# ---------------------------------------------------------------------------

def row_searchsorted(sorted_vals: jax.Array, lo: jax.Array, hi: jax.Array,
                     queries: jax.Array) -> jax.Array:
    """For each query q_i, first index in sorted_vals[lo_i:hi_i] >= q_i.

    Branchless binary search over *row slices* of one flat array — avoids
    int64 combined keys (XLA default int width) and keeps rows independent.
    ~32 gather rounds; fully vectorized over the query batch.
    """
    lo = lo.astype(INT)
    hi = hi.astype(INT)
    cap = max(int(sorted_vals.shape[0]) - 1, 0)
    # Enough iterations for any row length up to 2^31.
    def body(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = (lo + hi) // 2
        v = sorted_vals[jnp.clip(mid, 0, cap)] if cap or sorted_vals.shape[0] \
            else jnp.zeros_like(mid)
        pred = v < queries
        lo = jnp.where(active & pred, mid + 1, lo)
        hi = jnp.where(active & ~pred, mid, hi)
        return lo, hi
    lo, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
    return lo


# ---------------------------------------------------------------------------
# Generators (paper Table 1 mix: social/skew = RMAT, road = grid, uniform).
# ---------------------------------------------------------------------------

def rmat_graph(n_log2: int, avg_deg: int, seed: int = 0,
               a: float = 0.57, b: float = 0.19, c: float = 0.19,
               max_w: int = 100) -> Tuple[int, np.ndarray, np.ndarray]:
    """RMAT generator with the paper's SNAP parameters (a,b,c,d)."""
    rng = np.random.default_rng(seed)
    n = 1 << n_log2
    m = n * avg_deg
    srcs = np.zeros(m, dtype=np.int64)
    dsts = np.zeros(m, dtype=np.int64)
    r = np.empty(m)
    r2 = np.empty(m)
    for bit in range(n_log2):
        # quadrant probabilities: a (0,0), b (0,1), c (1,0), d (1,1)
        rng.random(out=r)
        src_bit = r >= a + b
        rng.random(out=r2)
        dst_bit = np.where(src_bit, r2 >= c / (1 - a - b), r2 >= a / (a + b))
        srcs <<= 1
        srcs |= src_bit
        dsts <<= 1
        dsts |= dst_bit
    edges = np.stack([srcs, dsts], axis=1)
    w = rng.integers(1, max_w, size=m).astype(np.int32)
    return n, edges, w


def uniform_graph(n: int, avg_deg: int, seed: int = 0, max_w: int = 100):
    rng = np.random.default_rng(seed)
    m = n * avg_deg
    edges = rng.integers(0, n, size=(m, 2)).astype(np.int64)
    w = rng.integers(1, max_w, size=m).astype(np.int32)
    return n, edges, w


def grid_graph(side: int, seed: int = 0, max_w: int = 100):
    """Road-network-like: 2D grid, degree ~4, large diameter (paper US/GR)."""
    rng = np.random.default_rng(seed)
    n = side * side
    idx = np.arange(n).reshape(side, side)
    e = []
    e.append(np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], 1))
    e.append(np.stack([idx[:, 1:].ravel(), idx[:, :-1].ravel()], 1))
    e.append(np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], 1))
    e.append(np.stack([idx[1:, :].ravel(), idx[:-1, :].ravel()], 1))
    edges = np.concatenate(e, axis=0).astype(np.int64)
    w = rng.integers(1, max_w, size=edges.shape[0]).astype(np.int32)
    return n, edges, w
