"""diff-CSR: the paper's dynamic graph representation, TPU-adapted.

Paper semantics (§3.5):
  * deletions tombstone the CSR ``coordinates`` slot (sentinel ∞);
  * additions first re-use a vacant slot, else go to a secondary
    *diff-CSR* (own offsets/coords/weights sized by the update batch);
  * after a configurable number of batches the chain is merged back
    into a clean CSR.

TPU adaptation (XLA needs static shapes; scatter-atomics become masks):
  * the main region keeps its allocation forever; a tombstone is an
    ``alive=False`` bit rather than an in-place ∞ write, which *preserves
    row sortedness* and therefore O(log deg) edge membership — a strict
    improvement over the paper's sentinel (recorded in DESIGN.md §2);
  * "vacant-slot reuse" becomes *revival*: re-adding a previously deleted
    edge flips its alive bit in place (same slot, no data movement);
  * the diff region is a fixed-capacity sorted edge pool with its own
    offsets, rebuilt per batch (cheap: capacity == max batch adds);
  * capacity overflow cannot raise inside jit, so it increments an
    ``overflow`` counter that the host checks between batches and
    responds to with ``merge()`` — the paper's merge policy, made
    explicit and fault-tolerant.

Everything here is pure-functional and jit-compatible; ``merge`` is the
one host-side (numpy) op, reserved for *capacity growth*.  Routine
maintenance stays on device: ``update_csr_add`` keeps the diff pool
sorted with an O(B log D) sorted-merge insert (no full-pool re-sort),
and ``compact`` reclaims tombstoned diff slots under jit without
changing shapes (DESIGN.md §2/§3).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graph.csr import CSR, INT, build_csr, row_searchsorted

BOOL = jnp.bool_


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DynGraph:
    """Dynamic graph = main CSR region + diff region, both static-shape."""

    # --- main region (rows sorted by dst within each src row) ---
    offsets: jax.Array      # (n+1,) int32
    src: jax.Array          # (E,) int32
    dst: jax.Array          # (E,) int32
    w: jax.Array            # (E,) int32
    alive: jax.Array        # (E,) bool
    # --- diff region (globally sorted by (src,dst); empty slots src=n) ---
    d_offsets: jax.Array    # (n+1,) int32
    d_src: jax.Array        # (D,) int32
    d_dst: jax.Array        # (D,) int32
    d_w: jax.Array          # (D,) int32
    d_alive: jax.Array      # (D,) bool
    # --- bookkeeping ---
    overflow: jax.Array     # () int32 — adds dropped for lack of capacity
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def main_capacity(self) -> int:
        return int(self.src.shape[0])

    @property
    def diff_capacity(self) -> int:
        return int(self.d_src.shape[0])

    # Flat edge view used by every ``forall (e in g.edges)`` lowering.
    def edge_arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        esrc = jnp.concatenate([self.src, jnp.minimum(self.d_src, self.n - 1)])
        edst = jnp.concatenate([self.dst, self.d_dst])
        ew = jnp.concatenate([self.w, self.d_w])
        ealive = jnp.concatenate([self.alive,
                                  self.d_alive & (self.d_src < self.n)])
        return esrc, edst, ew, ealive

    def out_degrees(self) -> jax.Array:
        esrc, _, _, ealive = self.edge_arrays()
        return jax.ops.segment_sum(ealive.astype(INT), esrc,
                                   num_segments=self.n)


def from_csr(csr: CSR, diff_capacity: int) -> DynGraph:
    d = max(int(diff_capacity), 1)
    n = csr.n
    e = csr.num_edges
    if e == 0:
        # keep ≥1 (dead) lane so gathers stay well-formed on empty graphs
        return DynGraph(
            offsets=csr.offsets,
            src=jnp.zeros((1,), INT), dst=jnp.zeros((1,), INT),
            w=jnp.ones((1,), INT), alive=jnp.zeros((1,), BOOL),
            d_offsets=jnp.zeros((n + 1,), INT),
            d_src=jnp.full((d,), n, INT), d_dst=jnp.zeros((d,), INT),
            d_w=jnp.zeros((d,), INT), d_alive=jnp.zeros((d,), BOOL),
            overflow=jnp.zeros((), INT), n=n)
    return DynGraph(
        offsets=csr.offsets, src=csr.src, dst=csr.dst, w=csr.w,
        alive=jnp.ones((csr.num_edges,), dtype=BOOL),
        d_offsets=jnp.zeros((n + 1,), dtype=INT),
        d_src=jnp.full((d,), n, dtype=INT),
        d_dst=jnp.zeros((d,), dtype=INT),
        d_w=jnp.zeros((d,), dtype=INT),
        d_alive=jnp.zeros((d,), dtype=BOOL),
        overflow=jnp.zeros((), dtype=INT),
        n=n,
    )


# ---------------------------------------------------------------------------
# Lookup helpers
# ---------------------------------------------------------------------------

def _locate_main(g: DynGraph, qs: jax.Array, qd: jax.Array):
    """(pos, found) of (qs->qd) in the main region, ignoring alive bit."""
    lo = g.offsets[qs]
    hi = g.offsets[qs + 1]
    pos = row_searchsorted(g.dst, lo, hi, qd)
    safe = jnp.clip(pos, 0, g.main_capacity - 1) if g.main_capacity else pos
    found = (pos < hi) & (g.dst[safe] == qd) if g.main_capacity else jnp.zeros_like(qs, BOOL)
    return safe, found


def _locate_diff(g: DynGraph, qs: jax.Array, qd: jax.Array):
    lo = g.d_offsets[qs]
    hi = g.d_offsets[qs + 1]
    pos = row_searchsorted(g.d_dst, lo, hi, qd)
    safe = jnp.clip(pos, 0, g.diff_capacity - 1) if g.diff_capacity else pos
    found = (pos < hi) & (g.d_dst[safe] == qd) if g.diff_capacity else jnp.zeros_like(qs, BOOL)
    return safe, found


def update_lanes(g: DynGraph, qs, qd, mask):
    """(lane, active) of batch edges in the E+D lane space of ``g`` —
    the addressing used to patch ELL packs in place."""
    E, D = g.main_capacity, g.diff_capacity
    p1, f1 = _locate_main(g, qs, qd)
    p2, f2 = _locate_diff(g, qs, qd)
    in_main = f1 & mask
    in_diff = f2 & mask & ~f1
    lane = jnp.where(in_main, p1, jnp.where(in_diff, E + p2, E + D))
    return lane, in_main | in_diff


def _pair_searchsorted(a_src: jax.Array, a_dst: jax.Array,
                       q_src: jax.Array, q_dst: jax.Array,
                       iters: int) -> jax.Array:
    """Branchless lexicographic searchsorted: for each query pair, the
    first index i with (a_src[i], a_dst[i]) >= (q_src, q_dst).  The key
    arrays must be sorted by (src, dst); avoids int64 combined keys."""
    lo = jnp.zeros(q_src.shape, INT)
    hi = jnp.full(q_src.shape, a_src.shape[0], INT)
    cap = max(int(a_src.shape[0]) - 1, 0)

    def body(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = (lo + hi) // 2
        safe = jnp.clip(mid, 0, cap)
        ms, md = a_src[safe], a_dst[safe]
        pred = (ms < q_src) | ((ms == q_src) & (md < q_dst))
        lo = jnp.where(active & pred, mid + 1, lo)
        hi = jnp.where(active & ~pred, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def _log2_iters(length: int) -> int:
    it = 1
    while (1 << it) < length + 1:
        it += 1
    return it + 1


def is_edge(g: DynGraph, qs: jax.Array, qd: jax.Array) -> jax.Array:
    """Vectorized alive-edge membership (u->v). qs/qd any broadcastable shape."""
    qs = jnp.asarray(qs, INT)
    qd = jnp.asarray(qd, INT)
    p1, f1 = _locate_main(g, qs, qd)
    p2, f2 = _locate_diff(g, qs, qd)
    return (f1 & g.alive[p1]) | (f2 & g.d_alive[p2])


def edge_weight(g: DynGraph, qs: jax.Array, qd: jax.Array) -> jax.Array:
    """Weight of alive edge u->v, or INF_W//1 semantics left to caller."""
    from repro.graph.csr import INF_W
    p1, f1 = _locate_main(g, qs, qd)
    p2, f2 = _locate_diff(g, qs, qd)
    w = jnp.full_like(qs, INF_W)
    w = jnp.where(f2 & g.d_alive[p2], g.d_w[p2], w)
    w = jnp.where(f1 & g.alive[p1], g.w[p1], w)
    return w


# ---------------------------------------------------------------------------
# updateCSRDel — tombstone deletions (paper §3.5)
# ---------------------------------------------------------------------------

def update_csr_del(g: DynGraph, del_src: jax.Array, del_dst: jax.Array,
                   mask: jax.Array | None = None) -> DynGraph:
    del_src = jnp.asarray(del_src, INT)
    del_dst = jnp.asarray(del_dst, INT)
    if mask is None:
        mask = jnp.ones(del_src.shape, BOOL)
    p1, f1 = _locate_main(g, del_src, del_dst)
    p2, f2 = _locate_diff(g, del_src, del_dst)
    kill1 = f1 & mask
    kill2 = f2 & mask & ~f1
    # Scatter False into alive bits via OOB-drop: masked-out lanes aim past
    # the end of the array and are dropped.  Duplicates are idempotent.
    E, D = g.main_capacity, g.diff_capacity
    alive = g.alive.at[jnp.where(kill1, p1, E)].set(False, mode="drop")
    d_alive = g.d_alive.at[jnp.where(kill2, p2, D)].set(False, mode="drop")
    return dataclasses.replace(g, alive=alive, d_alive=d_alive)


# ---------------------------------------------------------------------------
# updateCSRAdd — revive vacant slots, overflow into diff-CSR (paper §3.5)
# ---------------------------------------------------------------------------

def update_csr_add(g: DynGraph, add_src: jax.Array, add_dst: jax.Array,
                   add_w: jax.Array | None = None,
                   mask: jax.Array | None = None) -> DynGraph:
    add_src = jnp.asarray(add_src, INT)
    add_dst = jnp.asarray(add_dst, INT)
    if add_w is None:
        add_w = jnp.ones(add_src.shape, INT)
    if mask is None:
        mask = jnp.ones(add_src.shape, BOOL)

    E, D = g.main_capacity, g.diff_capacity

    # 1) revive / update in the main region (vacant-slot reuse).
    p1, f1 = _locate_main(g, add_src, add_dst)
    rev1 = f1 & mask
    idx1 = jnp.where(rev1, p1, E)
    alive = g.alive.at[idx1].set(True, mode="drop")
    w = g.w.at[idx1].set(add_w, mode="drop")

    # 2) revive / update in the diff region.
    p2, f2 = _locate_diff(g, add_src, add_dst)
    rev2 = f2 & mask & ~f1
    idx2 = jnp.where(rev2, p2, D)
    d_alive = g.d_alive.at[idx2].set(True, mode="drop")
    d_w = g.d_w.at[idx2].set(add_w, mode="drop")

    # 3) append the rest to the diff pool (OOB-drop on overflow).
    fresh = mask & ~f1 & ~f2
    # de-duplicate repeated fresh edges within the batch: sort by (src,dst),
    # keep only the first fresh lane of each key group.
    B = add_src.shape[0]
    order = jnp.lexsort((add_dst, add_src))
    s_src, s_dst, s_w = add_src[order], add_dst[order], add_w[order]
    s_fresh = fresh[order]
    first = jnp.concatenate([
        jnp.ones((1,), BOOL),
        (s_src[1:] != s_src[:-1]) | (s_dst[1:] != s_dst[:-1])])
    grp = jnp.cumsum(first.astype(INT)) - 1
    idx = jnp.arange(B, dtype=INT)
    first_fresh = jax.ops.segment_min(
        jnp.where(s_fresh, idx, jnp.asarray(B, INT)), grp, num_segments=B)
    s_fresh = s_fresh & (idx == first_fresh[grp])

    d = g.diff_capacity
    used = jnp.sum((g.d_src < g.n).astype(INT))
    # Sorted-merge insert (replaces the old full-pool lexsort): the pool
    # is already sorted by (src, dst) with vacant rows (src = n) sunk to
    # the end, and the admitted fresh edges are sorted within the batch —
    # so every row's post-merge position is its own rank plus the count
    # of keys from the other sorted list below it.  O((B + D)·log) gather
    # rounds, no O(D log D) re-sort of the pool.
    fresh_rank = jnp.cumsum(s_fresh.astype(INT)) - 1
    fits = s_fresh & (used + fresh_rank < d)
    overflow = g.overflow + jnp.sum((s_fresh & ~fits).astype(INT))
    if d:
        # compact the admitted fresh edges into a sorted (B,)-padded list
        f_src = jnp.full((B,), g.n, INT)
        f_dst = jnp.zeros((B,), INT)
        ftgt = jnp.where(fits, fresh_rank, B)
        f_src = f_src.at[ftgt].set(s_src, mode="drop")
        f_dst = f_dst.at[ftgt].set(s_dst, mode="drop")
        # merged position of each existing pool row / each admitted
        # edge.  Fresh edges are never equal to a materialized pool
        # key (they would have been revivals), so ties cannot occur.
        cnt_f = _pair_searchsorted(f_src, f_dst, g.d_src, g.d_dst,
                                   _log2_iters(B))
        cnt_p = _pair_searchsorted(g.d_src, g.d_dst, s_src, s_dst,
                                   _log2_iters(d))
        pool_rows = (g.d_src < g.n)
        pool_pos = jnp.where(pool_rows,
                             jnp.arange(d, dtype=INT) + cnt_f, d)
        fresh_pos = jnp.where(fits, fresh_rank + cnt_p, d)
        d_src = jnp.full((d,), g.n, INT).at[pool_pos].set(
            g.d_src, mode="drop").at[fresh_pos].set(s_src, mode="drop")
        d_dst = jnp.zeros((d,), INT).at[pool_pos].set(
            g.d_dst, mode="drop").at[fresh_pos].set(s_dst, mode="drop")
        d_wn = jnp.zeros((d,), INT).at[pool_pos].set(
            d_w, mode="drop").at[fresh_pos].set(s_w, mode="drop")
        d_al = jnp.zeros((d,), BOOL).at[pool_pos].set(
            d_alive, mode="drop").at[fresh_pos].set(True, mode="drop")
        d_offsets = jnp.searchsorted(d_src, jnp.arange(g.n + 1, dtype=INT),
                                     side="left").astype(INT)
    else:
        d_src, d_dst, d_wn, d_al, d_offsets = (g.d_src, g.d_dst, d_w,
                                               d_alive, g.d_offsets)
    return dataclasses.replace(
        g, alive=alive, w=w, d_src=d_src, d_dst=d_dst, d_w=d_wn,
        d_alive=d_al, d_offsets=d_offsets, overflow=overflow)


# ---------------------------------------------------------------------------
# compact — on-device reclamation of tombstoned diff slots (jit-compatible)
# ---------------------------------------------------------------------------

def compact(g: DynGraph) -> DynGraph:
    """Drop dead diff-pool rows in place, keeping shapes static.

    A tombstoned diff edge (materialized but ``d_alive == False``) holds a
    pool slot it no longer needs.  This stable left-compaction of the
    alive rows reclaims those slots without leaving jit — the routine
    merge of the paper's merge policy.  Host-side :func:`merge` remains
    only for capacity growth (true overflow).  Row order is preserved, so
    the pool stays sorted by (src, dst) and ``d_offsets`` stays exact.
    """
    d = g.diff_capacity
    if not d:
        return g
    keep = g.d_alive & (g.d_src < g.n)
    pos = jnp.cumsum(keep.astype(INT)) - 1
    tgt = jnp.where(keep, pos, d)
    d_src = jnp.full((d,), g.n, INT).at[tgt].set(g.d_src, mode="drop")
    d_dst = jnp.zeros((d,), INT).at[tgt].set(g.d_dst, mode="drop")
    d_w = jnp.zeros((d,), INT).at[tgt].set(g.d_w, mode="drop")
    d_alive = jnp.zeros((d,), BOOL).at[tgt].set(True, mode="drop")
    d_offsets = jnp.searchsorted(d_src, jnp.arange(g.n + 1, dtype=INT),
                                 side="left").astype(INT)
    return dataclasses.replace(g, d_src=d_src, d_dst=d_dst, d_w=d_w,
                               d_alive=d_alive, d_offsets=d_offsets)


def pool_counters(g: DynGraph) -> jax.Array:
    """(overflow, used, dead) int32 triple — the merge-pressure counters
    the streaming executor reads once per stream segment."""
    used = jnp.sum((g.d_src < g.n).astype(INT))
    dead = jnp.sum(((g.d_src < g.n) & ~g.d_alive).astype(INT))
    return jnp.stack([g.overflow, used, dead])


# ---------------------------------------------------------------------------
# merge — compaction of the diff chain back into a clean CSR (host-side)
# ---------------------------------------------------------------------------

def merge(g: DynGraph, diff_capacity: int | None = None,
          slack: float = 0.0) -> DynGraph:
    """Rebuild a clean CSR out of all alive edges (paper's periodic merge).

    Host-side numpy: this is the one shape-changing operation, so it sits
    at a jit boundary exactly like the paper's merge sits between batches.
    """
    esrc, edst, ew, ealive = (np.asarray(x) for x in g.edge_arrays())
    keep = ealive
    edges = np.stack([esrc[keep], edst[keep]], axis=1)
    csr = build_csr(g.n, edges, ew[keep], dedupe=True)
    if diff_capacity is None:
        diff_capacity = max(g.diff_capacity, 1)
    cap = int(diff_capacity * (1.0 + slack)) or 1
    return from_csr(csr, cap)
