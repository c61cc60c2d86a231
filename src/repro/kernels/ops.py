"""jit'd wrappers turning the ELL kernels into vertex-level graph ops."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.graph.csr import INF_W, INT
from repro.kernels.ell import Ell
from repro.kernels import csr_relax as K
from repro.kernels import pallas_repair as FK


def _combine_rows(row_vals, row2dst, n, kind, identity):
    seg = {"min": jax.ops.segment_min, "sum": jax.ops.segment_sum,
           "max": jax.ops.segment_max}[kind]
    dense = seg(row_vals, row2dst, num_segments=n + 1)
    return dense[:n]


def vertex_min_plus(ell: Ell, vals_n1: jax.Array):
    """out[v] = min over in-edges (u,v) of vals[u] + w(u,v); INF if none."""
    rows = K.relax_rowmin(ell.ell_src, ell.ell_w, vals_n1)
    return _combine_rows(rows, ell.row2dst, ell.n, "min",
                         jnp.asarray(INF_W, vals_n1.dtype))


def vertex_spmv(ell: Ell, vals_n1: jax.Array):
    """out[v] = sum over in-edges (u,v) of vals[u]  (PageRank pull)."""
    rows = K.spmv_rowsum(ell.ell_src, vals_n1)
    return _combine_rows(rows, ell.row2dst, ell.n, "sum",
                         jnp.zeros((), vals_n1.dtype))


def vertex_argmin_src(ell: Ell, vals_n1: jax.Array, vertex_min: jax.Array):
    """Smallest source achieving vertex_min[v] (deterministic parent)."""
    n = ell.n
    tgt_full = jnp.concatenate([vertex_min,
                                jnp.full((1,), INF_W, vertex_min.dtype)])
    row_tgt = tgt_full[jnp.minimum(ell.row2dst, n)]
    rows = K.relax_rowargmin(ell.ell_src, ell.ell_w, vals_n1, row_tgt, n=n)
    return _combine_rows(rows, ell.row2dst, ell.n, "min",
                         jnp.asarray(n, INT))


# ---------------------------------------------------------------------------
# fused repair path (kernels/pallas_repair.py): one launch per sweep
# ---------------------------------------------------------------------------

def _frontier_hit(ell: Ell, front_rows: jax.Array) -> jax.Array:
    """Scatter the in-kernel compacted frontier rows to a vertex mask —
    O(frontier) writes instead of a dense segment reduction over R."""
    n = ell.n
    safe = jnp.minimum(front_rows, ell.R - 1)
    dsts = jnp.where(front_rows < ell.R,
                     jnp.minimum(ell.row2dst[safe], n), n)
    return jnp.zeros((n + 1,), jnp.bool_).at[dsts].set(
        True, mode="drop")[:n]


def vertex_relax_fused(ell: Ell, vals_n1: jax.Array, *, block=None):
    """(vertex_min, parent, hit) from ONE fused relax launch.

    Bit-exact against the chained vertex_min_plus → hit →
    vertex_argmin_src composition: the vertex min is the min of row
    mins, the lexicographic argmin decomposes row-wise (rows not
    achieving the vertex min contribute the sentinel n), and a vertex
    is hit iff one of its rows improved on the identity."""
    n = ell.n
    row_min, row_arg, front_rows = FK.fused_relax_rows(
        ell.ell_src, ell.ell_w, vals_n1, block=block or FK.ROW_TILE)
    seg = jnp.minimum(ell.row2dst, n)
    vmin = jax.ops.segment_min(row_min, seg, num_segments=n + 1)[:n]
    tgt_full = jnp.concatenate([vmin,
                                jnp.full((1,), INF_W, vmin.dtype)])
    contrib = jnp.where(row_min == tgt_full[seg], row_arg,
                        jnp.asarray(n, row_arg.dtype))
    parent = jax.ops.segment_min(contrib, seg, num_segments=n + 1)[:n]
    return vmin, parent, _frontier_hit(ell, front_rows)


def vertex_spmv_fused(ell: Ell, vals_n1: jax.Array, *, block=None):
    """(vertex_sum, hit) from one fused SpMV launch; hit marks vertices
    owning a materialized ELL row (the chained path's segment_max)."""
    n = ell.n
    row_sum, front_rows = FK.fused_spmv_rows(
        ell.ell_src, ell.row2dst, vals_n1, block=block or FK.ROW_TILE)
    seg = jnp.minimum(ell.row2dst, n)
    vsum = jax.ops.segment_sum(row_sum, seg, num_segments=n + 1)[:n]
    return vsum, _frontier_hit(ell, front_rows)
