"""Pallas TPU kernels for the paper's hot loop: edge relaxation over ELL.

Three kernels, all the same shape discipline as the fused ones in
``pallas_repair`` (gather in XLA, transposed (K, R) slot tiles, rows on
lanes, lane-dense (1, R) outputs):

  grid = (R // ROW_TILE,)
  in:  vals[ell_src]ᵀ (K, ROW_TILE) tile   — gathered in XLA
       ell_wᵀ / ell_srcᵀ (K, ROW_TILE) tiles
  out: (1, ROW_TILE) per-row combined value

``relax_rowmin``   : out[r] = min_k  vals[src[r,k]] + w[r,k]   (SSSP)
``spmv_rowsum``    : out[r] = sum_k  vals[src[r,k]]            (PageRank)
``relax_rowargmin``: out[r] = min_k  {src | vals[src]+w == target[row2dst]}
                     (deterministic parent selection for SSSP)

The MXU plays no role here (no contractions); these are VPU kernels.
Cross-row combination back to vertices is a cheap segment reduction
outside the kernel (rows ≪ edges after packing).

Hardware alignment: ROW_TILE=128 rows fill the lane width; K defaults
to 8, one int32 sublane tile, so a (K, 128) tile is 4 KiB per operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pallas_repair import (ROW_TILE, fold_rows, row_argmin,
                                         row_call)


def _rowmin_kernel(gv_ref, w_ref, out_ref):
    out_ref[...] = fold_rows(gv_ref[...] + w_ref[...], jnp.minimum)


def _rowsum_kernel(gv_ref, out_ref):
    out_ref[...] = fold_rows(gv_ref[...], jnp.add)


def _rowargmin_kernel(gv_ref, w_ref, src_ref, tgt_ref, out_ref, *, n):
    out_ref[...] = row_argmin(gv_ref[...] + w_ref[...], src_ref[...],
                              tgt_ref[...], n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def relax_rowmin(ell_src, ell_w, vals, *, interpret=None):
    """vals: (n+1,) int32 with identity INF_W at slot n."""
    R, K = ell_src.shape
    (out,) = row_call(_rowmin_kernel, K, R, ROW_TILE, 2, (vals.dtype,),
                      interpret, vals[ell_src.T], ell_w.T)
    return out[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_rowsum(ell_src, vals, *, interpret=None):
    """vals: (n+1,) float32 with 0.0 at slot n."""
    R, K = ell_src.shape
    (out,) = row_call(_rowsum_kernel, K, R, ROW_TILE, 1, (vals.dtype,),
                      interpret, vals[ell_src.T])
    return out[0]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def relax_rowargmin(ell_src, ell_w, vals, row_targets, *, n,
                    interpret=None):
    """row_targets: (R,) the already-combined per-row target value."""
    R, K = ell_src.shape
    (out,) = row_call(functools.partial(_rowargmin_kernel, n=n), K, R,
                      ROW_TILE, 3, (ell_src.dtype,), interpret,
                      vals[ell_src.T], ell_w.T, ell_src.T,
                      row_targets[None, :])
    return out[0]
