"""Fused Pallas repair kernels for the ΔG hot path (DESIGN.md §3).

Two kernels replace the per-op chains in the Pallas backend:

``fused_relax_rows`` — one launch does the whole SSSP repair step that
previously took three (rowmin → hit → rowargmin):

  grid = (R // block,)
  in:  gathered (K, block) tile of vals[ell_src]ᵀ (the gather runs in
       XLA before the launch), ell_wᵀ / ell_srcᵀ (K, block) tiles, and
       the reduction identity as an SMEM scalar
  out: row_min  (R,)   min_k vals[src] + w        per row
       row_arg  (R,)   min_k {src | cand == row_min}  (deterministic)
       rows     (R,)   in-tile compacted ids of frontier rows
                       (row_min < identity), sentinel R past each
                       tile's frontier — ready for a scatter without
                       re-scanning R rows

``fused_spmv_rows`` is the same fusion for sum-combining sweeps
(PageRank): row sums + compacted materialized-row frontier.

TPU layout (what Mosaic accepts): the data-dependent gather
``vals[ell_src]`` cannot run inside a TPU kernel ("Cannot do int
indexing on TPU"), and a resident (n+1,) property vector would not fit
VMEM at graph scale, so the wrappers gather in XLA and hand the kernel
TRANSPOSED (K, R) tiles.  Rows then sit on the 128 lanes, the per-row
reductions run over the K sublanes, and every output is a lane-dense
(1, block) block of a (1, R) array.  In-tile compaction is a prefix
count on the MXU (a triangular 0/1 matmul, exact in f32) followed by a
one-hot select reduced over sublanes.

Interpret mode follows the platform (``platform.interpret_mode``):
compiled on a TPU, the Pallas interpreter everywhere else.  The ``interpret=``
keyword exists only so compile checks can force ``False`` on a host
without a chip.

Block sizes come from a tiny autotuner keyed on (N, E_cap, K) and
cached per handle shape: the heuristic picks the largest row block
that divides the ELL row count (tile granularity vs. grid overhead),
and ``measure=True`` (benchmarks) times the candidates instead.
"""
from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ell import ell_capacity
from repro.kernels.platform import interpret_mode

ROW_TILE = 128


def fold_rows(x: jax.Array, op) -> jax.Array:
    """Combine a (K, T) tile over its K slot rows in a fixed left-to-right
    order, keeping the (1, T) row layout — float sums are then identical
    on every platform and in interpret mode."""
    acc = x[0:1, :]
    for k in range(1, x.shape[0]):
        acc = op(acc, x[k:k + 1, :])
    return acc


def row_argmin(cand: jax.Array, src: jax.Array, target: jax.Array,
               n: int) -> jax.Array:
    """Smallest source among the slots whose candidate hits ``target``
    (sentinel n when none does)."""
    return fold_rows(jnp.where(cand == target, src, n), jnp.minimum)


def row_call(kernel, K: int, R: int, bt: int, n_lead: int, outs,
             interpret, *args, smem: int = 0):
    """Launch ``kernel`` over (K, R) slot tiles: ``smem`` leading SMEM
    scalars, ``n_lead`` (K, R) tiles, then (1, R) row vectors; every
    output is a (1, R) row vector of the given dtypes."""
    tile = pl.BlockSpec((K, bt), lambda i: (0, i))
    row = pl.BlockSpec((1, bt), lambda i: (0, i))
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    n_rows = len(args) - smem - n_lead
    return pl.pallas_call(
        kernel,
        grid=(R // bt,),
        in_specs=[scalar] * smem + [tile] * n_lead + [row] * n_rows,
        out_specs=[row] * len(outs),
        out_shape=[jax.ShapeDtypeStruct((1, R), d) for d in outs],
        interpret=interpret_mode(interpret),
    )(*args)


# ---------------------------------------------------------------------------
# fused relax: relax → row min/argmin → frontier flag → compact, one launch
# ---------------------------------------------------------------------------

def _compact(hit: jax.Array, base, bt: int, R: int) -> jax.Array:
    """(1, bt) row ids of the tile's ``hit`` rows packed to its prefix,
    sentinel R after the last one."""
    h = jnp.broadcast_to(hit.astype(jnp.float32), (8, bt)).T   # (bt, 8)
    i0 = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    i1 = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    # inclusive prefix count of hits, as a column: lower-triangular 0/1
    # matmul (integers ≤ bt accumulate exactly in f32)
    incl = jnp.dot((i1 <= i0).astype(jnp.float32), h,
                   preferred_element_type=jnp.float32)[:, 0:1]
    slot = incl.astype(jnp.int32) - 1                          # (bt, 1)
    land = (h[:, 0:1] > 0) & (slot == i1)     # row i lands in slot j
    packed = jnp.sum(jnp.where(land, i0, 0), axis=0, keepdims=True)
    count = incl[bt - 1:bt, :].astype(jnp.int32)               # (1, 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    return jnp.where(j < count, base + packed, R)


def _fused_relax_kernel(ident_ref, gv_ref, w_ref, src_ref, min_ref,
                        arg_ref, rows_ref, *, n, bt, R):
    cand = gv_ref[...] + w_ref[...]        # (K, bt) relax
    rmin = fold_rows(cand, jnp.minimum)
    min_ref[...] = rmin
    # deterministic per-row parent: smallest src achieving the row min
    arg_ref[...] = row_argmin(cand, src_ref[...], rmin, n)
    # frontier flag: the row improved on the identity
    hit = rmin < ident_ref[0]
    rows_ref[...] = _compact(hit, pl.program_id(0) * bt, bt, R)


def _fused_spmv_kernel(gv_ref, r2d_ref, sum_ref, rows_ref, *, n, bt, R):
    sum_ref[...] = fold_rows(gv_ref[...], jnp.add)
    hit = r2d_ref[...] < n                 # materialized row for some vertex
    rows_ref[...] = _compact(hit, pl.program_id(0) * bt, bt, R)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_relax_rows(ell_src, ell_w, vals, *, block=ROW_TILE,
                     interpret=None):
    """(row_min, row_arg, compacted frontier rows); ``vals`` is (n+1,)
    with the reduction identity at slot n."""
    R, K = ell_src.shape
    bt = block if R % block == 0 else ROW_TILE
    n = vals.shape[0] - 1
    mins, args, rows = row_call(
        functools.partial(_fused_relax_kernel, n=n, bt=bt, R=R),
        K, R, bt, 3, (vals.dtype, ell_src.dtype, jnp.int32), interpret,
        vals[n:], vals[ell_src.T], ell_w.T, ell_src.T, smem=1)
    return mins[0], args[0], rows[0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_spmv_rows(ell_src, row2dst, vals, *, block=ROW_TILE,
                    interpret=None):
    """(row_sum, compacted materialized rows)."""
    R, K = ell_src.shape
    bt = block if R % block == 0 else ROW_TILE
    n = vals.shape[0] - 1
    sums, rows = row_call(
        functools.partial(_fused_spmv_kernel, n=n, bt=bt, R=R),
        K, R, bt, 1, (vals.dtype, jnp.int32), interpret,
        vals[ell_src.T], row2dst[None, :])
    return sums[0], rows[0]


# ---------------------------------------------------------------------------
# autotuner: row block keyed on (N, E_cap, K), cached per handle shape
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RepairConfig:
    row_block: int      # fused relax/spmv row tile (divides R)


_TUNE_CACHE: dict = {}
# Pool workers race bind-time tuning for one shape; under measure=True a
# duplicate tuning is not just wasted compiles but a nondeterministic
# winner (timing noise picks the config).  The lock makes the first
# tuner authoritative.
_TUNE_LOCK = threading.Lock()
_ROW_CANDIDATES = (512, 256, 128)


def clear_tune_cache() -> None:
    with _TUNE_LOCK:
        _TUNE_CACHE.clear()


def repair_config(n: int, e_cap: int, k: int, *,
                  measure: bool = False) -> RepairConfig:
    """Block config for a handle shape; one tuning per (N, E_cap, K)."""
    key = (int(n), int(e_cap), int(k))
    with _TUNE_LOCK:
        cfg = _TUNE_CACHE.get(key)
        if cfg is None:
            cfg = _measure_config(*key) if measure else _heuristic_config(*key)
            _TUNE_CACHE[key] = cfg
    return cfg


def _heuristic_config(n: int, e_cap: int, k: int) -> RepairConfig:
    R = ell_capacity(n, e_cap, k)
    # largest candidate that divides R and leaves ≥ 2 grid steps (so the
    # pipeline has something to overlap); ROW_TILE always divides R.
    row = ROW_TILE
    for cand in _ROW_CANDIDATES:
        if R % cand == 0 and R // cand >= 2:
            row = cand
            break
    return RepairConfig(row_block=row)


def _measure_config(n: int, e_cap: int, k: int) -> RepairConfig:
    """Time the candidates on synthetic data of the keyed shape."""
    import numpy as np
    import timeit
    rng = np.random.default_rng(0)
    R = ell_capacity(n, e_cap, k)
    src = jnp.asarray(rng.integers(0, n + 1, (R, k)).astype(np.int32))
    w = jnp.asarray(rng.integers(1, 50, (R, k)).astype(np.int32))
    vals = jnp.asarray(
        np.concatenate([rng.integers(0, 1000, n), [2 ** 30]]).astype(np.int32))

    def time_row(bt):
        run = lambda: jax.block_until_ready(fused_relax_rows(
            src, w, vals, block=bt))
        run()                                        # compile
        return min(timeit.repeat(run, number=1, repeat=3))

    rows = [bt for bt in _ROW_CANDIDATES if R % bt == 0 and R // bt >= 1] \
        or [ROW_TILE]
    return RepairConfig(row_block=min(rows, key=time_row))
