"""FrontierEngine — work-efficient sparse-frontier sweeps.

The paper's CPU/GPU backends win on dynamic updates because their
worklists touch only the affected vertices per iteration.  Dense
TPU-style sweeps pay O(E) per fixed-point iteration regardless of
frontier size, which erases the dynamic-vs-static advantage on
small-diameter graphs (EXPERIMENTS.md §Reproduction).  This engine
restores work-efficiency with Ligra-style direction optimization:

  * the graph keeps a push-oriented row-split ELL
    (kernels/ell.pack_push_ell): active vertices map to their out-edge
    rows, each holding ≤ K destinations;
  * each fixed-point iteration reads |frontier| on the host (one small
    sync — the same host-driven loop the paper's OpenMP backend runs):
      - frontier > sparse_frac·R  →  dense sweep (inherited lowering);
      - else                       →  sparse step: gather the active
        rows (capacity = next pow2, so recompiles are O(log R)),
        compute candidates, and scatter-min/-max into the property —
        O(|frontier|·K + n) work instead of O(E);
  * sweeps opt in by declaring ``frontier`` (the boolean source-side
    property) on their EdgeSweep; everything else falls back to the
    dense lowering, so the full algorithm suite runs unchanged.

Semantics note: the scatter-min is the same re-associated combiner the
dense path uses — results are identical (tests/test_backends.py runs
this engine through the whole SSSP/PR/TC matrix).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import (JnpEngine, Collectives, Props,
    _StreamView, dyn_state, dyn_from_state)
from repro.core.ir import EdgeSweep
from repro.graph.csr import CSR, INT, INF_W
from repro.graph import diffcsr
from repro.graph.diffcsr import DynGraph
from repro.graph.updates import UpdateBatch
from repro.runtime import faults as _faults
from repro.kernels.ell import (Ell, ell_apply_add, ell_apply_del,
                               ell_state, ell_from_state)
from repro.kernels.ell import pack_push_ell as _pack_push_ell_raw
pack_push_ell = jax.jit(_pack_push_ell_raw, static_argnums=(1, 2))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FrontierHandle:
    g: DynGraph
    push: Ell


def _next_pow2(x: int) -> int:
    p = 16
    while p < x:
        p <<= 1
    return p


class _DenseStreamView(_StreamView):
    """Stream-scan facade for the FrontierEngine: identical semantics,
    but fixed points run JnpEngine's on-device while_loop (jit-safe,
    with its frontier switch) instead of the host-driven
    direction-optimized loop."""

    def fixed_point(self, h, sw: EdgeSweep, props: Props, cond_fn,
                    max_iter: int) -> Props:
        return JnpEngine.fixed_point(self._engine, h, sw, props, cond_fn,
                                     max_iter)


class FrontierEngine(JnpEngine):
    name = "frontier"

    def __init__(self, k: int = 8, sparse_frac: float = 0.05):
        super().__init__()
        self.k = k
        self.sparse_frac = sparse_frac
        self._jit_cache: Dict = {}
        # stable per-engine jitted repack (see PallasEngine): the
        # ell_apply_add cond branch binds a cached jaxpr per call
        # instead of re-tracing the whole push pack
        self._repack = jax.jit(functools.partial(_pack_push_ell_raw, k=k))

    # -- construction / updates (repack after structural change) -----------
    def prepare(self, csr: CSR, diff_capacity: int) -> FrontierHandle:
        g = super().prepare(csr, diff_capacity)
        return FrontierHandle(g=g, push=pack_push_ell(g, self.k))

    def merge(self, h: FrontierHandle) -> FrontierHandle:
        g = diffcsr.merge(h.g)
        return FrontierHandle(g=g, push=pack_push_ell(g, self.k))

    def out_degrees(self, h: FrontierHandle) -> jax.Array:
        return h.g.out_degrees()

    # -- durable state -----------------------------------------------------
    # Like PallasEngine, the push pack travels RAW so resume keeps the
    # exact slot layout (and hence summation order) of the saved run.
    state_kind = "frontier"

    def pack_state(self, h: FrontierHandle):
        return ({"g": dyn_state(h.g), "push": ell_state(h.push)},
                {"kind": "frontier", "n": h.g.n, "k": self.k})

    def unpack_state(self, tree, meta) -> FrontierHandle:
        if meta["k"] != self.k:
            raise ValueError(
                f"checkpoint was saved with k={meta['k']} lanes per row; "
                f"this engine has k={self.k} — bind the restoring engine "
                f"with the same k (or restore cross-backend)")
        self._n = meta["n"]
        return FrontierHandle(g=dyn_from_state(tree["g"], meta["n"]),
                              push=ell_from_state(tree["push"], meta["n"]))

    def update_del(self, h: FrontierHandle, batch: UpdateBatch):
        g = super().update_del(h.g, batch)
        push = ell_apply_del(h.push, h.g, batch.del_src, batch.del_dst,
                             batch.del_mask)
        return FrontierHandle(g=g, push=push)

    def update_add(self, h: FrontierHandle, batch: UpdateBatch):
        g = super().update_add(h.g, batch)
        # push layout: slots hold DESTINATIONS
        push = ell_apply_add(h.push, h.g, g, batch.add_src, batch.add_dst,
                             batch.add_w, batch.add_mask,
                             slot_value=batch.add_dst,
                             repack=self._repack)
        return FrontierHandle(g=g, push=push)

    def batch_edge_flags(self, h: FrontierHandle, qs, qd, mask):
        return super().batch_edge_flags(h.g, qs, qd, mask)

    def count_wedges(self, h: FrontierHandle, pair_fn, lane_flags,
                     out_example, bounds=None):
        return super().count_wedges(h.g, pair_fn, lane_flags, out_example,
                                    bounds=bounds)

    def vertex_map(self, h: FrontierHandle, fn, props):
        return fn(props)

    # -- streaming executor hooks ------------------------------------------
    def handle_graph(self, h: FrontierHandle) -> DynGraph:
        return h.g

    def grow(self, h: FrontierHandle, factor: float = 2.0) -> FrontierHandle:
        g = JnpEngine.grow(self, h.g, factor)
        return FrontierHandle(g=g, push=pack_push_ell(g, self.k))

    def compact_handle(self, h: FrontierHandle) -> FrontierHandle:
        g = JnpEngine._compact(h.g)
        return FrontierHandle(g=g, push=pack_push_ell(g, self.k))

    def stream_view(self, bounds=None):
        # the direction-optimized fixed point reads |frontier| on the
        # host per iteration — inside the fused scan we must stay on
        # device, so stream steps get the dense while_loop lowering.
        return _DenseStreamView(self, bounds)

    def sweep(self, h, sw: EdgeSweep, props: Props) -> Props:
        g = h.g if isinstance(h, FrontierHandle) else h
        return super()._run_sweep(g, sw, props)

    def _run_sweep(self, h, sw: EdgeSweep, props: Props) -> Props:
        g = h.g if isinstance(h, FrontierHandle) else h
        return super()._run_sweep(g, sw, props)

    # -- sparse push step ----------------------------------------------------
    def _sparse_step(self, handle, sw: EdgeSweep, props: Props,
                     frontier_mask, cap: int) -> Props:
        """One frontier-compacted iteration of a min-combining sweep."""
        push = handle.push
        n = self.n_pad
        K = push.K
        row_src = push.row2dst                      # (R,) row's SOURCE
        # rows owned by active vertices
        src_clip = jnp.minimum(row_src, n - 1)
        row_active = (row_src < n) & frontier_mask[src_clip]
        rows = jnp.nonzero(row_active, size=cap, fill_value=push.R)[0]
        safe = jnp.minimum(rows, push.R - 1)
        srcs = jnp.where(rows < push.R, row_src[safe], n)   # (cap,)
        dsts = push.ell_src[safe]                           # (cap, K)
        ws = push.ell_w[safe]

        (target, red), = [(t, r) for t, r in sw.reduces.items()
                          if r.kind in ("min", "max")]
        vec_fn, use_w = sw.gather_form[target]
        vec = vec_fn(props)                                 # (n,) source vals
        vec1 = jnp.concatenate([vec, jnp.full((1,), red.identity(vec.dtype),
                                              vec.dtype)])
        cand = vec1[jnp.minimum(srcs, n)][:, None]
        if use_w:
            cand = cand + ws
        valid = (dsts < n) & (srcs < n)[:, None]
        ident = red.identity(cand.dtype)
        cand = jnp.where(valid, cand, ident)
        tgt = jnp.where(valid, dsts, n)

        old = props[target]
        buf = jnp.full((n + 1,), ident, old.dtype)
        if red.kind == "min":
            buf = buf.at[tgt.reshape(-1)].min(cand.reshape(-1))
        else:
            buf = buf.at[tgt.reshape(-1)].max(cand.reshape(-1))
        reduced = {target: buf[:n]}
        hit = {target: buf[:n] != ident}

        # argmin ride: smallest source id achieving the reduced value
        for t2, r2 in sw.reduces.items():
            if r2.kind != "argmin":
                continue
            ach = valid & (cand == reduced[r2.of][jnp.minimum(dsts, n - 1)])
            sid = jnp.where(ach, jnp.broadcast_to(srcs[:, None], ach.shape),
                            n)
            abuf = jnp.full((n + 1,), n, INT) \
                .at[tgt.reshape(-1)].min(sid.reshape(-1).astype(INT))
            reduced[t2] = abuf[:n]
            hit[t2] = hit[r2.of]
        return sw.post_fn(props, reduced, hit)

    def _sparse_capable(self, sw: EdgeSweep) -> bool:
        if sw.frontier is None or sw.gather_form is None:
            return False
        kinds = sorted(r.kind for r in sw.reduces.values())
        return kinds in (["min"], ["argmin", "min"], ["max"])

    # -- direction-optimized fixed point --------------------------------------
    def fixed_point(self, h, sw: EdgeSweep, props: Props,
                    cond_fn: Callable, max_iter: int) -> Props:
        if not self._sparse_capable(sw):
            return super().fixed_point(h, sw, props, cond_fn, max_iter)
        col = Collectives()
        n = self.n_pad
        R = h.push.R
        # cache key on the sweep's CODE objects: algorithms rebuild their
        # EdgeSweep per call, but the factory's closures share code
        swkey = (sw.edge_fn.__code__, sw.post_fn.__code__,
                 tuple(sorted((t, r.kind, r.of)
                              for t, r in sw.reduces.items())),
                 sw.frontier, n)

        def sparse_jitted(cap):
            key = (swkey, cap)
            fn = self._jit_cache.get(key)
            if fn is None:
                fn = jax.jit(lambda hh, p, m: self._sparse_step(
                    hh, sw, p, m, cap))
                self._jit_cache[key] = fn
            return fn

        DENSE_CHUNK = 8
        it = 0
        while it < max_iter:
            if not bool(cond_fn(props, jnp.asarray(it, INT), col)):
                break
            fmask = props[sw.frontier]
            # active out-edge rows (one scalar sync per direction check —
            # the same host-driven loop the paper's OpenMP backend runs)
            f_rows = int(jnp.sum(
                fmask[jnp.minimum(h.push.row2dst, n - 1)]
                & (h.push.row2dst < n)))
            if f_rows > self.sparse_frac * R:
                # big frontier: run a fused dense while_loop chunk, then
                # re-check direction (Ligra's dense mode)
                props = super().fixed_point(
                    h, sw, props, cond_fn,
                    max_iter=min(DENSE_CHUNK, max_iter - it))
                it += DENSE_CHUNK
            else:
                cap = _next_pow2(max(f_rows, 1))
                _faults.fire("kernel_launch", engine=self.name,
                             op="sparse_step", cap=cap)
                props = sparse_jitted(cap)(h, props, fmask)
                it += 1
        return props
