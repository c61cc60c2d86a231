"""PallasEngine — the CUDA backend analogue: hot loops on TPU kernels.

Mirrors the paper's CUDA generator split: control flow stays on the
"host" (XLA program), the per-edge relaxation loop is a generated kernel.
Sweeps that declare a ``gather_form`` lower onto the row-split-ELL Pallas
kernels in ``repro.kernels``; everything else falls back to the JnpEngine
lowering (the paper, likewise, only kernelizes the forall bodies).

Two kernel regimes, selected by the ``fused`` flag:

  * fused (default) — the repair step runs ONE launch per sweep
    (``kernels/pallas_repair.fused_relax_rows``: relax → row min/argmin
    → frontier-flag → in-kernel compaction, over a gather done in XLA).
    Block sizes come from the (N, E_cap, K)-keyed autotuner, cached per
    handle shape.
  * chained (``fused=False``, registry name ``pallas_chained``) — the
    original per-op kernel chain (rowmin → hit → rowargmin), kept as
    the benchmark baseline for BENCH_pallas.json.

Both regimes are bit-exact against each other and the jnp lowering
(tests/test_kernels.py, tests/test_conformance_pallas.py).  ΔG merges into the
diff pool run on the jnp lowering (``diffcsr.update_csr_add``) in both.
Kernels compile on a TPU and run in the Pallas interpreter elsewhere
(``kernels.platform.interpret_mode``).

The ELL pack is rebuilt once per update batch and *reused across all
fixed-point iterations* — the analogue of the paper's CUDA optimization
of keeping the graph resident on the GPU across kernel launches (§5.3).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.core.ir import EdgeSweep
from repro.core.engine import (JnpEngine, Collectives, Props, dyn_state,
                               dyn_from_state)
from repro.graph.csr import CSR, INT, INF_W
from repro.graph import diffcsr
from repro.graph.diffcsr import DynGraph
from repro.graph.updates import UpdateBatch
from repro.kernels.ell import (Ell, ell_apply_add, ell_apply_del,
                               ell_state, ell_from_state)
from repro.kernels.ell import pack_ell as _pack_ell_raw
pack_ell = jax.jit(_pack_ell_raw, static_argnums=(1, 2))
from repro.kernels import ops as kops
from repro.kernels import pallas_repair as FK
from repro.runtime import faults as _faults


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PallasHandle:
    g: DynGraph
    ell: Ell



class PallasEngine(JnpEngine):
    name = "pallas"
    # every fixed-point iteration keeps the ELL kernel sweep
    frontier_switch = False

    def __init__(self, k: int = 8, fused: bool = True,
                 autotune: bool = False):
        super().__init__()
        self.k = k
        self.fused = fused
        self.autotune = autotune     # measure candidates vs. heuristic
        # stable per-engine jitted repack: ell_apply_add's cond branch
        # then hits jit's cache instead of re-tracing the pack per call
        self._repack = jax.jit(functools.partial(_pack_ell_raw, k=k))

    def _config(self, g: DynGraph) -> FK.RepairConfig:
        return FK.repair_config(
            g.n, g.main_capacity + g.diff_capacity, self.k,
            measure=self.autotune)

    # -- construction / updates --------------------------------------------
    # The ELL pack stays device-resident across batches: tombstones and
    # revivals patch their slots in place via lane2slot; only structural
    # diff-pool appends (which shift diff lane positions) trigger a
    # repack — and even that decision is a traced lax.cond, so the whole
    # update path runs inside the streaming executor's fused scan.
    def prepare(self, csr: CSR, diff_capacity: int) -> PallasHandle:
        g = super().prepare(csr, diff_capacity)
        return PallasHandle(g=g, ell=pack_ell(g, self.k))

    def merge(self, h: PallasHandle) -> PallasHandle:
        g = diffcsr.merge(h.g)
        return PallasHandle(g=g, ell=pack_ell(g, self.k))

    def out_degrees(self, h: PallasHandle) -> jax.Array:
        return h.g.out_degrees()

    # -- durable state -----------------------------------------------------
    # The Ell pack is saved RAW (not rebuilt on restore): repacking would
    # reassign slots, and float32 segment sums over the lanes depend on
    # slot order — saving the pack is what makes resume bit-exact.
    state_kind = "pallas"

    def pack_state(self, h: PallasHandle):
        return ({"g": dyn_state(h.g), "ell": ell_state(h.ell)},
                {"kind": "pallas", "n": h.g.n, "k": self.k})

    def unpack_state(self, tree, meta) -> PallasHandle:
        if meta["k"] != self.k:
            raise ValueError(
                f"checkpoint was saved with k={meta['k']} lanes per row; "
                f"this engine has k={self.k} — bind the restoring engine "
                f"with the same k (or restore cross-backend)")
        self._n = meta["n"]
        return PallasHandle(g=dyn_from_state(tree["g"], meta["n"]),
                            ell=ell_from_state(tree["ell"], meta["n"]))

    def update_del(self, h: PallasHandle, batch: UpdateBatch) -> PallasHandle:
        g = super().update_del(h.g, batch)
        ell = ell_apply_del(h.ell, h.g, batch.del_src, batch.del_dst,
                            batch.del_mask)
        return PallasHandle(g=g, ell=ell)

    def update_add(self, h: PallasHandle, batch: UpdateBatch) -> PallasHandle:
        # the ELL pack update below may repack on the device, so the
        # chaos seam sits here — ctx carries `fused` for targeting
        _faults.fire("kernel_launch", engine=self.name,
                     fused=self.fused, op="update_add")
        g = super().update_add(h.g, batch)
        # pull layout: slots hold SOURCES
        ell = ell_apply_add(h.ell, h.g, g, batch.add_src, batch.add_dst,
                            batch.add_w, batch.add_mask,
                            slot_value=batch.add_src,
                            repack=self._repack)
        return PallasHandle(g=g, ell=ell)

    def batch_edge_flags(self, h: PallasHandle, qs, qd, mask):
        return super().batch_edge_flags(h.g, qs, qd, mask)

    def count_wedges(self, h: PallasHandle, pair_fn, lane_flags, out_example,
                     bounds=None):
        return super().count_wedges(h.g, pair_fn, lane_flags, out_example,
                                    bounds=bounds)

    def vertex_map(self, h: PallasHandle, fn, props):
        return fn(props)

    # -- streaming executor hooks ------------------------------------------
    def handle_graph(self, h: PallasHandle) -> DynGraph:
        return h.g

    def grow(self, h: PallasHandle, factor: float = 2.0) -> PallasHandle:
        g = super().grow(h.g, factor)
        return PallasHandle(g=g, ell=pack_ell(g, self.k))

    def compact_handle(self, h: PallasHandle) -> PallasHandle:
        g = JnpEngine._compact(h.g)
        return PallasHandle(g=g, ell=pack_ell(g, self.k))

    # -- kernelized sweep ----------------------------------------------------
    def _kernel_compatible(self, sw: EdgeSweep) -> bool:
        if sw.gather_form is None:
            return False
        kinds = sorted(r.kind for r in sw.reduces.values())
        return kinds in (["min"], ["argmin", "min"], ["sum"])

    def _run_sweep(self, h, sw: EdgeSweep, props: Props) -> Props:
        if isinstance(h, DynGraph):  # fallback path re-entered with raw graph
            return super()._run_sweep(h, sw, props)
        if not self._kernel_compatible(sw):
            return super()._run_sweep(h.g, sw, props)
        _faults.fire("kernel_launch", engine=self.name, fused=self.fused,
                     op="sweep")
        if self.fused:
            return self._run_sweep_fused(h, sw, props)
        return self._run_sweep_chained(h, sw, props)

    def _run_sweep_fused(self, h: PallasHandle, sw: EdgeSweep,
                         props: Props) -> Props:
        """One fused launch per sweep: min/argmin/hit (or sum/hit) come
        out of a single kernel with in-kernel frontier compaction."""
        ell = h.ell
        cfg = self._config(h.g)
        reduced, hit, parents = {}, {}, {}
        for target, red in sw.reduces.items():
            if red.kind == "argmin":
                continue
            vec_fn, use_w = sw.gather_form[target]
            vec = vec_fn(props)
            ident = red.identity(vec.dtype)
            vals_n1 = jnp.concatenate([vec, jnp.full((1,), ident, vec.dtype)])
            if red.kind == "min":
                assert use_w
                vmin, parent, hv = kops.vertex_relax_fused(
                    ell, vals_n1, block=cfg.row_block)
                reduced[target], hit[target] = vmin, hv
                parents[target] = parent
            else:  # sum
                vsum, hv = kops.vertex_spmv_fused(
                    ell, vals_n1, block=cfg.row_block)
                reduced[target], hit[target] = vsum, hv
        for target, red in sw.reduces.items():
            if red.kind != "argmin":
                continue
            reduced[target] = parents[red.of]
            hit[target] = hit[red.of]
        return sw.post_fn(props, reduced, hit)

    def _run_sweep_chained(self, h: PallasHandle, sw: EdgeSweep,
                           props: Props) -> Props:
        """Per-op kernel chain (the pre-fusion lowering, benchmark
        baseline): rowmin → vertex combine → hit → rowargmin."""
        g, ell = h.g, h.ell
        n = self.n_pad
        reduced, hit = {}, {}
        # value reduce
        for target, red in sw.reduces.items():
            if red.kind == "argmin":
                continue
            vec_fn, use_w = sw.gather_form[target]
            vec = vec_fn(props)
            ident = red.identity(vec.dtype)
            vals_n1 = jnp.concatenate([vec, jnp.full((1,), ident, vec.dtype)])
            if red.kind == "min":
                assert use_w
                reduced[target] = kops.vertex_min_plus(ell, vals_n1)
                hit[target] = reduced[target] < ident
            else:  # sum
                r = kops.vertex_spmv(ell, vals_n1)
                reduced[target] = r
                hit[target] = jax.ops.segment_max(
                    (ell.row2dst < n).astype(INT),
                    jnp.minimum(ell.row2dst, n), num_segments=n + 1
                )[:n].astype(jnp.bool_)
        # arg reduce
        for target, red in sw.reduces.items():
            if red.kind != "argmin":
                continue
            of = red.of
            vec_fn, _ = sw.gather_form[of]
            vec = vec_fn(props)
            vals_n1 = jnp.concatenate(
                [vec, jnp.full((1,), INF_W, vec.dtype)])
            reduced[target] = kops.vertex_argmin_src(ell, vals_n1,
                                                     reduced[of])
            hit[target] = hit[of]
        return sw.post_fn(props, reduced, hit)


def PallasChainedEngine(**kw) -> PallasEngine:
    """Registry factory for the chained baseline (``pallas_chained``):
    the same engine with per-op kernel chains instead of fused launches
    — conformance keeps it honest, BENCH_pallas.json races it."""
    kw.setdefault("fused", False)
    return PallasEngine(**kw)
