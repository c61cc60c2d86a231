"""Fused vs chained Pallas repair path (ISSUE 6 tentpole scorecard).

Races the two kernel regimes of ``PallasEngine`` on the paper's graph
mix and lands every row in ``BENCH_pallas.json``:

  relax    one fused launch over an XLA-side gather (relax → frontier
           flag → in-kernel compaction,
           ``kernels/pallas_repair.fused_relax_rows``) vs
           the chained rowmin → hit → rowargmin kernel chain
  spmv     fused SpMV+frontier launch vs the chained rowsum + segment_max
  e2e      dynamic SSSP end to end on the ``pallas`` vs the
           ``pallas_chained`` registry engines

Each row carries a *roofline-relative efficiency*: achieved bytes/s for
a coarse traffic model of the launch (the XLA gather and transposes
that feed the kernel, its (K, R) tile reads, outputs once — the
chained rows pay the gather per op) against ``roofline.HBM_BW``.  On the CPU interpret
backend these fractions are tiny by construction; the quantity exists so
the same JSON rows become meaningful when the suite runs on a real TPU,
and so PRs can still compare fused-vs-chained *ratios* on CPU.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from common import timeit, emit, bench_graphs
from roofline import HBM_BW
from repro.graph import build_csr, random_updates
from repro.graph.csr import INF_W
from repro.core.registry import make_engine
from repro.kernels import ops as kops
from repro.algos import sssp

_ITM = 4  # int32/float32 lanes throughout the repair path


def _relax_bytes(R, K, n, fused):
    """Coarse HBM traffic model (bytes) for one relax sweep.

    Every launch first gathers vals[ell_src] in XLA (ell_src and vals
    read, a (K, R) array written) and transposes each other ELL array
    it takes to (K, R) (read + write); the kernel then reads its (K, R)
    tiles once.
    fused:   one gather, ell_w and ell_src transposed, three tile reads,
             min/arg/rows out.
    chained: rowmin (gather, ell_w transposed, two tile reads, one row
             out), rowargmin (gather, both transposed, three tile reads,
             targets in, one row out), then the hit pass.
    """
    rk = R * K
    gather = 2 * rk + (n + 1)
    if fused:
        return _ITM * (gather + 2 * 2 * rk + 3 * rk + 3 * R)
    rowmin = gather + 2 * rk + 2 * rk + R
    rowargmin = gather + 2 * 2 * rk + 3 * rk + 2 * R
    return _ITM * (rowmin + rowargmin + 2 * R)


def _spmv_bytes(R, K, n, fused):
    """The same model for a SpMV sweep: one gather and one (K, R) tile
    read, row sums out; fused also reads row2dst and writes the
    compacted rows."""
    base = 2 * R * K + (n + 1) + R * K + R
    return _ITM * (base + (2 * R if fused else 0))


def _roofline(nbytes, us):
    gbps = nbytes / (us / 1e6) / 1e9
    return gbps, nbytes / (us / 1e6) / HBM_BW


def run(small=True, quick=False, percent=5, batch=16, iters=2):
    graphs = bench_graphs(small)
    if quick:
        graphs = {"uniform": graphs["uniform"]}
        iters = 1
    for gname, (n, edges, w) in graphs.items():
        keep = edges[:, 0] != edges[:, 1]
        csr = build_csr(n, edges[keep], w[keep])
        ups = random_updates(csr, percent=percent, seed=7)
        cap = max(2 * ups.num_adds, 16)

        eng = make_engine("pallas")
        h = eng.prepare(csr, diff_capacity=cap)
        ell = h.ell
        R, K = ell.ell_src.shape
        cfg = eng._config(h.g)

        rng = np.random.default_rng(1)
        dist = jnp.concatenate([
            jnp.asarray(rng.integers(0, 1000, n).astype(np.int32)),
            jnp.full((1,), INF_W, jnp.int32)])
        rank = jnp.concatenate([
            jnp.asarray(rng.random(n).astype(np.float32)),
            jnp.zeros((1,), jnp.float32)])

        # -- relax: one fused launch vs the per-op chain -------------------
        def relax_fused():
            return kops.vertex_relax_fused(ell, dist, block=cfg.row_block)

        def relax_chained():
            vmin = kops.vertex_min_plus(ell, dist)
            parent = kops.vertex_argmin_src(ell, dist, vmin)
            return vmin, parent, vmin < INF_W

        t_f = timeit(relax_fused, iters=iters)
        t_c = timeit(relax_chained, iters=iters)
        for mode, t in (("fused", t_f), ("chained", t_c)):
            nbytes = _relax_bytes(R, K, n, mode == "fused")
            gbps, frac = _roofline(nbytes, t)
            emit(f"pallas/relax/{gname}/{mode}", t,
                 f"fused_speedup={t_c / max(t_f, 1):.2f};"
                 f"model_bytes={nbytes};gbps={gbps:.3f};"
                 f"roofline_frac={frac:.2e};"
                 f"rows={R};lanes={K};row_block={cfg.row_block}")

        # -- spmv: fused launch vs rowsum + segment_max --------------------
        def spmv_fused():
            return kops.vertex_spmv_fused(ell, rank, block=cfg.row_block)

        def spmv_chained():
            return kops.vertex_spmv(ell, rank)

        t_f = timeit(spmv_fused, iters=iters)
        t_c = timeit(spmv_chained, iters=iters)
        for mode, t in (("fused", t_f), ("chained", t_c)):
            nbytes = _spmv_bytes(R, K, n, mode == "fused")
            gbps, frac = _roofline(nbytes, t)
            emit(f"pallas/spmv/{gname}/{mode}", t,
                 f"fused_speedup={t_c / max(t_f, 1):.2f};"
                 f"model_bytes={nbytes};gbps={gbps:.3f};"
                 f"roofline_frac={frac:.2e}")

        # -- end to end: the two registry engines race dynamic SSSP --------
        if quick:
            continue
        times = {}
        for ename in ("pallas", "pallas_chained"):
            e2 = make_engine(ename)
            g2 = e2.prepare(csr, diff_capacity=cap)
            props0 = sssp.static_sssp(e2, g2, 0)
            times[ename] = timeit(
                lambda e2=e2, g2=g2, props0=props0: sssp.dyn_sssp(
                    e2, g2, 0, ups, batch, props=props0)[1]["dist"],
                iters=iters)
        for ename, t in times.items():
            mode = "fused" if ename == "pallas" else "chained"
            emit(f"pallas/e2e_sssp/{gname}/{mode}", t,
                 f"fused_speedup="
                 f"{times['pallas_chained'] / max(times['pallas'], 1):.2f};"
                 f"num_updates={ups.num_adds + ups.num_dels}")


if __name__ == "__main__":
    run()
