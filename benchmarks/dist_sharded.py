"""Weak-scaling evidence for the sharded backend (BENCH_dist.json).

The scaling claim of the ``dist_sharded`` engine (DESIGN.md §5): a
graph partitioned over P shards streams ΔG batches at (near) the
per-batch cost of a single shard, while each shard holds only its own
rows plus the halo tables.  This suite grows the graph WITH the mesh —
``n = n0 * P`` at constant degree, so per-shard row mass stays fixed —
and records, per shard count:

  per_batch_us         fused-scan streaming cost per ΔG batch
  edges_per_sec        edge-lanes streamed through repair sweeps / sec
  bytes_per_shard      one shard's resident graph bytes (rows + halo)
  single_device_bytes  the jnp engine's footprint for the SAME graph
  mem_frac             bytes_per_shard / single_device_bytes
  per_batch_vs_1shard  per-batch cost normalised to the 1-shard row

The CI dist-smoke job runs ``--quick`` on 8 virtual host devices and
warn-gates ``per_batch_vs_1shard`` at 2x; the ISSUE 10 memory bar is
``mem_frac < 0.6`` on the 8-shard graph.

Shard counts above ``len(jax.devices())`` are skipped, so this file
must fix the device count BEFORE jax initialises — it does so when run
as a script; ``benchmarks/run.py --suite dist`` does the same on the
orchestrator path.

Usage:
  PYTHONPATH=src python benchmarks/dist_sharded.py [--quick]
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

if __name__ == "__main__":                   # before any jax import
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import numpy as np
import jax

from common import timeit, emit, write_json
from repro.graph import build_csr, random_updates
from repro.graph.csr import uniform_graph
from repro.algos import sssp

SHARD_COUNTS = (1, 2, 4, 8)


def _footprint(handle) -> int:
    return sum(np.asarray(leaf).nbytes
               for leaf in jax.tree_util.tree_leaves(handle))


def run(small=True, quick=False):
    from repro.core.engine import JnpEngine
    from repro.shard.engine import ShardedEngine

    ndev = len(jax.devices())
    counts = [p for p in SHARD_COUNTS if p <= ndev]
    if counts != list(SHARD_COUNTS):
        print(f"[bench] only {ndev} devices: weak-scaling rows limited "
              f"to P={counts} (set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8)", flush=True)
    n0 = 192 if quick else (512 if small else 2048)
    deg = 4 if quick else 8
    batch = 16
    base_pb = None
    for P in counts:
        n, edges, w = uniform_graph(n0 * P, deg, seed=1)
        keep = edges[:, 0] != edges[:, 1]
        csr = build_csr(n, edges[keep], w[keep])
        ups = random_updates(csr, percent=10, seed=7)
        nb = ups.num_batches(batch)
        lanes = csr.num_edges + max(2 * ups.num_adds, 16)
        cap = max(2 * ups.num_adds, 16)

        eng = ShardedEngine(num_shards=P)
        g0 = eng.prepare(csr, diff_capacity=cap)
        props0 = sssp.static_sssp(eng, g0, 0)

        def fused():
            return sssp.dyn_sssp_stream(eng, g0, 0, ups, batch,
                                        props=props0,
                                        segment_size=nb)[1]["dist"]

        t = timeit(fused, iters=1 if quick else 2)
        per_batch = t / nb
        if base_pb is None:
            base_pb = per_batch
        bps = eng.per_shard_bytes(g0)
        single = _footprint(JnpEngine().prepare(csr, diff_capacity=cap))
        emit(f"dist/weak/P{P}", t,
             f"per_batch_us={per_batch:.1f};"
             f"per_batch_vs_1shard={per_batch / max(base_pb, 1e-9):.2f};"
             f"edges_per_sec={lanes * nb / (t / 1e6):.0f};"
             f"bytes_per_shard={bps};single_device_bytes={single};"
             f"mem_frac={bps / max(single, 1):.3f};"
             f"n={n};num_batches={nb};shards={P}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny graphs, one timing iteration")
    ap.add_argument("--full", action="store_true",
                    help="bench-scale graphs")
    args = ap.parse_args()
    from common import reset_results
    reset_results()
    run(small=not args.full, quick=args.quick)
    write_json("dist", meta={"small": not args.full,
                             "quick": bool(args.quick)})
