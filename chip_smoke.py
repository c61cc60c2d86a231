"""Drive the DSL → session → repair path once on a TPU and check it.

    python chip_smoke.py [--seed 0] [--scale 20]     # one chip
    python chip_smoke.py --chips 4                   # the sharded path

One process, one JAX client.  It refuses to run anywhere but a TPU,
and says why on stderr.  ``--rehearse`` lets it run on the CPU at a
tiny scale to exercise the control flow; a rehearsal never prints a
result and always exits non-zero.

Phases (one line each: wall time, compile time, peak device bytes):

  a  Graph500-shaped RMAT graph (initiator 0.57/0.19/0.19/0.05, edge
     factor 16) and a 0.1% ΔG stream, half inserts and half deletes,
     with the numpy references on the post-update edge set.
  b  sssp.sp / pagerank.sp bound on ``jnp`` and ``pallas`` through
     ``repro.compile`` → ``bind`` → ``Session.run``, plus one armed
     ``pallas`` session fed batches through ``apply``.  SSSP is
     bit-exact, PageRank within rtol 5e-2 and atol 1e-2/n.
  c  tc.sp on a small symmetric RMAT graph against ``tc_oracle``.
  d  a ``SessionPool`` of mixed armed/structural tenants with forced
     evictions, on ``jnp`` and ``pallas``, every tenant oracle-exact.

``--chips 4`` instead runs DynSSSP on ``dist`` and ``dist_sharded``
(4 shards, both partitioners) over the same graph and stream, against
``jnp`` on device 0 and the numpy reference.  It prints each device's
resident bytes while the sharded state is held, and fails unless the
most loaded device holds at most ``SPREAD`` times the least.

Every session is bound with failover off and must report the backend
it was bound to and zero failovers.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

import repro  # noqa: E402
from repro.algos import oracles  # noqa: E402
from repro.core.engine import state_to_csr  # noqa: E402
from repro.dsl_programs import path as program_path  # noqa: E402
from repro.graph import build_csr, random_updates  # noqa: E402
from repro.graph.csr import rmat_graph  # noqa: E402
from repro.graph.updates import UpdateStream  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import SessionPool  # noqa: E402

GRAPH500 = dict(a=0.57, b=0.19, c=0.19)     # d = 0.05
EDGE_FACTOR = 16
TARGET_SCALE = 22
# Scale 22 overruns one 1200 s smoke run on a TPU v5e: there one DynSSSP
# pass over its 8-batch stream took 382 s on jnp and 760 s on pallas.
# At scale 20 the stream has 2 batches over a quarter of the edges.
SCALE = 20
UPDATE_PERCENT = 0.1
BATCH = 4096
ARMED_BATCHES = 3
SPREAD = 1.5     # most / fewest resident bytes per device, sharded state
SRC = 0
PR_ARGS = dict(beta=1e-4, delta=0.85, maxIter=100)

_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _mem(dev=None) -> dict:
    stats = (dev or jax.devices()[0]).memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


class Phase:
    """Times one phase and prints its line; an exception propagates."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self

    def __exit__(self, kind, *_):
        if kind is None:
            print(f"[{self.name}] wall_s={time.perf_counter() - self.t0:.3f} "
                  f"compile_s={_compile_s[0] - self.c0:.3f} "
                  f"peak_bytes_in_use={_mem()['peak_bytes_in_use']}",
                  flush=True)


def check_session(sess, backend: str) -> None:
    h = sess.health
    assert h.backend == backend, (h.backend, backend)
    assert h.failovers == 0 and h.kernel_failures == 0, h.as_dict()


# ---------------------------------------------------------------------------
# a. graph, stream, references
# ---------------------------------------------------------------------------

def build_graph(scale: int, seed: int):
    n, edges, w = rmat_graph(scale, EDGE_FACTOR, seed=seed, **GRAPH500)
    generated = len(edges)
    keep = edges[:, 0] != edges[:, 1]
    csr = build_csr(n, edges[keep], w[keep])      # sorted, deduplicated
    del edges, w, keep
    ups = random_updates(csr, percent=UPDATE_PERCENT, seed=seed + 1)
    print(f"graph: rmat scale={scale} n={n} generated_edges={generated} "
          f"edges_after_dedupe_and_self_loops={csr.num_edges}; "
          f"stream: adds={ups.num_adds} dels={ups.num_dels} batch={BATCH} "
          f"batches={ups.num_batches(BATCH)}", flush=True)
    return n, csr, ups


def host_edges(csr):
    return (np.stack([np.asarray(csr.src), np.asarray(csr.dst)], 1)
            .astype(np.int64), np.asarray(csr.w))


def sssp_ref(n, e2, w2):
    return oracles.sssp_oracle(n, e2, w2, SRC)


def armed_batches(ups) -> int:
    return min(ARMED_BATCHES, ups.num_batches(BATCH))


class References:
    """The numpy references on the post-update edge sets, computed on
    host threads while the device runs the sessions."""

    def __init__(self, n, csr, ups, pagerank=True):
        self._pool = concurrent.futures.ThreadPoolExecutor(4)
        e, w = host_edges(csr)

        def after(stream):
            return oracles.edges_after_updates(n, e, w, stream.adds,
                                               stream.dels)

        post = self._pool.submit(after, ups)
        self.sssp = self._pool.submit(lambda: sssp_ref(n, *post.result()))
        if pagerank:
            self.pagerank = self._pool.submit(
                lambda: oracles.pagerank_oracle(
                    n, post.result()[0], beta=PR_ARGS["beta"],
                    delta=PR_ARGS["delta"], max_iter=PR_ARGS["maxIter"]))
            win = ups.window(BATCH, 0, armed_batches(ups))
            self.armed = self._pool.submit(
                lambda: sssp_ref(n, *after(win)))

    def close(self):
        self._pool.shutdown(cancel_futures=True)


def as_dist(d) -> np.ndarray:
    return np.minimum(np.asarray(d).astype(np.int64), oracles.INF)


# ---------------------------------------------------------------------------
# b. DSL sessions on jnp / pallas
# ---------------------------------------------------------------------------

def device_bytes() -> list:
    return [_mem(d)["bytes_in_use"] for d in jax.devices()]


def check_spread(resident: list) -> None:
    """Sharded state must sit on every device in near-equal parts, not
    pile up on device 0."""
    if None in resident:             # the CPU backend keeps no stats
        assert jax.devices()[0].platform != "tpu", resident
        return
    lo, hi = min(resident), max(resident)
    assert lo > 0 and hi <= SPREAD * lo, \
        f"resident bytes per device not spread: {resident}"
    print(f"  spread: most/fewest resident bytes per device = "
          f"{hi / lo:.3f} (limit {SPREAD})", flush=True)


def run_sssp(csr, ups, backend: str, **opts) -> np.ndarray:
    with Phase(" ".join([f"sssp/{backend}"] + [f"{k}={v}" for k, v in
                                               opts.items()])):
        base = device_bytes()
        sess = repro.compile(program_path("sssp")).bind(
            csr, backend=backend, capacity="auto", failover=False, **opts)
        res = sess.run("DynSSSP", updateBatch=ups, batchSize=BATCH, src=SRC)
        dist = as_dist(res.props.host("dist"))
        check_session(sess, backend)
        resident = [None if b is None else a - b
                    for a, b in zip(device_bytes(), base)]
        total = None if None in resident else sum(resident)
        print(f"  sssp/{backend} resident_bytes={total} "
              f"reachable={int((dist < oracles.INF).sum())}", flush=True)
        if len(resident) > 1:
            print("  resident bytes per device: " + " ".join(
                f"{d.id}:{r}" for d, r in zip(jax.devices(), resident)),
                flush=True)
            if opts.get("num_shards", 1) > 1:
                check_spread(resident)
        del sess, res            # never two graphs resident at once
        gc.collect()
    return dist


def run_pagerank(csr, ups, backend: str) -> np.ndarray:
    with Phase(f"pagerank/{backend}"):
        sess = repro.compile(program_path("pagerank")).bind(
            csr, backend=backend, capacity="auto", failover=False)
        res = sess.run("DynPR", updateBatch=ups, batchSize=BATCH, **PR_ARGS)
        pr = res.props.host("pageRank")
        check_session(sess, backend)
        del sess, res
        gc.collect()
    return pr


def run_armed(csr, ups, backend: str) -> np.ndarray:
    with Phase(f"armed sssp/{backend}"):
        sess = repro.compile(program_path("sssp")).bind(
            csr, backend=backend, capacity="auto", failover=False)
        sess.run("DynSSSP", src=SRC, batchSize=BATCH)     # prologue only
        for i in range(armed_batches(ups)):
            sess.apply(ups.batch(i, BATCH))
        got = as_dist(sess.props.host("dist"))
        check_session(sess, backend)
        del sess
        gc.collect()
    return got


def phase_sessions(n, csr, ups, refs: References) -> None:
    dists = {b: run_sssp(csr, ups, b) for b in ("jnp", "pallas")}
    for backend, dist in dists.items():
        np.testing.assert_array_equal(dist, refs.sssp.result(),
                                      err_msg=f"sssp/{backend} dist")
    print("  sssp dist bit-exact: jnp == pallas == numpy reference",
          flush=True)
    got = run_armed(csr, ups, "pallas")
    np.testing.assert_array_equal(got, refs.armed.result(),
                                  err_msg="armed pallas dist")
    print(f"  armed sssp/pallas: {armed_batches(ups)} batches through "
          f"apply, bit-exact", flush=True)
    prs = {b: run_pagerank(csr, ups, b) for b in ("jnp", "pallas")}
    pref = refs.pagerank.result()
    for backend, pr in prs.items():
        print(f"  pagerank/{backend} l1_to_reference="
              f"{float(np.abs(pr - pref).sum()):.6e} "
              f"max_abs={float(np.abs(pr - pref).max()):.6e}", flush=True)
        np.testing.assert_allclose(pr, pref, rtol=5e-2, atol=1e-2 / n,
                                   err_msg=f"pagerank/{backend}")


# ---------------------------------------------------------------------------
# c. triangle counting on a small symmetric graph
# ---------------------------------------------------------------------------

def symmetric_stream(csr, percent: float, seed: int) -> UpdateStream:
    """Both directions of every update, adjacent in the same batch."""
    ups = random_updates(csr, percent=percent, seed=seed)
    adds = np.stack([ups.adds, ups.adds[:, [1, 0, 2]]], 1).reshape(-1, 3)
    dels = np.stack([ups.dels, ups.dels[:, [1, 0]]], 1).reshape(-1, 2)
    return UpdateStream(adds=adds, dels=dels)


def phase_tc(seed: int, scale: int) -> None:
    n, edges, _ = rmat_graph(scale, 8, seed=seed, **GRAPH500)
    edges = edges[edges[:, 0] != edges[:, 1]]
    e, w = oracles.symmetrize(edges, np.ones(len(edges), np.int32))
    csr = build_csr(n, e, w)
    ups = symmetric_stream(csr, 5, seed + 2)
    e2, _ = oracles.edges_after_updates(n, *host_edges(csr), ups.adds,
                                        ups.dels)
    ref = oracles.tc_oracle(n, e2)
    print(f"  tc reduced: symmetric rmat scale={scale} n={n} "
          f"edges={csr.num_edges} (wedge enumeration costs edges x "
          f"max-degree lane passes, and tc_oracle is pure Python)",
          flush=True)
    for backend in ("jnp", "pallas"):
        sess = repro.compile(program_path("tc")).bind(
            csr, backend=backend, capacity="auto", failover=False)
        res = sess.run("DynTC", updateBatch=ups, batchSize=64)
        got = int(res.value)
        check_session(sess, backend)
        del sess, res
        gc.collect()
        assert got == ref, (backend, got, ref)
        print(f"  tc/{backend} triangles={got} == tc_oracle", flush=True)


# ---------------------------------------------------------------------------
# d. multi-tenant pool
# ---------------------------------------------------------------------------

def _alive_edges(sess):
    tree, meta = sess.engine.pack_state(sess.handle)
    c, _ = state_to_csr(jax.tree_util.tree_map(np.asarray, tree), meta)
    return dict(zip(zip(np.asarray(c.src).tolist(), np.asarray(c.dst)
                        .tolist()), np.asarray(c.w).tolist()))


def phase_pool(seed: int, backend: str, tenants=32, armed=8, ticks=3,
               batch=8, max_resident=24) -> None:
    n, edges, w = rmat_graph(9, 8, seed=seed, **GRAPH500)
    keep = edges[:, 0] != edges[:, 1]
    csr = build_csr(n, edges[keep], w[keep])
    e0, w0 = host_edges(csr)
    pool = SessionPool(repro.compile(program_path("sssp")), backend=backend,
                       max_resident=max_resident)
    streams = []
    for t in range(tenants):
        streams.append(random_updates(csr, percent=10 + 5 * (t % 5),
                                      seed=seed + 100 + t))
        sess = pool.bind(f"t{t}", csr)
        if t < armed:
            sess.run("DynSSSP", batchSize=batch, src=SRC)
    for i in range(ticks):
        pool.apply_many([(f"t{t}", s.batch(i % s.num_batches(batch), batch))
                         for t, s in enumerate(streams)])
    st = pool.stats()
    assert st["evictions"] > 0 and st["restores"] > 0, st
    for t, s in enumerate(streams):
        win = s.window(batch, 0, min(ticks, s.num_batches(batch)))
        e2, w2 = oracles.edges_after_updates(n, e0, w0, win.adds, win.dels)
        sess = pool.session(f"t{t}")
        check_session(sess, backend)
        want = dict(zip(map(tuple, e2.tolist()), w2.tolist()))
        assert _alive_edges(sess) == want, f"t{t}: edge set diverged"
        if t < armed:
            np.testing.assert_array_equal(
                as_dist(sess.props.host("dist")), sssp_ref(n, e2, w2),
                err_msg=f"pool/{backend} t{t} dist")
    print(f"  pool/{backend}: {tenants} tenants ({armed} armed) "
          f"applied={st['applied']} mega_calls={st['mega_calls']} "
          f"evictions={st['evictions']} restores={st['restores']}; "
          f"every tenant reference-exact", flush=True)
    del pool, sess
    gc.collect()


# ---------------------------------------------------------------------------
# --chips 4: the sharded path and what it is compared with
# ---------------------------------------------------------------------------

def phase_sharded(n, csr, ups, refs: References) -> None:
    want = run_sssp(csr, ups, "jnp")
    for backend, opts in (("dist", {"num_shards": 4}),
                          ("dist_sharded", {"num_shards": 4,
                                            "partitioner": "block"}),
                          ("dist_sharded", {"num_shards": 4,
                                            "partitioner": "degree"})):
        print(f"  {backend} {opts}", flush=True)
        got = run_sssp(csr, ups, backend, **opts)
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{backend} {opts} dist")
    np.testing.assert_array_equal(want, refs.sssp.result(),
                                  err_msg="jnp dist")
    print("  sharded dist bit-exact: dist == dist_sharded(block) == "
          "dist_sharded(degree) == jnp == numpy reference", flush=True)


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=None,
                    help=f"RMAT scale (default {SCALE}; 8 when rehearsing)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny scale; never a result")
    args = ap.parse_args()
    scale = args.scale or (8 if args.rehearse else SCALE)

    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        print(f"no TPU: jax.devices()[0].platform == {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    cache = "off (rehearsal)" if args.rehearse else enable_compile_cache()
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"compile cache: {cache}", flush=True)
    if scale != TARGET_SCALE:
        why = ("CPU rehearsal" if args.rehearse else
               "scale 22 runs DynSSSP for 382 s on jnp and 760 s on pallas "
               "on a v5e, past the smoke's 1200 s" if scale == SCALE else
               "requested")
        print(f"reduced: rmat scale {scale} instead of {TARGET_SCALE} "
              f"({why}); initiator, edge factor {EDGE_FACTOR} and the "
              f"{UPDATE_PERCENT}% half-insert/half-delete stream unchanged",
              flush=True)

    with Phase("a graph"):
        n, csr, ups = build_graph(scale, args.seed)
        refs = References(n, csr, ups, pagerank=args.chips == 1)
    try:
        if args.chips == 4:
            with Phase("sharded x4"):
                phase_sharded(n, csr, ups, refs)
        else:
            with Phase("b sessions"):
                phase_sessions(n, csr, ups, refs)
    finally:
        refs.close()
    del csr, ups, refs
    gc.collect()
    if args.chips == 1:
        with Phase("c tc"):
            phase_tc(args.seed, min(scale, 9))
        with Phase("d pool"):
            for backend in ("jnp", "pallas"):
                phase_pool(args.seed, backend)

    if args.rehearse:
        print("rehearsal complete: no chip, no result", file=sys.stderr)
        return 3
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
