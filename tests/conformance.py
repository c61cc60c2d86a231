"""Cross-backend conformance harness — the paper's Table-level evaluation
turned into an executable, seedable test matrix.

A :class:`Scenario` fixes (graph, update stream, batch size); the
``assert_*`` runners drive a compiled ``src/repro/dsl_programs/*.sp``
program through the public API (``repro.api.compile(...).bind(...)``)
on a chosen backend name and require a four-way agreement:

    api Session output   ==  deprecated Program.run shim (bit-exact)
                         ==  repro.algos.oracles (from-scratch numpy)
                         ==  hand-staged repro.algos.{sssp,pagerank,triangles}

Scenarios deliberately cover the degenerate shapes the paper's
evaluation never exercises: the empty graph, self-loops, duplicate
edges inside one batch, deletes of absent edges, delete-then-re-add
streams (same batch and across batches), and batch sizes 1 / 8 / 64.

Every future engine or kernel PR must keep this matrix green; to add an
algorithm, compile its ``.sp`` program, add an ``assert_<algo>`` runner
against its oracle, and register scenarios below (see ROADMAP.md).
Backends are addressed by registry name ('jnp' | 'dist' | 'pallas' |
'frontier'), so a newly registered engine joins the matrix by adding
its name to the lists in :func:`matrix`.

The matrix's test functions are built by :func:`matrix` and collected
from one ``test_conformance_<group>.py`` file per backend group, so
``pytest-xdist --dist loadfile`` spreads the groups over workers.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import numpy as np
import pytest

import repro.api as api
from repro.core.registry import make_engine
from repro.graph import build_csr, random_updates
from repro.graph.updates import UpdateStream
from repro.dsl_programs import path as program_path
from repro.algos import oracles
from repro.algos import sssp as hand_sssp
from repro.algos import pagerank as hand_pr
from repro.algos import triangles as hand_tc


def program(name: str) -> api.CompiledProgram:
    """Compile one of the shipped .sp programs (cached in api.compile)."""
    return api.compile(program_path(name))


def _shim_run(name: str, func: str, backend: str, csr, args, capacity):
    """The deprecated Program.run path, for the bit-exact cross-check."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return program(name).program.run(func, make_engine(backend), csr,
                                         args=args, diff_capacity=capacity)


@dataclasses.dataclass(frozen=True)
class Scenario:
    name: str
    n: int
    edges: np.ndarray          # canonical (deduped, sorted) base edge set
    w: np.ndarray
    stream: UpdateStream
    batch_size: int
    src: int = 0
    diff_capacity: int = 64


def _canonical(n, edges, w=None):
    """Dedup/sort through build_csr so scenario base == engine base."""
    csr = build_csr(n, edges, w)
    e = np.stack([np.asarray(csr.src), np.asarray(csr.dst)], 1) \
        .astype(np.int64)
    return csr, e, np.asarray(csr.w)


def _digraph(n, deg, seed, max_w=50, self_loops=False):
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, size=(n * deg, 2)).astype(np.int64)
    if not self_loops:
        edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.integers(1, max_w, size=edges.shape[0]).astype(np.int32)
    return _canonical(n, edges, w)


def _symgraph(n, m, seed):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2))
    e = e[e[:, 0] != e[:, 1]]
    e, w = oracles.symmetrize(e, np.ones(len(e), np.int32))
    return _canonical(n, e, w)


def _sym_pairs(rows):
    """[(u, v, w), ...] → adds array with both directions adjacent."""
    out = []
    for u, v, w in rows:
        out.append((u, v, w))
        out.append((v, u, w))
    return np.asarray(out, np.int32).reshape(-1, 3)


def _sym_del_pairs(rows):
    out = []
    for u, v in rows:
        out.append((u, v))
        out.append((v, u))
    return np.asarray(out, np.int32).reshape(-1, 2)


# ---------------------------------------------------------------------------
# Directed scenarios (SSSP, PageRank)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def digraph_scenario(name: str) -> Scenario:
    if name == "batch1":
        # every update is its own batch
        _, e, w = _digraph(n=20, deg=3, seed=5)
        ups = random_updates(build_csr(20, e, w), percent=12, seed=9)
        return Scenario(name, 20, e, w, ups, batch_size=1)
    if name == "batch8":
        _, e, w = _digraph(n=32, deg=4, seed=3)
        ups = random_updates(build_csr(32, e, w), percent=15, seed=2)
        return Scenario(name, 32, e, w, ups, batch_size=8)
    if name == "batch64":
        # the whole Δ lands in a single batch
        _, e, w = _digraph(n=32, deg=4, seed=7)
        ups = random_updates(build_csr(32, e, w), percent=25, seed=4)
        return Scenario(name, 32, e, w, ups, batch_size=64)
    if name == "empty":
        # no base edges at all; adds grow a graph; one del hits nothing
        n = 10
        e = np.zeros((0, 2), np.int64)
        w = np.zeros((0,), np.int32)
        adds = np.asarray([(0, 1, 3), (1, 2, 4), (2, 3, 1), (0, 3, 9),
                           (3, 4, 2), (4, 5, 7)], np.int32)
        dels = np.asarray([(5, 6)], np.int32)     # absent edge: no-op
        return Scenario(name, n, e, w, UpdateStream(adds=adds, dels=dels),
                        batch_size=4, diff_capacity=16)
    if name == "self_loops":
        _, e, w = _digraph(n=24, deg=3, seed=11, self_loops=True)
        assert (e[:, 0] == e[:, 1]).any(), "scenario needs self-loops"
        ups = random_updates(build_csr(24, e, w), percent=15, seed=6)
        return Scenario(name, 24, e, w, ups, batch_size=8)
    if name == "dup_in_batch":
        # the same add / del repeated inside one batch (same weight)
        _, e, w = _digraph(n=24, deg=3, seed=13)
        e0 = (int(e[0, 0]), int(e[0, 1]))
        f1 = _fresh_edge(24, e, seed=1)
        f2 = _fresh_edge(
            24, np.concatenate([e, np.asarray([f1], np.int64)]), seed=2)
        adds = np.asarray([f1 + (9,), f1 + (9,), f2 + (5,)], np.int32)
        dels = np.asarray([e0, e0, (int(e[3, 0]), int(e[3, 1]))], np.int32)
        return Scenario(name, 24, e, w, UpdateStream(adds=adds, dels=dels),
                        batch_size=8)
    if name == "del_then_readd":
        # e0 deleted+re-added in one batch; e1 deleted in batch 0 and
        # re-added (new weight) in batch 2 — exercises tombstone revival
        _, e, w = _digraph(n=24, deg=3, seed=17)
        e0 = (int(e[0, 0]), int(e[0, 1]))
        e1 = (int(e[5, 0]), int(e[5, 1]))
        adds = np.asarray([e0 + (4,),
                           _fresh_edge(24, e, seed=3) + (6,),
                           e1 + (2,)], np.int32)
        dels = np.asarray([e0, e1], np.int32)
        return Scenario(name, 24, e, w, UpdateStream(adds=adds, dels=dels),
                        batch_size=1)
    raise KeyError(name)


def _fresh_edge(n, edges, seed):
    existing = set(map(tuple, edges.tolist()))
    rng = np.random.default_rng(seed)
    while True:
        u, v = (int(x) for x in rng.integers(0, n, size=2))
        if u != v and (u, v) not in existing:
            return (u, v)


# ---------------------------------------------------------------------------
# Symmetric scenarios (Triangle Counting) — paired directions must share
# a batch, so batch sizes are even
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def sym_scenario(name: str) -> Scenario:
    if name == "sym_batch2":
        _, e, w = _symgraph(n=18, m=70, seed=0)
        return Scenario(name, 18, e, w, _rand_sym_stream(18, e, k=4, seed=5),
                        batch_size=2, diff_capacity=64)
    if name == "sym_batch16":
        _, e, w = _symgraph(n=24, m=110, seed=4)
        return Scenario(name, 24, e, w, _rand_sym_stream(24, e, k=8, seed=7),
                        batch_size=16, diff_capacity=128)
    if name == "sym_empty":
        # grow two triangles sharing edge (0,1) out of nothing
        n = 8
        e = np.zeros((0, 2), np.int64)
        w = np.zeros((0,), np.int32)
        adds = _sym_pairs([(0, 1, 1), (1, 2, 1), (0, 2, 1),
                           (1, 3, 1), (0, 3, 1)])
        dels = np.zeros((0, 2), np.int32)
        return Scenario(name, n, e, w, UpdateStream(adds=adds, dels=dels),
                        batch_size=4, diff_capacity=32)
    if name == "sym_del_readd":
        # delete a triangle edge (pair) in batch 0, re-add it in batch 1
        _, e, w = _symgraph(n=16, m=60, seed=9)
        u, v = int(e[0, 0]), int(e[0, 1])
        filler = _fresh_sym_pair(16, e, seed=2)
        adds = np.concatenate([_sym_pairs([filler + (1,)]),
                               _sym_pairs([(u, v, 1)])])
        dels = _sym_del_pairs([(u, v)])
        return Scenario(name, 16, e, w, UpdateStream(adds=adds, dels=dels),
                        batch_size=2, diff_capacity=64)
    raise KeyError(name)


def _rand_sym_stream(n, edges, k, seed):
    """k deleted pairs (sampled from base) + k fresh added pairs."""
    rng = np.random.default_rng(seed)
    half = edges[edges[:, 0] < edges[:, 1]]
    del_rows = half[rng.choice(len(half), size=min(k, len(half)),
                               replace=False)]
    existing = set(map(tuple, edges.tolist()))
    add_rows = []
    while len(add_rows) < k:
        u, v = (int(x) for x in rng.integers(0, n, size=2))
        if u != v and (u, v) not in existing:
            add_rows.append((u, v, 1))
            existing.add((u, v))
            existing.add((v, u))
    return UpdateStream(adds=_sym_pairs(add_rows),
                        dels=_sym_del_pairs(del_rows.tolist()))


def _fresh_sym_pair(n, edges, seed):
    existing = set(map(tuple, edges.tolist()))
    rng = np.random.default_rng(seed)
    while True:
        u, v = (int(x) for x in rng.integers(0, n, size=2))
        if u != v and (u, v) not in existing:
            return (u, v)


# ---------------------------------------------------------------------------
# Differential runners: api Session == shim == oracle == hand-staged
# ---------------------------------------------------------------------------

def assert_sssp(backend: str, sc: Scenario):
    csr = build_csr(sc.n, sc.edges, sc.w)
    args = {"updateBatch": sc.stream, "batchSize": sc.batch_size,
            "src": sc.src}
    sess = program("sssp").bind(csr, backend=backend,
                                capacity=sc.diff_capacity)
    res = sess.run("DynSSSP", **args)
    dist = res.props.host("dist")

    shim = _shim_run("sssp", "DynSSSP", backend, csr, args,
                     sc.diff_capacity)
    np.testing.assert_array_equal(
        dist, shim.props["dist"],
        err_msg=f"[{sc.name}] session DynSSSP != Program.run shim")

    e2, w2 = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                         sc.stream.adds, sc.stream.dels)
    ref = oracles.sssp_oracle(sc.n, e2, w2, sc.src)
    got = np.minimum(dist.astype(np.int64), oracles.INF)
    np.testing.assert_array_equal(
        got, ref, err_msg=f"[{sc.name}] DSL DynSSSP != oracle")

    gsess = api.bind_graph(csr, backend=backend,
                           capacity=sc.diff_capacity)
    gsess.call(hand_sssp.dyn_sssp, sc.src, sc.stream, sc.batch_size)
    hand = np.minimum(gsess.props.host("dist").astype(np.int64),
                      oracles.INF)
    np.testing.assert_array_equal(
        hand, ref, err_msg=f"[{sc.name}] hand-staged dyn_sssp != oracle")


def assert_pagerank(backend: str, sc: Scenario, beta=1e-4, delta=0.85,
                    max_iter=100, rtol=5e-2, atol=1e-4):
    # beta is tighter than the paper's 1e-3 so per-batch convergence
    # slack (≈ beta/(1-delta) per recompute) stays well inside rtol even
    # for batchSize=1 streams.
    csr = build_csr(sc.n, sc.edges, sc.w)
    args = {"updateBatch": sc.stream, "batchSize": sc.batch_size,
            "beta": beta, "delta": delta, "maxIter": max_iter}
    sess = program("pagerank").bind(csr, backend=backend,
                                    capacity=sc.diff_capacity)
    res = sess.run("DynPR", **args)
    pr = res.props.host("pageRank")

    shim = _shim_run("pagerank", "DynPR", backend, csr, args,
                     sc.diff_capacity)
    np.testing.assert_array_equal(
        pr, shim.props["pageRank"],
        err_msg=f"[{sc.name}] session DynPR != Program.run shim")

    e2, _ = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                        sc.stream.adds, sc.stream.dels)
    ref = oracles.pagerank_oracle(sc.n, e2, beta=beta, delta=delta,
                                  max_iter=max_iter)
    np.testing.assert_allclose(
        pr, ref, rtol=rtol, atol=atol,
        err_msg=f"[{sc.name}] DSL DynPR != oracle")

    gsess = api.bind_graph(csr, backend=backend,
                           capacity=sc.diff_capacity)
    gsess.call(hand_pr.dyn_pr, sc.stream, sc.batch_size, beta=beta,
               delta=delta, max_iter=max_iter)
    np.testing.assert_allclose(
        gsess.props.host("pr"), ref, rtol=rtol, atol=atol,
        err_msg=f"[{sc.name}] hand-staged dyn_pr != oracle")


def assert_sssp_stream(backend: str, sc: Scenario, segment_size: int = 4):
    """Streaming-executor cell: GraphSession.run_stream (the fused
    engine executor) must stay oracle-exact — same contract as the
    per-batch dispatch path."""
    csr = build_csr(sc.n, sc.edges, sc.w)
    e2, w2 = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                         sc.stream.adds, sc.stream.dels)
    ref = oracles.sssp_oracle(sc.n, e2, w2, sc.src)
    sess = api.bind_graph(csr, backend=backend, capacity=sc.diff_capacity)
    props0 = sess.call(hand_sssp.static_sssp, sc.src)
    props = sess.run_stream(sc.stream, sc.batch_size,
                            hand_sssp.stream_step, props0,
                            segment_size=segment_size)
    got = np.minimum(sess.props.host("dist").astype(np.int64),
                     oracles.INF)
    np.testing.assert_array_equal(
        got, ref, err_msg=f"[{sc.name}] session sssp run_stream != oracle")


def assert_pagerank_stream(backend: str, sc: Scenario, beta=1e-4,
                           delta=0.85, max_iter=100, rtol=5e-2, atol=1e-4,
                           segment_size: int = 4):
    csr = build_csr(sc.n, sc.edges, sc.w)
    e2, _ = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                        sc.stream.adds, sc.stream.dels)
    ref = oracles.pagerank_oracle(sc.n, e2, beta=beta, delta=delta,
                                  max_iter=max_iter)
    sess = api.bind_graph(csr, backend=backend, capacity=sc.diff_capacity)
    props0 = sess.call(hand_pr.static_pr, beta, delta, max_iter)
    step = hand_pr.make_stream_step(beta, delta, max_iter)
    sess.run_stream(sc.stream, sc.batch_size, step, props0,
                    segment_size=segment_size)
    np.testing.assert_allclose(
        sess.props.host("pr"), ref, rtol=rtol, atol=atol,
        err_msg=f"[{sc.name}] session pr run_stream != oracle")


def assert_tc_stream(backend: str, sc: Scenario, segment_size: int = 4):
    import jax.numpy as jnp
    csr = build_csr(sc.n, sc.edges, sc.w)
    e2, _ = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                        sc.stream.adds, sc.stream.dels)
    ref = oracles.tc_oracle(sc.n, e2)
    sess = api.bind_graph(csr, backend=backend, capacity=sc.diff_capacity)
    count0 = jnp.asarray(sess.call(hand_tc.static_tc), jnp.int32)
    count = sess.run_stream(sc.stream, sc.batch_size,
                            hand_tc.stream_step, count0,
                            segment_size=segment_size)
    assert int(count) == ref, \
        f"[{sc.name}] session tc run_stream {int(count)} != oracle {ref}"


def assert_sssp_save_restore(backend: str, sc: Scenario, ckpt_dir,
                             restore_backend: str = None,
                             restore_opts: dict = None):
    """Durability cell: arm DynSSSP, apply the first half of the stream,
    ``save``, ``restore_session``, apply the rest — the final ``dist``
    must be bit-identical to the uninterrupted armed run, and
    oracle-exact.  ``restore_backend`` names a different backend to
    restore onto (cross-backend / elastic cells); SSSP's int-min fold is
    order-independent, so the bit-exact contract holds across backends
    and across dist re-partitioning."""
    restore_opts = restore_opts or {}
    csr = build_csr(sc.n, sc.edges, sc.w)
    batches = list(sc.stream.batches(sc.batch_size))
    k = max(1, len(batches) // 2)

    # uninterrupted reference: one armed session over every batch
    ref_sess = program("sssp").bind(csr, backend=backend,
                                    capacity=sc.diff_capacity)
    ref_sess.run("DynSSSP", batchSize=sc.batch_size, src=sc.src)
    for b in batches:
        ref_sess.apply(b)
    ref = np.asarray(ref_sess.props.host("dist"))

    # interrupted: save after k batches, drop everything, restore, finish
    sess = program("sssp").bind(csr, backend=backend,
                                capacity=sc.diff_capacity)
    sess.run("DynSSSP", batchSize=sc.batch_size, src=sc.src)
    for b in batches[:k]:
        sess.apply(b)
    sess.save(ckpt_dir)
    del sess

    res = api.restore_session(ckpt_dir, backend=restore_backend,
                              **restore_opts)
    assert res.armed, f"[{sc.name}] restore must re-arm the Batch loop"
    assert res.stream_cursor == k, \
        f"[{sc.name}] cursor {res.stream_cursor} != batches applied {k}"
    for b in batches[k:]:
        res.apply(b)
    got = np.asarray(res.props.host("dist"))
    np.testing.assert_array_equal(
        got, ref,
        err_msg=f"[{sc.name}] save/restore DynSSSP != uninterrupted "
                f"({backend} -> {restore_backend or backend})")

    e2, w2 = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                         sc.stream.adds, sc.stream.dels)
    oracle = oracles.sssp_oracle(sc.n, e2, w2, sc.src)
    np.testing.assert_array_equal(
        np.minimum(got.astype(np.int64), oracles.INF), oracle,
        err_msg=f"[{sc.name}] save/restore DynSSSP != oracle")


def assert_pagerank_save_restore(backend: str, sc: Scenario, ckpt_dir,
                                 beta=1e-4, delta=0.85, max_iter=100):
    """Float bit-exactness cell: same-backend save/restore must resume
    PageRank *bit-identically* — raw handle leaves (diff pool layout,
    ELL pack) are restored, so float summation order is preserved.
    Same-backend only: dist re-meshes and cross-backend converts, which
    keeps values but not float bit patterns."""
    csr = build_csr(sc.n, sc.edges, sc.w)
    args = {"batchSize": sc.batch_size, "beta": beta, "delta": delta,
            "maxIter": max_iter}
    batches = list(sc.stream.batches(sc.batch_size))
    k = max(1, len(batches) // 2)

    ref_sess = program("pagerank").bind(csr, backend=backend,
                                        capacity=sc.diff_capacity)
    ref_sess.run("DynPR", **args)
    for b in batches:
        ref_sess.apply(b)
    ref = np.asarray(ref_sess.props.host("pageRank"))

    sess = program("pagerank").bind(csr, backend=backend,
                                    capacity=sc.diff_capacity)
    sess.run("DynPR", **args)
    for b in batches[:k]:
        sess.apply(b)
    sess.save(ckpt_dir)
    del sess

    res = api.restore_session(ckpt_dir)
    for b in batches[k:]:
        res.apply(b)
    np.testing.assert_array_equal(
        np.asarray(res.props.host("pageRank")), ref,
        err_msg=f"[{sc.name}] save/restore DynPR not bit-exact on "
                f"{backend}")


# ---------------------------------------------------------------------------
# Adversarial-stream runners (admission guard, DESIGN.md §6): a
# pure-poison batch PREPENDED to the scenario stream shifts every clean
# batch by exactly one, so after the guard disposes of batch 0 (clamp
# masks every lane — a no-op batch; quarantine dead-letters it) the
# applied updates are identical to the clean stream and the oracle is
# unchanged.
# ---------------------------------------------------------------------------

def poison_stream(sc: Scenario, with_weight_poison: bool = False
                  ) -> UpdateStream:
    """The scenario stream with one batch of poison rows up front:
    out-of-range and negative vertex ids (never clampable into a real
    update), plus — for the quarantine cells — one NaN-weight row with
    valid ids (only detectable on the raw host arrays;
    ``UpdateStream.batch`` would silently int-cast it)."""
    bs, n = sc.batch_size, sc.n
    rows = [(n + 1 + i, 0, 1) if i % 2 == 0 else (0, -(i + 1), 1)
            for i in range(bs)]
    pa = np.asarray(rows, np.float64).reshape(bs, 3)
    if with_weight_poison:
        pa[0] = (0, min(1, n - 1), np.nan)
    adds = np.concatenate(
        [pa, np.asarray(sc.stream.adds, np.float64).reshape(-1, 3)])
    pd = np.asarray([(n + 7, n + 8)] * bs, np.int64)
    dels = np.concatenate(
        [pd, np.asarray(sc.stream.dels, np.int64).reshape(-1, 2)])
    return UpdateStream(adds=adds, dels=dels)


def assert_sssp_poison(backend: str, sc: Scenario, policy: str):
    """DSL one-shot cell under attack: ``run`` must survive the poison
    batch per policy and end oracle-exact against the CLEAN stream."""
    csr = build_csr(sc.n, sc.edges, sc.w)
    e2, w2 = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                         sc.stream.adds, sc.stream.dels)
    ref = oracles.sssp_oracle(sc.n, e2, w2, sc.src)
    pstream = poison_stream(sc, with_weight_poison=(policy == "quarantine"))
    sess = program("sssp").bind(csr, backend=backend,
                                capacity=sc.diff_capacity,
                                admission=policy)
    res = sess.run("DynSSSP", updateBatch=pstream,
                   batchSize=sc.batch_size, src=sc.src)
    got = np.minimum(res.props.host("dist").astype(np.int64), oracles.INF)
    np.testing.assert_array_equal(
        got, ref,
        err_msg=f"[{sc.name}/{policy}] poisoned DynSSSP != clean oracle "
                f"on {backend}")
    h = sess.health
    if policy == "quarantine":
        assert h.quarantined >= 1, "poison batch must be dead-lettered"
        assert len(sess.dead_letter) >= 1
    else:
        assert h.clamped >= 1, "poison batch must be sanitized"
    assert h.admitted >= pstream.num_batches(sc.batch_size) - 1


def assert_sssp_stream_poison(backend: str, sc: Scenario, policy: str,
                              segment_size: int = 4):
    """Fused-executor cell under attack: poison batches are spliced out
    per policy while clean contiguous ranges still run fused; final
    state oracle-exact against the clean stream."""
    csr = build_csr(sc.n, sc.edges, sc.w)
    e2, w2 = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                         sc.stream.adds, sc.stream.dels)
    ref = oracles.sssp_oracle(sc.n, e2, w2, sc.src)
    pstream = poison_stream(sc, with_weight_poison=(policy == "quarantine"))
    sess = api.bind_graph(csr, backend=backend, capacity=sc.diff_capacity,
                          admission=policy)
    props0 = sess.call(hand_sssp.static_sssp, sc.src)
    sess.run_stream(pstream, sc.batch_size, hand_sssp.stream_step, props0,
                    segment_size=segment_size)
    got = np.minimum(sess.props.host("dist").astype(np.int64), oracles.INF)
    np.testing.assert_array_equal(
        got, ref,
        err_msg=f"[{sc.name}/{policy}] poisoned sssp run_stream != clean "
                f"oracle on {backend}")
    h = sess.health
    if policy == "quarantine":
        assert h.quarantined >= 1 and len(sess.dead_letter) >= 1
    else:
        assert h.clamped >= 1
    assert sess.stream_cursor == pstream.num_batches(sc.batch_size)


def assert_tc(backend: str, sc: Scenario):
    csr = build_csr(sc.n, sc.edges, sc.w)
    args = {"updateBatch": sc.stream, "batchSize": sc.batch_size}
    sess = program("tc").bind(csr, backend=backend,
                              capacity=sc.diff_capacity)
    res = sess.run("DynTC", **args)
    e2, _ = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                        sc.stream.adds, sc.stream.dels)
    ref = oracles.tc_oracle(sc.n, e2)
    assert int(res.value) == ref, \
        f"[{sc.name}] DSL DynTC {int(res.value)} != oracle {ref}"

    shim = _shim_run("tc", "DynTC", backend, csr, args, sc.diff_capacity)
    assert int(shim.value) == int(res.value), \
        f"[{sc.name}] session DynTC != Program.run shim"

    gsess = api.bind_graph(csr, backend=backend, capacity=sc.diff_capacity)
    count = gsess.call(hand_tc.dyn_tc, sc.stream, sc.batch_size)
    assert int(count) == ref, \
        f"[{sc.name}] hand-staged dyn_tc {int(count)} != oracle {ref}"


# ---------------------------------------------------------------------------
# The matrix: program × backend × scenario cells as pytest test functions
# ---------------------------------------------------------------------------

BACKENDS = ["jnp", "dist", "dist_sharded", "pallas"]

SSSP_SCENARIOS = ["batch1", "batch8", "batch64", "empty", "self_loops",
                  "dup_in_batch", "del_then_readd"]
PR_SCENARIOS = ["batch1", "batch8", "batch64", "del_then_readd"]
TC_SCENARIOS = ["sym_batch2", "sym_batch16", "sym_empty", "sym_del_readd"]

# the dist cell that stays in the fast lane: a single whole-Δ batch
DIST_FAST = {"batch64"}

# backends whose cells mostly run in the slow lane (one fast
# representative each), which keeps the fast lane short: dist and
# dist_sharded are the widest columns, pallas_chained is the pre-fusion
# baseline kept honest by one cell per program.
_MOSTLY_SLOW = {"dist", "dist_sharded", "pallas_chained"}


def _cells(scenarios, backends, fast=DIST_FAST, prefix=""):
    out = []
    for s in scenarios:
        for b in backends:
            marks = ()
            if b in _MOSTLY_SLOW and s not in fast:
                marks = (pytest.mark.slow,)
            out.append(pytest.param(s, b, marks=marks,
                                    id=f"{prefix}{s}-{b}"))
    return out


# TC's wedge enumeration on the dist backend is the paper's admitted MPI
# bottleneck; the two fast engines cover the kernel surface here while
# test_backends.py keeps one dist TC case.  dist_sharded joins the
# column (halo'd wedge bounds make sharded TC work) with its own fast
# representative — DIST_FAST names no symmetric scenario.
TC_FAST = {"sym_batch2"}

# Streaming-executor cells: the same scenarios driven through
# GraphSession.run_stream (one fused lax.scan per segment) must stay
# oracle-exact on every backend.  Scenario-representative subset per
# program keeps the fast lane fast; dist cells follow the DIST_FAST rule.
STREAM_SSSP = ["batch1", "batch8", "empty", "dup_in_batch", "del_then_readd"]
STREAM_PR = ["batch1", "batch8", "del_then_readd"]
STREAM_TC = ["sym_batch2", "sym_empty", "sym_del_readd"]
# the dist stream cell that stays in the fast lane
DIST_STREAM_FAST = {"batch8"}

# Adversarial-stream cells (admission guard, DESIGN.md §6): a poison
# batch prepended to the scenario stream must leave the final state
# oracle-exact against the CLEAN stream after the guard disposes of it
# (clamp → masked no-op, quarantine → dead-letter).  Both the DSL
# one-shot path and the fused streaming executor get cells on every
# registered backend.
POISON_SCENARIOS = ["batch8", "batch64"]
POISON_POLICIES = ["clamp", "quarantine"]
# the admission guard sits in front of the engine, so its cells need
# one distributed representative, not two: dist covers the shard_map
# column and dist_sharded stays out of the poison grid
POISON_BACKENDS = [b for b in BACKENDS if b != "dist_sharded"]


def _poison_cells(scenarios, backends, fast=DIST_FAST):
    out = []
    for s in scenarios:
        for b in backends:
            for p in POISON_POLICIES:
                marks = ()
                if b in _MOSTLY_SLOW and s not in fast:
                    marks = (pytest.mark.slow,)
                out.append(pytest.param(s, b, p, marks=marks,
                                        id=f"poison-{s}-{b}-{p}"))
    return out


# Durability cells: arm the Batch loop, apply half the stream, save,
# restore from disk, apply the rest — bit-identical to the uninterrupted
# armed run (see assert_sssp_save_restore).  Every registered backend
# gets a cell; dist's restores onto a second engine, so it rides the
# slow lane alongside pallas_chained per the _MOSTLY_SLOW convention.
DURABLE_BACKENDS = ["jnp", "dist", "dist_sharded", "pallas",
                    "pallas_chained", "frontier"]


def matrix(backends) -> dict:
    """The matrix's test functions, each parametrized over the cells
    whose (saving) backend is in ``backends``; tests with no such cell
    are left out.  A ``test_conformance_<group>.py`` file collects them
    with ``globals().update(matrix({...}))``."""
    tests = {}

    def add(fn, argnames, cells, col=1):
        mine = [c for c in cells if c.values[col] in backends]
        if mine:
            tests[fn.__name__] = pytest.mark.parametrize(argnames,
                                                         mine)(fn)

    def test_conformance_sssp(scenario, backend):
        assert_sssp(backend, digraph_scenario(scenario))
    add(test_conformance_sssp, ("scenario", "backend"),
        _cells(SSSP_SCENARIOS, BACKENDS))

    def test_conformance_pagerank(scenario, backend):
        assert_pagerank(backend, digraph_scenario(scenario))
    add(test_conformance_pagerank, ("scenario", "backend"),
        _cells(PR_SCENARIOS, BACKENDS))

    def test_conformance_tc(scenario, backend):
        assert_tc(backend, sym_scenario(scenario))
    add(test_conformance_tc, ("scenario", "backend"),
        _cells(TC_SCENARIOS, ["jnp", "dist_sharded", "pallas"],
               fast=TC_FAST))

    def test_stream_conformance_sssp(scenario, backend):
        assert_sssp_stream(backend, digraph_scenario(scenario))
    add(test_stream_conformance_sssp, ("scenario", "backend"),
        _cells(STREAM_SSSP, BACKENDS + ["pallas_chained", "frontier"],
               fast=DIST_STREAM_FAST, prefix="stream-"))

    def test_stream_conformance_pagerank(scenario, backend):
        assert_pagerank_stream(backend, digraph_scenario(scenario))
    add(test_stream_conformance_pagerank, ("scenario", "backend"),
        _cells(STREAM_PR, BACKENDS + ["pallas_chained", "frontier"],
               fast=DIST_STREAM_FAST, prefix="stream-"))

    # dist refuses wedge enumeration inside the fused scan (no static
    # bounds); dist_sharded provides them, so the sharded column is the
    # FIRST distributed engine in the streaming-TC row.
    def test_stream_conformance_tc(scenario, backend):
        assert_tc_stream(backend, sym_scenario(scenario))
    add(test_stream_conformance_tc, ("scenario", "backend"),
        _cells(STREAM_TC, ["jnp", "dist_sharded", "pallas"], fast=TC_FAST,
               prefix="stream-"))

    def test_conformance_sssp_poison(scenario, backend, policy):
        assert_sssp_poison(backend, digraph_scenario(scenario), policy)
    add(test_conformance_sssp_poison, ("scenario", "backend", "policy"),
        _poison_cells(POISON_SCENARIOS, POISON_BACKENDS))

    def test_stream_conformance_sssp_poison(scenario, backend, policy):
        assert_sssp_stream_poison(backend, digraph_scenario(scenario),
                                  policy)
    add(test_stream_conformance_sssp_poison,
        ("scenario", "backend", "policy"),
        _poison_cells(["batch8"],
                      POISON_BACKENDS + ["pallas_chained", "frontier"],
                      fast=DIST_STREAM_FAST))

    def test_conformance_sssp_save_restore(scenario, backend, tmp_path):
        assert_sssp_save_restore(backend, digraph_scenario(scenario),
                                 tmp_path)
    add(test_conformance_sssp_save_restore, ("scenario", "backend"),
        _cells(["batch8"], DURABLE_BACKENDS, fast=set(), prefix="ckpt-"))

    # elastic re-mesh: save on the full mesh, restore onto half of it (on
    # a single-device host this degenerates to 1 -> 1, which still walks
    # the pack/re-partition path; CI's dist-smoke job runs it at 8 -> 4)
    if "dist_sharded" in backends:
        @pytest.mark.slow
        def test_conformance_sssp_save_restore_remesh(tmp_path):
            import jax
            m = max(1, len(jax.devices()) // 2)
            assert_sssp_save_restore("dist_sharded",
                                     digraph_scenario("batch8"), tmp_path,
                                     restore_opts={"num_shards": m})
        tests[test_conformance_sssp_save_restore_remesh.__name__] = \
            test_conformance_sssp_save_restore_remesh

    # float bit-exactness: raw-leaf restore preserves the diff-pool
    # layout and ELL pack, so resumed PageRank is bit-identical, not
    # just close
    def test_conformance_pagerank_save_restore(scenario, backend,
                                               tmp_path):
        assert_pagerank_save_restore(backend, digraph_scenario(scenario),
                                     tmp_path)
    add(test_conformance_pagerank_save_restore, ("scenario", "backend"),
        _cells(["batch8"], ["jnp", "pallas"], fast=set(), prefix="ckpt-"))

    # cross-backend restore: the checkpoint converts through the
    # canonical alive-edge list; SSSP's int-min fold makes the contract
    # still bit-exact
    def test_conformance_cross_backend_restore(save_backend,
                                               restore_backend, tmp_path):
        assert_sssp_save_restore(save_backend, digraph_scenario("batch8"),
                                 tmp_path, restore_backend=restore_backend)
    add(test_conformance_cross_backend_restore,
        ("save_backend", "restore_backend"),
        [pytest.param("jnp", "pallas", id="ckpt-jnp-to-pallas"),
         pytest.param("pallas", "jnp", id="ckpt-pallas-to-jnp")], col=0)
    return tests
