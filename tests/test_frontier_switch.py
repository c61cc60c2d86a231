"""The frontier switch of ``JnpEngine.fixed_point``: a fixed-point
iteration whose frontier owns few edge lanes sweeps those lanes alone.

The sparse branch must be bit-identical to the dense one (min / argmin
reductions are exact and order-free), wherever the capacity puts the
boundary: forced on, forced off, at one sweep's exact lane count and one
below it; with frontier vertices of degree 0, and with frontier vertices
that own diff-pool lanes, tombstones among them.
"""
import numpy as np
import pytest

import repro.api as api
import repro.core.engine as engine_mod
from repro.algos import oracles
from repro.algos import sssp as hand_sssp
from repro.core.engine import JnpEngine
from repro.dsl_programs import path as program_path
from repro.graph import build_csr
from repro.graph.csr import INF_W, rmat_graph
from repro.graph.updates import UpdateStream

BATCH = 32
SRC = 0


def _graph():
    n, e, w = rmat_graph(9, 6, seed=4)
    csr = build_csr(n, e, w)
    edges = np.stack([np.asarray(csr.src), np.asarray(csr.dst)], 1) \
        .astype(np.int64)
    return n, csr, edges, np.asarray(csr.w)


def _tree_edges(n, edges, w, dist):
    """(parent, v) of every reachable v: its smallest tight in-neighbour."""
    d = dist[edges[:, 0]] + w
    tight = (dist[edges[:, 0]] < oracles.INF) & (d == dist[edges[:, 1]])
    out = {}
    for u, v in edges[tight]:
        if v != SRC and (v not in out or u < out[v]):
            out[v] = u
    return np.asarray(sorted((u, v) for v, u in out.items()), np.int64)


def _stream(n, edges, w, seed=7):
    """Three batches: tree edges deleted; fresh edges into the diff pool;
    some of those deleted again (diff tombstones) and revived; deleted
    main edges revived with new weights."""
    rng = np.random.default_rng(seed)
    tree = _tree_edges(n, edges, w, oracles.sssp_oracle(n, edges, w, SRC))
    tree = tree[rng.permutation(len(tree))]
    existing = set(map(tuple, edges.tolist()))
    # fresh edges out of the source put diff lanes in the first frontier
    src_fresh = [(SRC, v, 500) for v in range(1, n)
                 if (SRC, v) not in existing][:3]
    existing.update((u, v) for u, v, _ in src_fresh)
    src_fresh = np.asarray(src_fresh, np.int64)
    fresh = []
    while len(fresh) < 2 * BATCH:
        u, v = (int(x) for x in rng.integers(0, n, 2))
        if u != v and (u, v) not in existing:
            existing.add((u, v))
            fresh.append((u, v, int(rng.integers(1, 100))))
    fresh = np.asarray(fresh, np.int64)
    d0 = tree[:BATCH]
    a0 = np.concatenate([src_fresh, fresh[:BATCH - len(src_fresh)]])
    d1 = np.concatenate([a0[:BATCH // 2, :2], tree[BATCH:BATCH * 3 // 2]])
    a1 = np.concatenate([np.column_stack([d0[:BATCH // 2], 1 + np.arange(
        BATCH // 2)]), fresh[BATCH:BATCH * 3 // 2]])
    d2 = tree[BATCH * 3 // 2:BATCH * 5 // 2]
    a2 = np.concatenate([a0[:BATCH // 4], fresh[BATCH * 3 // 2:]])
    return [(a.astype(np.int32), d.astype(np.int32))
            for a, d in ((a0, d0), (a1, d1), (a2, d2))]


def _batch(adds, dels):
    return UpdateStream(adds=adds, dels=dels).batch(0, BATCH)


def _run_armed(csr, batches, cap, monkeypatch):
    if cap is not None:
        monkeypatch.setattr(engine_mod, "sparse_lane_capacity",
                            lambda main_capacity: cap)
    sess = api.compile(program_path("sssp")).bind(csr, capacity=256)
    sess.run("DynSSSP", batchSize=BATCH, src=SRC)
    for adds, dels in batches:
        sess.apply(_batch(adds, dels))
    return (sess.props.host("dist"), sess.props.host("parent"),
            sess.health)


@pytest.fixture(scope="module")
def scenario():
    n, csr, edges, w = _graph()
    batches = _stream(n, edges, w)
    e, ww = edges, w
    for adds, dels in batches:
        e, ww = oracles.edges_after_updates(n, e, ww, adds, dels)
    return n, csr, edges, batches, e, ww


@pytest.fixture(scope="module")
def dense(scenario):
    n, csr, _, batches, _, _ = scenario
    with pytest.MonkeyPatch.context() as mp:
        return _run_armed(csr, batches, 0, mp)


@pytest.mark.parametrize("mode", ["on", "exact", "exact_minus_1",
                                  "default"])
def test_armed_sssp_is_bit_identical_to_dense(scenario, dense, mode,
                                              monkeypatch):
    n, csr, edges, batches, e, ww = scenario
    # the static solve's first sweep: the source's out-lanes
    first = int(np.sum(edges[:, 0] == SRC))
    cap = {"on": 1 << 20, "exact": first, "exact_minus_1": first - 1,
           "default": None}[mode]
    dist, parent, health = _run_armed(csr, batches, cap, monkeypatch)
    np.testing.assert_array_equal(dist, dense[0])
    np.testing.assert_array_equal(parent, dense[1])
    assert dense[2].sweeps_sparse == 0 and dense[2].sweeps_dense > 0
    assert health.sweeps_sparse > 0
    if mode == "on":
        assert health.sweeps_dense == 0
    assert (health.sweeps_dense + health.sweeps_sparse
            == dense[2].sweeps_dense)

    ref = oracles.sssp_oracle(n, e, ww, SRC)
    np.testing.assert_array_equal(
        np.minimum(dist.astype(np.int64), oracles.INF), ref)
    wmap = {(int(a), int(b)): int(x) for (a, b), x in zip(e, ww)}
    for v in np.flatnonzero((ref < oracles.INF) & (np.arange(n) != SRC)):
        u = int(parent[v])
        assert ref[u] + wmap[(u, int(v))] == ref[v], v


def test_exact_capacity_boundary_moves_one_sweep(scenario, monkeypatch):
    """At the first sweep's exact lane count it runs sparse; one lane
    fewer and it runs dense; everything else the same."""
    _, csr, edges, batches, _, _ = scenario
    first = int(np.sum(edges[:, 0] == SRC))
    counts = {}
    for cap in (first, first - 1):
        with monkeypatch.context() as mp:
            h = _run_armed(csr, batches, cap, mp)[2]
        counts[cap] = (h.sweeps_dense, h.sweeps_sparse)
    assert counts[first][1] > counts[first - 1][1]
    assert sum(counts[first]) == sum(counts[first - 1])


def _one_sweep(g, props, cap, monkeypatch):
    monkeypatch.setattr(engine_mod, "sparse_lane_capacity",
                        lambda main_capacity: cap)
    eng = JnpEngine()
    eng._n = g.n
    out = eng.fixed_point(g, hand_sssp._relax_sweep(), props,
                          cond_fn=lambda p, it, col: col.any(p["modified"]),
                          max_iter=1)
    return ({k: np.asarray(v) for k, v in out.items()},
            tuple(int(x) for x in np.asarray(eng.sweep_counts)))


def test_frontier_owning_diff_lanes_and_tombstones(monkeypatch):
    """One vertex whose main and diff rows, dead ones included, are the
    frontier's lanes: sparse at exactly that many, dense one below."""
    n, csr, edges, w = _graph()
    v = int(np.bincount(edges[:, 0], minlength=n).argmax())
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=64)
    nbrs = set(edges[edges[:, 0] == v, 1].tolist())
    fresh = [u for u in range(n) if u != v and u not in nbrs][:6]
    add = np.asarray([(v, u, 3 + u % 5) for u in fresh], np.int32)
    g = eng.update_add(g, _batch(add, np.zeros((0, 2), np.int32)))
    dels = np.asarray([(v, fresh[0]), (v, sorted(nbrs)[0])], np.int32)
    g = eng.update_del(g, _batch(np.zeros((0, 3), np.int32), dels))
    main_deg = int(np.diff(np.asarray(g.offsets))[v])
    diff_deg = int(np.diff(np.asarray(g.d_offsets))[v])
    assert diff_deg == len(fresh) and main_deg == len(nbrs)
    lanes = main_deg + diff_deg

    iota = np.arange(n)
    props = {"dist": np.where(iota == v, 0, INF_W).astype(np.int32),
             "parent": np.full(n, -1, np.int32), "modified": iota == v}
    sparse, c1 = _one_sweep(g, props, lanes, monkeypatch)
    dense, c0 = _one_sweep(g, props, lanes - 1, monkeypatch)
    assert c1 == (0, 1) and c0 == (1, 0)
    for k in dense:
        np.testing.assert_array_equal(sparse[k], dense[k], err_msg=k)
    reached = set(np.flatnonzero(sparse["dist"] < INF_W).tolist()) - {v}
    assert reached == (nbrs - {sorted(nbrs)[0]}) | set(fresh[1:])
    assert (sparse["parent"][sorted(reached)] == v).all()


def test_frontier_with_degree_zero_vertices(monkeypatch):
    n, csr, edges, w = _graph()
    deg = np.bincount(edges[:, 0], minlength=n)
    isolated = np.flatnonzero(deg == 0)
    assert len(isolated) >= 4, "the graph needs vertices of degree 0"
    g = JnpEngine().prepare(csr, diff_capacity=16)
    rng = np.random.default_rng(1)
    dist = rng.integers(0, 1000, n).astype(np.int32)
    front = np.zeros(n, bool)
    front[isolated[:4]] = True
    front[[SRC, int(deg.argmax())]] = True
    props = {"dist": dist, "parent": np.full(n, -1, np.int32),
             "modified": front}
    lanes = int(deg[front].sum())
    sparse, c1 = _one_sweep(g, props, lanes, monkeypatch)
    dense, c0 = _one_sweep(g, props, 0, monkeypatch)
    assert c1 == (0, 1) and c0 == (1, 0)
    for k in dense:
        np.testing.assert_array_equal(sparse[k], dense[k], err_msg=k)


def test_dynpr_never_sweeps_sparse(monkeypatch):
    """PageRank's sweeps declare no frontier (pull, float sums), and
    propagateNodeFlags builds its own: every iteration stays dense."""
    n, csr, edges, w = _graph()
    adds, dels = _stream(n, edges, w)[0]
    sess = api.compile(program_path("pagerank")).bind(csr, capacity=256)
    sess.run("DynPR", batchSize=BATCH, beta=1e-4, delta=0.85, maxIter=100)
    sess.apply(_batch(adds, dels))
    assert sess.health.sweeps_sparse == 0
    assert sess.health.sweeps_dense > 0
