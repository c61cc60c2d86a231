"""Algorithms vs from-scratch oracles, on the jnp engine (paper's OpenMP
analogue).  Dynamic results must equal the static oracle on the
post-update graph — the paper's correctness criterion."""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import random_digraph, random_symgraph, sym_stream
from repro.graph import random_updates
from repro.core.engine import JnpEngine
from repro.algos import sssp, pagerank, triangles, oracles


@pytest.fixture(scope="module")
def setup():
    n, csr, edges, w = random_digraph()
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=64)
    return n, csr, edges, w, eng, g


def test_static_sssp(setup):
    n, csr, edges, w, eng, g = setup
    props = sssp.static_sssp(eng, g, source=0)
    ref = oracles.sssp_oracle(n, edges, w, 0)
    got = np.minimum(np.asarray(props["dist"]).astype(np.int64), oracles.INF)
    assert np.array_equal(got, ref)
    # parent pointers form valid shortest paths
    par = np.asarray(props["parent"])
    for v in range(n):
        if got[v] < oracles.INF and v != 0:
            p = par[v]
            assert p >= 0 and got[p] < got[v]


@pytest.mark.parametrize("percent,batch", [(10, 8), (30, 16)])
def test_dynamic_sssp(setup, percent, batch):
    n, csr, edges, w, eng, g = setup
    ups = random_updates(csr, percent=percent, seed=7)
    _, props = sssp.dyn_sssp(eng, g, 0, ups, batch_size=batch)
    e2, w2 = oracles.edges_after_updates(n, edges, w, ups.adds, ups.dels)
    ref = oracles.sssp_oracle(n, e2, w2, 0)
    got = np.minimum(np.asarray(props["dist"]).astype(np.int64), oracles.INF)
    assert np.array_equal(got, ref)


def test_static_pr(setup):
    n, csr, edges, w, eng, g = setup
    props = pagerank.static_pr(eng, g)
    ref = oracles.pagerank_oracle(n, edges)
    np.testing.assert_allclose(np.asarray(props["pr"]), ref,
                               rtol=2e-3, atol=1e-5)


def test_dynamic_pr(setup):
    n, csr, edges, w, eng, g = setup
    ups = random_updates(csr, percent=20, seed=9)
    _, props = pagerank.dyn_pr(eng, g, ups, batch_size=8)
    e2, _ = oracles.edges_after_updates(n, edges, w, ups.adds, ups.dels)
    ref = oracles.pagerank_oracle(n, e2)
    np.testing.assert_allclose(np.asarray(props["pr"]), ref,
                               rtol=5e-2, atol=1e-4)


def test_static_tc():
    n, csr, edges = random_symgraph()
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=128)
    c = triangles.static_tc(eng, g)
    assert int(c) == oracles.tc_oracle(n, edges)


def test_dynamic_tc():
    n, csr, edges = random_symgraph()
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=256)
    ups = sym_stream(csr, percent=20, seed=5)
    _, c = triangles.dyn_tc(eng, g, ups, batch_size=16)
    e2, _ = oracles.edges_after_updates(
        n, edges, np.ones(len(edges), np.int32), ups.adds, ups.dels)
    assert int(c) == oracles.tc_oracle(n, e2)


def test_propagate_flags():
    # chain 0->1->2, isolated 3
    from repro.graph import build_csr
    csr = build_csr(4, np.array([(0, 1), (1, 2)]))
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=2)
    props = {"flag": jnp.array([True, False, False, False])}
    props = eng.propagate_flags(g, props, "flag")
    assert np.asarray(props["flag"]).tolist() == [True, True, True, False]


def _edges_after_updates_dict(edges, weights, adds, dels):
    """The plain dict semantics the vectorized oracle must keep."""
    ew = {}
    for (u, v), w in zip(edges.tolist(), weights.tolist()):
        ew[(u, v)] = w
    for u, v in dels.tolist():
        ew.pop((u, v), None)
    for u, v, w in adds.tolist():
        ew[(u, v)] = w
    if not ew:
        return np.zeros((0, 2), np.int64), np.zeros((0,), np.int32)
    e = np.array(sorted(ew), dtype=np.int64)
    return e, np.array([ew[tuple(x)] for x in e.tolist()], dtype=np.int32)


@pytest.mark.parametrize("seed", range(6))
def test_edges_after_updates_matches_dict_semantics(seed):
    """Deletes before adds, last add wins on duplicates, sorted output —
    on streams with duplicate base edges, duplicate adds, deletes of
    absent edges and delete-then-re-add lanes."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 40))
    m = int(rng.integers(0, 4 * n))
    edges = rng.integers(0, n, size=(m, 2)).astype(np.int64)
    w = rng.integers(1, 50, size=m).astype(np.int32)
    adds = np.concatenate([
        np.stack([rng.integers(0, n, 3 * n), rng.integers(0, n, 3 * n),
                  rng.integers(1, 50, 3 * n)], axis=1),
        np.concatenate([edges[: m // 3], w[: m // 3, None]], axis=1),
    ]).astype(np.int32)
    dels = np.concatenate([edges[::2], adds[::3, :2],
                           rng.integers(0, n, size=(n, 2))]).astype(np.int32)
    if seed == 0:                      # nothing at all
        edges, w = edges[:0], w[:0]
        adds, dels = adds[:0], dels[:0]
    got_e, got_w = oracles.edges_after_updates(n, edges, w, adds, dels)
    want_e, want_w = _edges_after_updates_dict(edges, w, adds, dels)
    assert got_e.dtype == np.int64 and got_w.dtype == np.int32
    np.testing.assert_array_equal(got_e.reshape(-1, 2), want_e)
    np.testing.assert_array_equal(got_w, want_w)
