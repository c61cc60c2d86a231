"""The benchmark's copies of the generators and references agree with the
program's originals at scale 8."""
import numpy as np
import pytest

import ml_dtypes

import gen
import manifest
import reference
from repro.algos import oracles
from repro.graph.csr import rmat_graph


CFG = {"scale": 8, "edge_factor": 16, "graph_seed": 4, "a": 0.57,
       "b": 0.19, "c": 0.19, "weight_bits": 20}


def graph(seed=3):
    return gen.make_graph(CFG, seed)


def test_kronecker_draws_match_rmat_graph():
    n, want, _ = rmat_graph(8, 16, seed=11, a=0.57, b=0.19, c=0.19)
    got = gen.kronecker(8, 16, np.random.default_rng(11), 0.57, 0.19, 0.19)
    assert n == 256 and np.array_equal(got, want)


def undirected(n, e, w) -> bool:
    """Every edge is held both ways with one weight."""
    key = e[:, 0].astype(np.int64) * n + e[:, 1]
    rev = e[:, 1].astype(np.int64) * n + e[:, 0]
    pos = np.minimum(np.searchsorted(key, rev), len(key) - 1)
    return bool(np.all(key[pos] == rev) and np.array_equal(w[pos], w))


def test_graph_is_undirected_without_repeats_or_loops():
    for seed in (0, 2 ** 31 + 9, -4):
        n, e, w = graph(seed)
        key = e[:, 0].astype(np.int64) * n + e[:, 1]
        assert np.all(np.diff(key) > 0) and np.all(e[:, 0] != e[:, 1])
        assert undirected(n, e, w)
        assert w.dtype == np.int32 and w.min() >= 1 and w.max() <= 2 ** 20
        assert w.max() > 2 ** 16      # a distance needs more than 16 bits


def test_stream_updates_each_edge_both_ways():
    n, e, w = graph(6)
    s = gen.make_stream(n, e, w, {"batch": 64, "inserts": 64, "deletes": 32,
                                  "stream_batches": 4}, 6, 20)
    for k in range(12):                      # forward and inverse passes
        a, d = s.rows(k)
        assert np.array_equal(a[0::2, :2], a[1::2, 1::-1])
        assert np.array_equal(a[0::2, 2], a[1::2, 2])
        assert np.array_equal(d[0::2], d[1::2, ::-1])
        assert undirected(n, *reference.edges_after_updates(e, w,
                                                            *s.net(k + 1)))


def test_config_search_key_follows_graph500_rule():
    cfg = manifest.Cell("sssp-kron18-mixed").config
    key = gen.search_key(cfg)
    n, e, _ = gen.make_graph(cfg, cfg["graph_seed"])
    assert cfg["args"]["src"] == key and np.any(e[:, 0] == key)


@pytest.mark.parametrize("dtype", [np.int16, ml_dtypes.bfloat16])
def test_sixteen_bit_distances_are_not_exact(dtype):
    cfg = {**CFG, "scale": 12}
    n, e, w = gen.make_graph(cfg, 8)
    src = gen.search_key(cfg)
    want = reference.sssp(n, e, w, src)
    assert (reference.sssp(n, e, w, src, dtype=dtype) != want).sum() > n // 4


@pytest.mark.parametrize("seed", [1, 2])
def test_edges_after_updates_matches_oracle(seed):
    n, e, w = graph(seed)
    s = gen.make_stream(n, e, w, {"batch": 64, "inserts": 64, "deletes": 32,
                                  "stream_batches": 4}, seed, 20)
    adds, dels = s.net(3)
    got = reference.edges_after_updates(e, w, adds, dels)
    want = oracles.edges_after_updates(n, e, w, adds, dels)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


@pytest.mark.parametrize("seed", [1, 2])
def test_sssp_matches_oracle(seed):
    n, e, w = graph(seed)
    assert np.array_equal(reference.sssp(n, e, w, 0),
                          oracles.sssp_oracle(n, e, w, 0))


@pytest.mark.parametrize("seed", [1, 2])
def test_pagerank_matches_oracle(seed):
    n, e, _ = graph(seed)
    np.testing.assert_allclose(reference.pagerank(n, e, 1e-4, 0.85, 100),
                               oracles.pagerank_oracle(n, e, 1e-4, 0.85, 100),
                               rtol=1e-12, atol=0)


def test_stream_cycles_without_returning_to_the_base_graph():
    n, e, w = graph(5)
    s = gen.make_stream(n, e, w, {"batch": 64, "inserts": 64, "deletes": 64,
                                  "stream_batches": 3}, 5, 20)
    g = (e, w)
    first = reference.edges_after_updates(e, w, *s.rows(0))
    for k in range(10):                      # batch 0, then two cycles
        a, d = s.rows(k)
        g = reference.edges_after_updates(*g, a, d)
        want = reference.edges_after_updates(e, w, *s.net(k + 1))
        assert np.array_equal(g[0], want[0]) and np.array_equal(g[1], want[1])
        assert len(g[0]) != len(e) or not np.array_equal(g[0], e)
        if k + 1 in (5, 9):                  # each cycle ends on batch 0 alone
            assert np.array_equal(g[0], first[0])
            assert np.array_equal(g[1], first[1])


def test_seeds_rename_vertices_of_one_graph_and_stream():
    cfg = CFG
    traffic = {"batch": 64, "inserts": 64, "deletes": 64, "stream_batches": 3}
    n, e0, w0, s0, p0 = gen.make_inputs(cfg, traffic, 1)
    _, e1, w1, s1, p1 = gen.make_inputs(cfg, traffic, 2 ** 31 + 1)
    assert not np.array_equal(p0, p1)
    back = np.empty_like(p0)
    back[p0] = np.arange(n)
    # seed 1's inputs, renamed by seed 2's permutation, are seed 2's inputs
    ren = p1[back]
    g = ren[e0]
    order = np.lexsort((g[:, 1], g[:, 0]))
    assert np.array_equal(g[order], e1) and np.array_equal(w0[order], w1)
    assert np.array_equal(ren[s0.dels], s1.dels)
    assert np.array_equal(ren[s0.adds[:, :2]], s1.adds[:, :2])
    assert reference.sssp(n, e0, w0, int(p0[0]))[p0].tolist() == \
        reference.sssp(n, e1, w1, int(p1[0]))[p1].tolist()
