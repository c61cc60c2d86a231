"""diff-CSR substrate: unit + hypothesis property tests.

Property: any sequence of add/delete batches applied to a DynGraph equals
a python dict-of-sets model of the same edge multiset.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.graph import (build_csr, from_csr, update_csr_add, update_csr_del,
                         merge, is_edge, edge_weight)
from repro.graph.csr import row_searchsorted
from repro.graph.diffcsr import DynGraph


def test_fig6_example():
    """The paper's Figure 6 walk-through."""
    edges = [(0, 1), (1, 2), (1, 3), (2, 0), (3, 4), (4, 5), (5, 3)]
    g = from_csr(build_csr(6, np.array(edges)), diff_capacity=4)
    g = update_csr_del(g, jnp.array([1]), jnp.array([3]))      # B->D deleted
    assert not bool(is_edge(g, 1, 3))
    g = update_csr_add(g, jnp.array([4]), jnp.array([2]))      # E->C added
    assert bool(is_edge(g, 4, 2))
    # vacant-slot revival: re-adding B->D reuses its tombstoned slot
    before_diff = int(jnp.sum(g.d_src < g.n))
    g = update_csr_add(g, jnp.array([1]), jnp.array([3]), jnp.array([9]))
    assert bool(is_edge(g, 1, 3)) and int(edge_weight(g, 1, 3)) == 9
    assert int(jnp.sum(g.d_src < g.n)) == before_diff  # no diff growth
    assert g.out_degrees().tolist() == [1, 2, 1, 1, 2, 1]


def test_overflow_counter():
    g = from_csr(build_csr(4, np.array([(0, 1)])), diff_capacity=2)
    g = update_csr_add(g, jnp.array([0, 0, 0, 1]), jnp.array([2, 3, 1, 0]))
    # 0->1 revives in main; 0->2, 0->3 fill diff; 1->0 overflows
    assert int(g.overflow) == 1
    gm = merge(g, diff_capacity=8)
    assert int(gm.overflow) == 0
    for u, v in [(0, 1), (0, 2), (0, 3)]:
        assert bool(is_edge(gm, u, v))
    assert not bool(is_edge(gm, 1, 0))  # dropped by capacity, as declared


def test_row_searchsorted():
    vals = jnp.array([1, 3, 5, 2, 2, 9], jnp.int32)  # rows [0,3) and [3,6)
    lo = jnp.array([0, 3, 3], jnp.int32)
    hi = jnp.array([3, 6, 6], jnp.int32)
    q = jnp.array([3, 2, 10], jnp.int32)
    out = row_searchsorted(vals, lo, hi, q)
    assert out.tolist() == [1, 3, 6]


# Each example draws its graph from a few fixed sizes and pads every
# batch to LANES lanes behind a mask, so the updates meet a bounded set
# of shapes and compile once per shape, not once per example.
SIZES = (6, 12, 20)        # vertices
BASE_EDGES = (0, 16, 30)   # distinct non-loop base edges
LANES = 6
_update_add = jax.jit(update_csr_add)
_update_del = jax.jit(update_csr_del)
_is_edge = jax.jit(is_edge)


def _padded(pairs, mask):
    """``(k, 2)`` endpoint pairs and their mask → LANES-wide lanes."""
    src = np.zeros(LANES, np.int32)
    dst = np.zeros(LANES, np.int32)
    lane_mask = np.zeros(LANES, bool)
    src[:len(pairs)], dst[:len(pairs)] = pairs[:, 0], pairs[:, 1]
    lane_mask[:len(pairs)] = mask
    return jnp.asarray(src), jnp.asarray(dst), jnp.asarray(lane_mask)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_updates_match_model(data):
    n = data.draw(st.sampled_from(SIZES))
    rng = np.random.default_rng(data.draw(st.integers(0, 10_000)))
    m = data.draw(st.sampled_from(BASE_EDGES))
    pairs = np.argwhere(~np.eye(n, dtype=bool))
    edges = pairs[rng.choice(len(pairs), size=m, replace=False)]
    csr = build_csr(n, edges)
    model = set(map(tuple, np.stack(
        [np.asarray(csr.src), np.asarray(csr.dst)], 1).tolist())) \
        if csr.num_edges else set()
    g = from_csr(csr, diff_capacity=64)

    for _ in range(data.draw(st.integers(1, 4))):
        k = data.draw(st.integers(1, LANES))
        adds = rng.integers(0, n, size=(k, 2))
        add_mask = adds[:, 0] != adds[:, 1]
        dels_pool = list(model) or [(0, 1)]
        didx = rng.integers(0, len(dels_pool), size=k)
        dels = np.array([dels_pool[i] for i in didx])
        g = _update_del(g, *_padded(dels, np.ones(k, bool)))
        model -= set(map(tuple, dels.tolist()))
        a_src, a_dst, a_mask = _padded(adds, add_mask)
        g = _update_add(g, a_src, a_dst, mask=a_mask)
        model |= set(map(tuple, adds[add_mask].tolist()))

    assert int(g.overflow) == 0
    # full membership check against the model
    qs, qd = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    got = np.asarray(_is_edge(g, qs.ravel(), qd.ravel())).reshape(n, n)
    want = np.zeros((n, n), bool)
    for u, v in model:
        want[u, v] = True
    assert np.array_equal(got, want)
    # degrees
    deg = np.asarray(g.out_degrees())
    wdeg = want.sum(1)
    assert np.array_equal(deg, wdeg)
    # merge preserves the edge set
    gm = merge(g)
    got2 = np.asarray(_is_edge(gm, qs.ravel(), qd.ravel())).reshape(n, n)
    assert np.array_equal(got2, want)
