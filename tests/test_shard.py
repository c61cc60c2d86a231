"""Sharded-DynGraph suite (``repro.shard``, DESIGN.md §5): partition
correctness, halo-plan invariants, equivalence against the jnp
reference engine, and the elastic pack/unpack roundtrip.

Host-side partition/halo properties run everywhere.  Cells that need a
real multi-device mesh skip on a single-device host and run for real in
CI's dist-smoke job (``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
the slow subprocess cell at the bottom drives the full 8-shard stream +
re-mesh path regardless of the parent process's device count.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.graph import build_csr, random_updates
from repro.graph.partition import (PARTITIONERS, block_partition,
                                   degree_partition, make_partition)
from repro.graph.halo import build_plan, ghost_sets
from repro.algos import sssp

MULTIDEV = len(jax.devices()) >= 2
needs_mesh = pytest.mark.skipif(
    not MULTIDEV, reason="needs >1 XLA device (run under "
    "XLA_FLAGS=--xla_force_host_platform_device_count=N)")


def _edges(n, deg, seed, skew=False):
    rng = np.random.default_rng(seed)
    if skew:
        # zipf-ish source skew: low ids emit most of the edges
        src = (n * rng.random(n * deg) ** 3).astype(np.int64)
    else:
        src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, len(src))
    keep = src != dst
    return src[keep], dst[keep]


# ---------------------------------------------------------------------------
# Partition correctness (both partitioners, property-style)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", PARTITIONERS)
@pytest.mark.parametrize("n,P,seed", [(1, 1, 0), (7, 3, 1), (64, 4, 2),
                                      (64, 8, 3), (100, 7, 4), (257, 8, 5)])
def test_partition_covers_every_vertex_exactly_once(kind, n, P, seed):
    src, _ = _edges(n, 4, seed, skew=True)
    part = make_partition(kind, n, P, src)
    assert part.starts[0] == 0 and part.starts[-1] == n
    assert (np.diff(part.starts) >= 0).all()
    owners = part.assign
    assert owners.shape == (n,)
    assert ((owners >= 0) & (owners < P)).all()
    # contiguous ranges: each vertex lands in exactly the one range that
    # contains it, and the ranges tile [0, n)
    for p in range(P):
        lo, hi = part.starts[p], part.starts[p + 1]
        assert (owners[lo:hi] == p).all()
    counts = np.bincount(owners, minlength=P)
    assert counts.sum() == n


@pytest.mark.parametrize("n,P,seed", [(64, 4, 2), (200, 8, 7), (500, 5, 9)])
def test_degree_partition_balances_mass(n, P, seed):
    src, _ = _edges(n, 6, seed, skew=True)
    part = degree_partition(n, P, src)
    deg = np.bincount(src, minlength=n)
    total, dmax = int(deg.sum()), int(deg.max())
    for p in range(P):
        mass = int(deg[part.starts[p]:part.starts[p + 1]].sum())
        # each cut overshoots the ideal total/P by at most one vertex
        assert mass <= total / P + dmax, (p, mass, total / P, dmax)


def test_degree_partition_edgeless_falls_back_to_block():
    part = degree_partition(10, 4, np.zeros(0, np.int64))
    assert part.kind == "degree"
    np.testing.assert_array_equal(part.starts, block_partition(10, 4).starts)


def test_block_partition_matches_property_ownership():
    part = block_partition(100, 8)
    v = np.arange(100)
    np.testing.assert_array_equal(part.owner_of(v), v // part.block)


# ---------------------------------------------------------------------------
# Halo-plan invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", PARTITIONERS)
@pytest.mark.parametrize("n,P,seed", [(32, 2, 0), (64, 4, 1), (100, 8, 2)])
def test_ghosts_are_exactly_the_cut_edge_endpoints(kind, n, P, seed):
    src, dst = _edges(n, 4, seed, skew=True)
    part = make_partition(kind, n, P, src)
    gsets = ghost_sets(src, dst, part.owner_of(src), part.block, P)
    for p in range(P):
        mine = part.owner_of(src) == p
        ends = np.unique(np.concatenate([src[mine], dst[mine]]))
        expect = ends[(ends // part.block) != p]   # foreign endpoints only
        np.testing.assert_array_equal(gsets[p], expect)
        assert (np.diff(gsets[p]) > 0).all() if len(gsets[p]) > 1 else True


def test_ghost_hints_added_to_every_foreign_shard():
    n, P = 32, 4
    src, dst = _edges(n, 3, 5)
    part = block_partition(n, P)
    hints = np.array([0, 9, 31])
    gsets = ghost_sets(src, dst, part.owner_of(src), part.block, P,
                       hints=hints)
    for p in range(P):
        for h in hints:
            if h // part.block != p:
                assert h in gsets[p], (p, h)


@pytest.mark.parametrize("kind", PARTITIONERS)
def test_halo_plan_tables_describe_one_bijective_packet(kind):
    n, P, seed = 96, 4, 11
    src, dst = _edges(n, 5, seed, skew=True)
    part = make_partition(kind, n, P, src)
    blk = part.block
    gsets = ghost_sets(src, dst, part.owner_of(src), blk, P)
    plan = build_plan(gsets, P, blk, blk * P)
    for p in range(P):
        gh = gsets[p]
        slots = []
        for q in range(P):
            tgt = plan.recv_tgt[p, q]
            real = tgt[tgt < plan.H]
            slots.extend(real.tolist())
            # the same packet seen from the owner side: send_idx entries
            # are owner-local offsets of exactly the ghost ids p expects
            sidx = plan.send_idx[q, p][:len(real)]
            assert ((sidx >= 0) & (sidx < blk)).all()
            np.testing.assert_array_equal(sidx + q * blk, gh[real])
            # pad lanes stay pads on both sides
            assert (plan.send_idx[q, p][len(real):] == blk).all()
            assert (tgt[len(real):] == plan.H).all()
        # every real ghost slot of p is filled exactly once
        np.testing.assert_array_equal(np.sort(slots), np.arange(len(gh)))
        np.testing.assert_array_equal(plan.ghosts[p, :len(gh)], gh)
        assert (plan.ghosts[p, len(gh):] == blk * P).all()


# ---------------------------------------------------------------------------
# Engine equivalence vs the jnp reference
# ---------------------------------------------------------------------------

def _csr_stream(n=48, deg=4, seed=3, percent=40, add_frac=0.6):
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    keep = src != dst
    w = rng.integers(1, 40, keep.sum()).astype(np.int64)
    csr = build_csr(n, np.stack([src[keep], dst[keep]], 1), w)
    return csr, random_updates(csr, percent=percent, seed=seed + 1,
                               add_frac=add_frac)


def _sssp_stream(backend_engine, csr, ups, cap=16):
    g0 = backend_engine.prepare(csr, diff_capacity=cap)
    _, props = sssp.dyn_sssp_stream(backend_engine, g0, 0, ups,
                                    batch_size=8, segment_size=3)
    return np.asarray(props["dist"])


def test_single_shard_matches_jnp_bit_exact():
    from repro.core.engine import JnpEngine
    from repro.shard.engine import ShardedEngine
    csr, ups = _csr_stream()
    ref = _sssp_stream(JnpEngine(), csr, ups)
    # tiny capacity on purpose: the rollback-grow-replay path must stay
    # bit-exact through capacity growth
    got = _sssp_stream(ShardedEngine(num_shards=1), csr, ups, cap=4)
    np.testing.assert_array_equal(got, ref)


@needs_mesh
@pytest.mark.parametrize("kind", PARTITIONERS)
def test_two_shards_match_jnp_bit_exact(kind):
    from repro.core.engine import JnpEngine
    from repro.shard.engine import ShardedEngine
    # add-heavy stream so inserted endpoints fall outside the initial
    # ghost tables: the halo-miss → rollback → rebuild-with-hints →
    # replay loop must land bit-exactly on the reference
    csr, ups = _csr_stream(percent=50, add_frac=0.7)
    ref = _sssp_stream(JnpEngine(), csr, ups)
    eng = ShardedEngine(num_shards=2, partitioner=kind)
    got = _sssp_stream(eng, csr, ups)
    np.testing.assert_array_equal(got, ref)


@needs_mesh
def test_per_shard_bytes_below_replicated_footprint():
    from repro.shard.engine import ShardedEngine
    csr, _ = _csr_stream(n=256, deg=8)
    eng = ShardedEngine(num_shards=2)
    sg = eng.prepare(csr, diff_capacity=64)
    # a shard holds its rows + halo tables, not the whole edge set
    per = eng.per_shard_bytes(sg)
    whole = sum(int(np.prod(np.asarray(a).shape[1:] or (1,))) *
                np.asarray(a).dtype.itemsize
                for a in (sg.src, sg.dst, sg.w, sg.alive))
    assert per < 2 * whole  # sanity: same order as one shard's lanes
    assert eng.per_shard_bytes(sg) == per  # deterministic


# ---------------------------------------------------------------------------
# Elastic pack/unpack roundtrip
# ---------------------------------------------------------------------------

def _sorted_triples(tree):
    e = np.stack([np.asarray(tree["src"]), np.asarray(tree["dst"]),
                  np.asarray(tree["w"])], axis=1)
    return e[np.lexsort((e[:, 2], e[:, 1], e[:, 0]))]


@needs_mesh
@pytest.mark.parametrize("kind", PARTITIONERS)
def test_heavy_insertion_pack_state_roundtrips_bit_exact(kind):
    from repro.shard.engine import ShardedEngine
    csr, ups = _csr_stream(n=40, percent=60, add_frac=0.9)
    eng = ShardedEngine(num_shards=2, partitioner=kind)
    sg = eng.prepare(csr, diff_capacity=2 * ups.num_adds + 8)
    # one wide batch: the heavy insertion goes in as a single del+add
    # round
    width = max(ups.num_adds, ups.num_dels, 1)
    b = ups.batch(0, width)
    sg = eng.update_del(sg, b)
    sg = eng.update_add(sg, b)
    tree1, meta1 = eng.pack_state(sg)
    assert meta1["partitioner"] == kind
    assert meta1["kind"] == "dist"          # shard-count-independent

    # restore onto a DIFFERENT mesh width: the edge set must survive
    # re-partitioning exactly (order may differ, triples may not)
    eng2 = ShardedEngine(num_shards=1, partitioner=kind)
    sg2 = eng2.unpack_state(tree1, meta1)
    tree2, _ = eng2.pack_state(sg2)
    np.testing.assert_array_equal(_sorted_triples(tree2),
                                  _sorted_triples(tree1))

    # a second pack of untouched state is bit-identical, not just
    # set-equal: the canonical snapshot is deterministic
    tree3, _ = eng2.pack_state(sg2)
    for k in ("src", "dst", "w"):
        np.testing.assert_array_equal(np.asarray(tree2[k]),
                                      np.asarray(tree3[k]))


# ---------------------------------------------------------------------------
# Full 8-shard stream + elastic re-mesh (subprocess: needs its own
# XLA_FLAGS before jax initialises; ~5 min of shard_map compiles)
# ---------------------------------------------------------------------------

_EIGHT_SHARD_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    assert len(jax.devices()) == 8, jax.devices()
    import repro.api as api
    from repro.dsl_programs import path as program_path
    from repro.graph import build_csr, random_updates

    rng = np.random.default_rng(11)
    n, deg = 64, 4
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    keep = src != dst
    w = rng.integers(1, 50, keep.sum()).astype(np.int64)
    csr = build_csr(n, np.stack([src[keep], dst[keep]], 1), w)
    ups = random_updates(csr, percent=30, seed=5)
    batches = list(ups.batches(8))
    k = max(1, len(batches) // 2)

    prog = api.compile(program_path("sssp"))
    ref_sess = prog.bind(csr, backend="jnp", capacity=256)
    ref_sess.run("DynSSSP", src=0, batchSize=8)
    for b in batches:
        ref_sess.apply(b)
    ref = np.asarray(ref_sess.props.host("dist"))

    sess = prog.bind(csr, backend="dist_sharded", capacity=256,
                     num_shards=8)
    sess.run("DynSSSP", src=0, batchSize=8)
    for b in batches[:k]:
        sess.apply(b)
    sess.save("/tmp/shard_ckpt")
    del sess

    res = api.restore_session("/tmp/shard_ckpt", num_shards=2)
    assert res.armed and res.stream_cursor == k
    for b in batches[k:]:
        res.apply(b)
    got = np.asarray(res.props.host("dist"))
    np.testing.assert_array_equal(got, ref)
    print("SHARD-OK")
""")


@pytest.mark.slow
def test_eight_shard_stream_and_remesh_subprocess(time_limit):
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", _EIGHT_SHARD_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=time_limit - 10)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARD-OK" in out.stdout
