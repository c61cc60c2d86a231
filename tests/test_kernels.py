"""Per-kernel interpret-mode validation against pure-jnp oracles,
with shape/dtype sweeps and hypothesis randomization (brief §(c))."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.graph import build_csr, from_csr
from repro.graph.csr import INF_W, INT
from repro.kernels.ell import pack_ell, Ell
from repro.kernels import csr_relax as K
from repro.kernels import pallas_repair as FK
from repro.kernels import ref as R
from repro.kernels import ops as kops
from repro.kernels.platform import interpret_mode
from repro.kernels.flash_attention import flash_attention


def _random_ell(rng, n, e, k=8):
    edges = rng.integers(0, n, size=(e, 2))
    edges = edges[edges[:, 0] != edges[:, 1]]
    csr = build_csr(n, edges, rng.integers(1, 50, len(edges)).astype(np.int32))
    g = from_csr(csr, diff_capacity=4)
    return g, pack_ell(g, k=k)


@pytest.mark.parametrize("n,e,k", [(64, 256, 8), (200, 1000, 4),
                                   (300, 600, 16)])
def test_rowmin_matches_ref(n, e, k):
    rng = np.random.default_rng(n + e)
    _, ell = _random_ell(rng, n, e, k)
    vals = jnp.concatenate([
        jnp.asarray(rng.integers(0, 1000, n).astype(np.int32)),
        jnp.full((1,), INF_W, jnp.int32)])
    out = K.relax_rowmin(ell.ell_src, ell.ell_w, vals)
    ref = R.relax_rowmin_ref(ell.ell_src, ell.ell_w, vals)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n,e,k", [(64, 256, 8), (128, 512, 8)])
def test_rowsum_matches_ref(n, e, k):
    rng = np.random.default_rng(7)
    _, ell = _random_ell(rng, n, e, k)
    vals = jnp.concatenate([
        jnp.asarray(rng.random(n).astype(np.float32)),
        jnp.zeros((1,), jnp.float32)])
    out = K.spmv_rowsum(ell.ell_src, vals)
    ref = R.spmv_rowsum_ref(ell.ell_src, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_argmin_matches_ref():
    rng = np.random.default_rng(13)
    _, ell = _random_ell(rng, 64, 256, 8)
    vals = jnp.concatenate([
        jnp.asarray(rng.integers(0, 1000, 64).astype(np.int32)),
        jnp.full((1,), INF_W, jnp.int32)])
    vmin = kops.vertex_min_plus(ell, vals)
    tgt_full = jnp.concatenate([vmin, jnp.full((1,), INF_W, jnp.int32)])
    row_tgt = tgt_full[jnp.minimum(ell.row2dst, 64)]
    out = K.relax_rowargmin(ell.ell_src, ell.ell_w, vals, row_tgt, n=64)
    ref = R.relax_rowargmin_ref(ell.ell_src, ell.ell_w, vals, row_tgt, n=64)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_vertex_ops_match_segment_reduction():
    """ELL path == direct segment reduction over the edge list."""
    rng = np.random.default_rng(3)
    g, ell = _random_ell(rng, 100, 700, 8)
    esrc, edst, ew, ealive = g.edge_arrays()
    vals = jnp.concatenate([
        jnp.asarray(rng.integers(0, 1000, 100).astype(np.int32)),
        jnp.full((1,), INF_W, jnp.int32)])
    got = kops.vertex_min_plus(ell, vals)
    cand = jnp.where(ealive, vals[esrc] + ew, INF_W)
    want = jax.ops.segment_min(cand, edst, num_segments=100)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# fused repair kernels (kernels/pallas_repair.py) vs the chained path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,e,k,block", [(64, 256, 8, 128),
                                         (200, 1000, 4, 128),
                                         (300, 2000, 8, 256),
                                         (800, 5000, 8, 512)])
def test_fused_relax_matches_chained(n, e, k, block):
    """One fused launch == rowmin → hit → rowargmin chain, bit-exact."""
    rng = np.random.default_rng(n + e)
    _, ell = _random_ell(rng, n, e, k)
    vals = jnp.concatenate([
        jnp.asarray(rng.integers(0, 1000, n).astype(np.int32)),
        jnp.full((1,), INF_W, jnp.int32)])
    vmin, parent, hit = kops.vertex_relax_fused(ell, vals, block=block)
    want_min = kops.vertex_min_plus(ell, vals)
    want_par = kops.vertex_argmin_src(ell, vals, want_min)
    assert np.array_equal(np.asarray(vmin), np.asarray(want_min))
    assert np.array_equal(np.asarray(parent), np.asarray(want_par))
    assert np.array_equal(np.asarray(hit), np.asarray(want_min) < INF_W)


@pytest.mark.parametrize("n,e,block", [(64, 256, 128), (800, 5000, 512)])
def test_fused_relax_frontier_compaction_invariants(n, e, block):
    """The in-kernel compaction packs frontier row ids to each tile's
    prefix (padded with sentinel R), in row order."""
    rng = np.random.default_rng(5)
    _, ell = _random_ell(rng, n, e, 8)
    assert ell.R % block == 0
    vals = jnp.concatenate([
        jnp.asarray(rng.integers(0, 1000, n).astype(np.int32)),
        jnp.full((1,), INF_W, jnp.int32)])
    rmin, _, rows = FK.fused_relax_rows(ell.ell_src, ell.ell_w, vals,
                                        block=block)
    R_ = ell.R
    hit = np.asarray(rmin) < INF_W
    got_rows, got = np.asarray(rows), []
    for t in range(R_ // block):
        c = int(hit[t * block:(t + 1) * block].sum())
        seg = got_rows[t * block:(t + 1) * block]
        assert (seg[:c] < R_).all() and (seg[c:] == R_).all(), t
        got.extend(seg[:c].tolist())
    assert got == np.nonzero(hit)[0].tolist()


@pytest.mark.parametrize("block", [128, 256, 512])
def test_fused_spmv_frontier_compaction_invariants(block):
    """The SpMV launch packs each tile's materialized rows (row2dst < n)
    to the tile's prefix in row order, sentinel R after them."""
    rng = np.random.default_rng(11)
    n = 800
    _, ell = _random_ell(rng, n, 5000, 8)
    assert ell.R % block == 0
    vals = jnp.concatenate([jnp.asarray(rng.random(n).astype(np.float32)),
                            jnp.zeros((1,), jnp.float32)])
    _, rows = FK.fused_spmv_rows(ell.ell_src, ell.row2dst, vals, block=block)
    mat = np.asarray(ell.row2dst) < n
    got_rows, got = np.asarray(rows), []
    for t in range(ell.R // block):
        c = int(mat[t * block:(t + 1) * block].sum())
        seg = got_rows[t * block:(t + 1) * block]
        assert (seg[c:] == ell.R).all(), t
        got.extend(seg[:c].tolist())
    assert got == np.nonzero(mat)[0].tolist()


def test_interpret_mode_follows_platform():
    """Kernels interpret off the TPU and compile on it; an explicit
    bool (the compile checks' False) overrides the platform."""
    assert interpret_mode() == (jax.default_backend() != "tpu")
    assert interpret_mode(False) is False
    assert interpret_mode(True) is True


def test_fused_spmv_matches_chained():
    rng = np.random.default_rng(9)
    n = 100
    _, ell = _random_ell(rng, n, 700, 8)
    vals = jnp.concatenate([jnp.asarray(rng.random(n).astype(np.float32)),
                            jnp.zeros((1,), jnp.float32)])
    vsum, hit = kops.vertex_spmv_fused(ell, vals)
    want = kops.vertex_spmv(ell, vals)
    want_hit = jax.ops.segment_max(
        (ell.row2dst < n).astype(INT), jnp.minimum(ell.row2dst, n),
        num_segments=n + 1)[:n].astype(bool)
    assert np.array_equal(np.asarray(vsum), np.asarray(want))
    assert np.array_equal(np.asarray(hit), np.asarray(want_hit))


def test_repair_config_cached_per_shape():
    FK.clear_tune_cache()
    c1 = FK.repair_config(64, 300, 8)
    assert FK.repair_config(64, 300, 8) is c1          # cache hit
    assert FK.repair_config(64, 600, 8) is not c1      # new shape, new cfg
    R_ = 128 * ((64 + -(-300 // 8) + 127) // 128)
    assert R_ % c1.row_block == 0
    FK.clear_tune_cache()
    cm = FK.repair_config(32, 64, 8, measure=True)     # timed candidates
    assert cm.row_block in (128, 256, 512)
    FK.clear_tune_cache()


@pytest.mark.parametrize("S,dh,causal,dtype", [
    (128, 64, True, jnp.float32),
    (256, 128, True, jnp.float32),
    (256, 128, False, jnp.float32),
    (512, 64, True, jnp.bfloat16),
    (128, 256, True, jnp.float32),
])
def test_flash_attention_sweep(S, dh, causal, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S + dh), 3)
    q = jax.random.normal(k1, (2, S, dh), dtype)
    k = jax.random.normal(k2, (2, S, dh), dtype)
    v = jax.random.normal(k3, (2, S, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=128, bk=128)
    ref = R.flash_ref(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < tol, err


def test_flash_softcap():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (2, 256, 128))
    k = jax.random.normal(k2, (2, 256, 128))
    v = jax.random.normal(k3, (2, 256, 128))
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128, softcap=30.0)
    ref = R.flash_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 50), st.integers(2, 30), st.sampled_from([4, 8, 16]))
def test_ell_pack_property(seed, n, k):
    """pack_ell places every alive edge in exactly one slot with its dst."""
    rng = np.random.default_rng(seed)
    e = max(n, 4) * 3
    g, ell = _random_ell(rng, n, e, k)
    esrc, edst, ew, ealive = (np.asarray(x) for x in g.edge_arrays())
    want = {}
    for s, d, w, a in zip(esrc, edst, ew, ealive):
        if a:
            want[(s, d)] = want.get((s, d), 0) + 1
    got = {}
    src = np.asarray(ell.ell_src)
    r2d = np.asarray(ell.row2dst)
    for r in range(src.shape[0]):
        for c in range(src.shape[1]):
            if src[r, c] < n:
                assert r2d[r] < n
                got[(src[r, c], r2d[r])] = got.get((src[r, c], r2d[r]), 0) + 1
    assert got == want
