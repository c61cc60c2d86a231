"""Regression tests pinning the four PR 5 stream-executor perf debts
(failing before the fix, passing after):

  #1 ``_stream_cache`` leaked one compiled executable per capacity step
     because keys ignored the handle's static shapes — ``grow`` now
     evicts the stale entries;
  #2 ``ell_apply_add`` re-traced the repack branch on every eager call
     (fresh ``repack`` lambda per call) — engines now pass a stable
     jitted repack, pinned via the pack trace counter;
  #3 ``_run_stream_fused`` re-stacked the segment on every
     grow-and-replay retry — stacked once per segment window now;
  #4 baseline ``Engine.run_stream`` synced the pool counters twice per
     batch — one (overflow, used, dead) read per batch now.

Plus #5 (PR 7): session ``apply`` read the (overflow, used, dead)
triple twice per attempt (a pre-read to establish the baseline and a
post-read to detect overflow) — ``_retry_on_overflow`` now reads it
ONCE post-attempt against the running ``_of_base``.

Plus #6 (PR 10): ``DistEngine.pack_state``'s ``_gather_edges`` pulled
the stacked edge lanes to host one device_get per array (and per shard
before that) — the harvest is now ONE fused ``_host_fetch`` of the
whole lane pytree per save.

Plus #7: every ``DistEngine`` op ran its shard_map body eagerly,
compiling op by op on every call — ``_spmd`` now compiles each distinct
program once, with the closures' arrays passed in as arguments.
"""
import dataclasses

import numpy as np
import pytest

from repro.graph import build_csr
from repro.graph.updates import UpdateStream, random_updates
from repro.core.engine import Engine, JnpEngine
from repro.core.pallas_engine import PallasEngine
from repro.core.frontier_engine import FrontierEngine
from repro.kernels import ell as ell_mod
from repro.algos import sssp


def _graph(n=48, deg=4, seed=7, max_w=30):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(n * deg, 2))
    e = e[e[:, 0] != e[:, 1]]
    w = rng.integers(1, max_w, size=len(e)).astype(np.int32)
    return build_csr(n, e, w)


# ---------------------------------------------------------------------------
# #1: grow() evicts the stale-capacity stream executables
# ---------------------------------------------------------------------------

def test_stream_cache_evicted_on_grow():
    csr = _graph()
    ups = random_updates(csr, percent=40, seed=3)
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=4)          # guaranteed overflow
    g2, _ = sssp.dyn_sssp_stream(eng, g, 0, ups, batch_size=4,
                                 segment_size=3)
    assert eng.handle_graph(g2).diff_capacity > 4  # at least one grow
    final = eng._handle_shape_key(g2)
    assert eng._stream_cache, "fused path should have cached a runner"
    stale = [k for k in eng._stream_cache if final not in k]
    assert not stale, f"stale-capacity executables leaked: {stale}"


def test_stream_cache_keys_carry_shapes_and_batch_size():
    csr = _graph(seed=11)
    ups = random_updates(csr, percent=10, seed=5)
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=64)         # no overflow
    sssp.dyn_sssp_stream(eng, g, 0, ups, batch_size=8, segment_size=2)
    key = eng._handle_shape_key(g)
    assert all(key in k and 8 in k for k in eng._stream_cache)


# ---------------------------------------------------------------------------
# #2: structural adds stop re-tracing the repack once warm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_cls", [PallasEngine, FrontierEngine],
                         ids=["pallas", "frontier"])
def test_repack_traces_once_across_eager_adds(engine_cls):
    csr = _graph(n=32, seed=17)
    eng = engine_cls()
    h = eng.prepare(csr, diff_capacity=32)
    fresh = [(1, 30), (2, 29), (3, 28), (4, 27)]

    def add(h, u, v):
        b = UpdateStream(adds=np.array([[u, v, 5]], np.int32),
                         dels=np.zeros((0, 2), np.int32)).batch(0, 4)
        return eng.update_add(h, b)

    h = add(h, *fresh[0])                          # warm the caches
    before = ell_mod.TRACE_COUNTS["pack"]
    for u, v in fresh[1:]:                         # same shapes, fresh edges
        h = add(h, u, v)
    traced = ell_mod.TRACE_COUNTS["pack"] - before
    assert traced == 0, (
        f"repack branch re-traced {traced}x on cached-shape eager adds")


# ---------------------------------------------------------------------------
# #3: one stacked() per segment window, replays included
# ---------------------------------------------------------------------------

class _CountingStream(UpdateStream):
    calls = {"stacked": 0}

    def stacked(self, *a, **kw):
        _CountingStream.calls["stacked"] += 1
        return super().stacked(*a, **kw)


def test_segment_stacked_once_across_overflow_replays():
    csr = _graph()
    ups = random_updates(csr, percent=40, seed=3)
    stream = _CountingStream(adds=ups.adds, dels=ups.dels)
    eng = JnpEngine()
    g = eng.prepare(csr, diff_capacity=4)          # guaranteed overflow
    _CountingStream.calls["stacked"] = 0
    batch_size, seg = 4, 3
    g2, _ = sssp.dyn_sssp_stream(eng, g, 0, stream, batch_size=batch_size,
                                 segment_size=seg)
    assert eng.handle_graph(g2).diff_capacity > 4  # replays happened
    nb = stream.num_batches(batch_size)
    windows = -(-nb // seg)
    assert _CountingStream.calls["stacked"] == windows, (
        f"expected one stacked() per segment window ({windows}), got "
        f"{_CountingStream.calls['stacked']} — replays must reuse the stack")


# ---------------------------------------------------------------------------
# #4: one counter sync per baseline batch
# ---------------------------------------------------------------------------

class _SyncCountingJnp(JnpEngine):
    def __init__(self):
        super().__init__()
        self.counter_syncs = 0

    def handle_counters(self, handle):
        self.counter_syncs += 1
        return super().handle_counters(handle)


def test_baseline_run_stream_syncs_counters_once_per_batch():
    csr = _graph(seed=13)
    ups = random_updates(csr, percent=20, seed=5)
    eng = _SyncCountingJnp()
    g = eng.prepare(csr, diff_capacity=64)         # ample: no replays
    props0 = sssp.static_sssp(eng, g, 0)
    eng.counter_syncs = 0
    Engine.run_stream(eng, g, ups, 4, sssp.stream_step, props0)
    nb = ups.num_batches(4)
    assert eng.counter_syncs == 1 + nb, (
        f"baseline dispatch synced {eng.counter_syncs}x for {nb} batches; "
        f"want 1 initial + 1 per batch")


# ---------------------------------------------------------------------------
# #6: one host sync per dist pack_state, diff pool included
# ---------------------------------------------------------------------------

def test_dist_pack_state_one_host_sync(monkeypatch):
    from repro.core import dist as dist_mod
    from repro.core.engine import JnpEngine, state_to_csr

    csr = _graph(seed=23)
    eng = dist_mod.DistEngine()
    g = eng.prepare(csr, diff_capacity=64)
    ups = random_updates(csr, percent=30, seed=4)
    b = ups.batch(0, max(ups.num_adds, ups.num_dels, 1))
    g = eng.update_del(g, b)
    g = eng.update_add(g, b)                  # populated diff pool

    calls = {"n": 0}
    real = dist_mod._host_fetch

    def counting(tree):
        calls["n"] += 1
        return real(tree)

    monkeypatch.setattr(dist_mod, "_host_fetch", counting)
    tree, meta = eng.pack_state(g)
    assert calls["n"] == 1, (
        f"pack_state cost {calls['n']} host syncs; the whole edge "
        f"harvest must be one fused transfer")

    # the fused harvest is a pure layout change: the packed edge set
    # must equal the jnp engine's canonical view of the same state
    jeng = JnpEngine()
    jg = jeng.prepare(csr, diff_capacity=64)
    jg = jeng.update_del(jg, b)
    jg = jeng.update_add(jg, b)
    jtree, jmeta = jeng.pack_state(jg)
    ref, _ = state_to_csr(jtree, jmeta)
    packed = np.stack([np.asarray(tree["src"]), np.asarray(tree["dst"]),
                       np.asarray(tree["w"])], 1)
    want = np.stack([np.asarray(ref.src), np.asarray(ref.dst),
                     np.asarray(ref.w)], 1)
    order = lambda e: e[np.lexsort((e[:, 2], e[:, 1], e[:, 0]))]
    np.testing.assert_array_equal(order(packed), order(want))


# ---------------------------------------------------------------------------
# #5: one counter sync per session apply (armed and structural)
# ---------------------------------------------------------------------------

def test_session_apply_syncs_counters_once_per_apply():
    import repro.api as api
    from repro.dsl_programs import path as program_path

    csr = _graph(seed=19)
    ups = random_updates(csr, percent=15, seed=9)
    batches = list(ups.batches(4))

    # armed DSL applies: the ΔG hot path of a long-lived session
    eng = _SyncCountingJnp()
    sess = api.Session(api.compile(program_path("sssp")), eng, csr,
                       capacity=64)                # ample: no replays
    sess.run("DynSSSP", batchSize=4, src=0)
    eng.counter_syncs = 0
    for b in batches:
        sess.apply(b)
    assert eng.counter_syncs == len(batches), (
        f"armed apply synced {eng.counter_syncs}x for {len(batches)} "
        f"batches; want exactly one post-attempt read per apply")

    # structural applies go through the same _retry_on_overflow
    eng2 = _SyncCountingJnp()
    gsess = api.GraphSession(eng2, csr, capacity=64)
    gsess.apply(batches[0])                        # prepares lazily
    eng2.counter_syncs = 0
    for b in batches[1:]:
        gsess.apply(b)
    assert eng2.counter_syncs == len(batches) - 1, (
        f"structural apply synced {eng2.counter_syncs}x for "
        f"{len(batches) - 1} batches; want one per apply")


# ---------------------------------------------------------------------------
# #7: one compile per distributed program, not one per call
# ---------------------------------------------------------------------------

def test_dist_ops_compile_once_per_program():
    import jax.numpy as jnp
    from repro.core.dist import DistEngine
    from repro.graph.csr import INT

    csr = _graph(seed=29)
    eng = DistEngine()
    g = eng.prepare(csr, diff_capacity=64)
    props = {"x": eng.full(0, INT)}
    batches = list(random_updates(csr, percent=30, seed=6).batches(4))
    assert len(batches) > 2
    seen = []
    for i, b in enumerate(batches):
        # a fresh closure over a fresh array on every call
        shift = jnp.full((eng.n_pad,), i + 1, INT)
        out = eng.vertex_map(g, lambda p: {"x": p["x"] + shift}, props)
        seen.append(int(np.asarray(out["x"])[0]))
        g = eng.update_add(eng.update_del(g, b), b)
    programs = [k for k in eng._stream_cache if k[0] == "spmd"]
    assert len(programs) == 3, programs      # vertex_map, del, add
    # closed-over arrays enter as arguments: each call saw its own
    assert seen == list(range(1, len(batches) + 1))
    # a host scalar is a literal of the program: a new one is a new
    # program, never a stale hit
    for c in (7, 9):
        out = eng.vertex_map(g, lambda p: {"x": p["x"] + c}, props)
        assert int(np.asarray(out["x"])[0]) == c
