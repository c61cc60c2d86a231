"""The public API surface: repro.api sessions, the engine registry, and
capacity="auto" sizing/recovery.

The contract under test (ISSUE 5 acceptance criteria):

  * ``CompiledProgram.bind(...).run(...)`` matches the deprecated
    ``Program.run`` shim bit-exactly (spot-checked here; the full
    program × backend × scenario sweep lives in test_conformance_*.py);
  * a session that stays bound across N update batches produces
    identical results to a one-shot run over the same N batches, while
    calling ``engine.prepare`` exactly once;
  * unknown/duplicate backend names fail loudly; ``register_engine``
    plugs a new engine in by name without touching the facade;
  * ``capacity="auto"`` sizes the diff pool from the bound stream, and
    recovers from underestimates via grow-and-replay.
"""
import numpy as np
import pytest

import repro
import repro.api as api
from repro.core import registry
from repro.core.engine import JnpEngine
from repro.dsl_programs import path as program_path
from repro.graph import build_csr
from repro.algos import oracles, sssp as hand_sssp

from conformance import digraph_scenario


def _scenario_bits(name="batch8"):
    sc = digraph_scenario(name)
    csr = build_csr(sc.n, sc.edges, sc.w)
    e2, w2 = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                         sc.stream.adds, sc.stream.dels)
    ref = oracles.sssp_oracle(sc.n, e2, w2, sc.src)
    return sc, csr, ref


def _as_oracle(dist):
    return np.minimum(np.asarray(dist).astype(np.int64), oracles.INF)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_unknown_backend():
    with pytest.raises(registry.UnknownBackendError) as ei:
        registry.make_engine("no-such-backend")
    msg = str(ei.value)
    assert "no-such-backend" in msg and "jnp" in msg  # lists what exists

    sc, csr, _ = _scenario_bits()
    prog = api.compile(program_path("sssp"))
    with pytest.raises(registry.UnknownBackendError):
        prog.bind(csr, backend="no-such-backend")


def test_registry_duplicate_registration():
    with pytest.raises(registry.DuplicateBackendError):
        registry.register_engine("jnp", JnpEngine)
    # non-callable factories and bad names are rejected up front
    with pytest.raises(TypeError):
        registry.register_engine("bad", object())
    with pytest.raises(ValueError):
        registry.register_engine("", JnpEngine)


def test_registry_plugin_engine_binds_by_name():
    class TracedEngine(JnpEngine):
        name = "traced"

    try:
        registry.register_engine("traced", TracedEngine)
        with pytest.raises(registry.DuplicateBackendError):
            registry.register_engine("traced", TracedEngine)
        registry.register_engine("traced", TracedEngine, overwrite=True)
        assert "traced" in registry.available_backends()

        sc, csr, ref = _scenario_bits()
        sess = api.compile(program_path("sssp")).bind(
            csr, backend="traced", capacity=sc.diff_capacity)
        assert isinstance(sess.engine, TracedEngine)
        res = sess.run("DynSSSP", updateBatch=sc.stream,
                       batchSize=sc.batch_size, src=sc.src)
        np.testing.assert_array_equal(
            _as_oracle(res.props.host("dist")), ref)
    finally:
        registry.unregister_engine("traced")
    assert "traced" not in registry.available_backends()


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", [
    "jnp", "pallas", "frontier",
    pytest.param("dist", marks=pytest.mark.slow),
])
def test_session_reuse_matches_one_shot(backend):
    """N applies on one bound session == run_stream == one-shot run ==
    deprecated shim, oracle-exact, with engine.prepare called once."""
    sc, csr, ref = _scenario_bits("batch8")
    prog = api.compile(program_path("sssp"))
    args = dict(batchSize=sc.batch_size, src=sc.src)

    # one-shot session + deprecated shim (bit-exact cross-check)
    one = prog.bind(csr, backend=backend, capacity=sc.diff_capacity)
    res = one.run("DynSSSP", updateBatch=sc.stream, **args)
    with pytest.warns(DeprecationWarning):
        shim = prog.program.run(
            "DynSSSP", registry.make_engine(backend), csr,
            args={"updateBatch": sc.stream, **args},
            diff_capacity=sc.diff_capacity)
    np.testing.assert_array_equal(res.props.host("dist"),
                                  shim.props["dist"])
    np.testing.assert_array_equal(_as_oracle(res.props["dist"]), ref)

    # armed session: count prepares, apply every batch one by one
    sess = prog.bind(csr, backend=backend, capacity=sc.diff_capacity)
    prepares = []
    orig_prepare = sess.engine.prepare
    sess.engine.prepare = lambda *a, **k: (prepares.append(1),
                                           orig_prepare(*a, **k))[1]
    sess.run("DynSSSP", **args)
    assert sess.armed and sess.prepared
    for batch in sc.stream.batches(sc.batch_size):
        sess.apply(batch)
    np.testing.assert_array_equal(sess.props.host("dist"),
                                  shim.props["dist"])
    assert len(prepares) == 1, "prepare must run exactly once per session"

    # run_stream drives the same armed loop
    sess2 = prog.bind(csr, backend=backend, capacity=sc.diff_capacity)
    sess2.run("DynSSSP", **args)
    out = sess2.run_stream(sc.stream)          # batchSize from arm time
    np.testing.assert_array_equal(out.props.host("dist"),
                                  shim.props["dist"])


def test_session_props_are_device_resident():
    sc, csr, _ = _scenario_bits()
    sess = api.compile(program_path("sssp")).bind(
        csr, backend="jnp", capacity=sc.diff_capacity)
    sess.run("DynSSSP", src=sc.src, batchSize=sc.batch_size)
    import jax
    dist = sess.props["dist"]
    assert isinstance(dist, jax.Array)          # no implicit host sync
    assert dist.shape[0] == sess.engine.n_pad   # padded device layout
    host = sess.props.to_host()
    assert isinstance(host["dist"], np.ndarray)
    assert host["dist"].shape[0] == sc.n        # sliced to real vertices
    assert set(sess.props) >= {"dist", "parent", "modified"}


def test_session_value_epilogue_is_stable():
    """TC's armed session: reading .value evaluates the post-Batch
    epilogue without disturbing the live state (same answer twice)."""
    from conformance import sym_scenario
    sc = sym_scenario("sym_batch2")
    csr = build_csr(sc.n, sc.edges, sc.w)
    e2, _ = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                        sc.stream.adds, sc.stream.dels)
    ref = oracles.tc_oracle(sc.n, e2)
    sess = api.compile(program_path("tc")).bind(
        csr, backend="jnp", capacity=sc.diff_capacity)
    sess.run("DynTC", batchSize=sc.batch_size)
    for batch in sc.stream.batches(sc.batch_size):
        sess.apply(batch)
    assert int(sess.value) == ref
    assert int(sess.value) == ref               # re-read: state untouched


def test_capacity_auto_sizes_from_stream():
    sc, csr, ref = _scenario_bits("batch8")
    sess = api.compile(program_path("sssp")).bind(csr, backend="jnp",
                                                  capacity="auto")
    assert not sess.prepared                    # lazy until first use
    res = sess.run("DynSSSP", updateBatch=sc.stream,
                   batchSize=sc.batch_size, src=sc.src)
    g = sess.handle
    assert g.diff_capacity >= 2 * sc.stream.num_adds
    np.testing.assert_array_equal(_as_oracle(res.props["dist"]), ref)


def test_capacity_auto_floors_at_default():
    """Every ``capacity='auto'`` path floors at the same default.  The
    stream path used to floor at 16, so a tiny probe stream prepared a
    pool 4x smaller than an identically-bound armed session's and paid a
    grow-merge-replay on its first real batch."""
    from repro.api import _auto_capacity, _DEFAULT_CAPACITY
    from repro.graph.updates import UpdateStream
    tiny = UpdateStream(adds=np.asarray([(0, 1, 5)], dtype=np.int64),
                        dels=np.zeros((0, 2), dtype=np.int64))
    assert _auto_capacity(stream=tiny) == _DEFAULT_CAPACITY
    assert _auto_capacity(batch=tiny.batch(0, 2)) == _DEFAULT_CAPACITY
    assert _auto_capacity() == _DEFAULT_CAPACITY
    # and the floor still yields to real demand
    big = UpdateStream(
        adds=np.asarray([(0, 1, 5)] * 100, dtype=np.int64),
        dels=np.zeros((0, 2), dtype=np.int64))
    assert _auto_capacity(stream=big) == 2 * big.num_adds


def test_capacity_overflow_grows_and_replays():
    """An undersized pool must not drop adds: the armed apply path rolls
    back, grows, and replays — final state stays oracle-exact."""
    sc, csr, ref = _scenario_bits("batch8")
    assert sc.stream.num_adds > 2
    prog = api.compile(program_path("sssp"))
    sess = prog.bind(csr, backend="jnp", capacity=2)   # way too small
    sess.run("DynSSSP", src=sc.src, batchSize=sc.batch_size)
    cap0 = sess.handle.diff_capacity
    for batch in sc.stream.batches(sc.batch_size):
        sess.apply(batch)
    assert sess.handle.diff_capacity > cap0            # grew at least once
    np.testing.assert_array_equal(_as_oracle(sess.props["dist"]), ref)

    # the one-shot run path recovers too (grow + whole-run replay)
    one = prog.bind(csr, backend="jnp", capacity=2)
    res = one.run("DynSSSP", updateBatch=sc.stream,
                  batchSize=sc.batch_size, src=sc.src)
    assert one.handle.diff_capacity > 2
    np.testing.assert_array_equal(_as_oracle(res.props["dist"]), ref)

    # the structural (GraphSession) apply path recovers too
    gsess = api.bind_graph(csr, backend="jnp", capacity=2)
    for batch in sc.stream.batches(sc.batch_size):
        gsess.apply(batch)
    props = hand_sssp.static_sssp(gsess.engine, gsess.handle, sc.src)
    np.testing.assert_array_equal(_as_oracle(props["dist"][: sc.n]), ref)

    # ... and hand-staged drivers through call() (grow + driver replay)
    csess = api.bind_graph(csr, backend="jnp", capacity=2)
    csess.call(hand_sssp.dyn_sssp, sc.src, sc.stream, sc.batch_size)
    np.testing.assert_array_equal(
        _as_oracle(csess.props.host("dist")), ref)


def test_bind_graph_call_adopts_handle():
    sc, csr, ref = _scenario_bits("batch8")
    sess = repro.bind_graph(csr, backend="jnp",
                            capacity=sc.diff_capacity)
    h0 = sess.handle
    props = sess.call(hand_sssp.dyn_sssp, sc.src, sc.stream,
                      sc.batch_size)
    assert sess.handle is not h0                # updated handle adopted
    np.testing.assert_array_equal(
        _as_oracle(sess.props.host("dist")), ref)
    np.testing.assert_array_equal(_as_oracle(props["dist"][: sc.n]), ref)


def test_compile_is_cached_and_lists_functions():
    p = program_path("sssp")
    prog = api.compile(p)
    assert prog is api.compile(p)               # compile once per source
    assert "DynSSSP" in prog.functions and "staticSSSP" in prog.functions
    # repro top-level re-export
    assert repro.compile is api.compile


def test_run_unknown_function_fails_early():
    sc, csr, _ = _scenario_bits()
    sess = api.compile(program_path("sssp")).bind(csr, backend="jnp")
    with pytest.raises(KeyError):
        sess.run("NoSuchFunc")


def test_missing_scalar_arg_fails_for_one_shot():
    from repro.core.dsl.codegen import CodegenError
    sc, csr, _ = _scenario_bits()
    sess = api.compile(program_path("sssp")).bind(csr, backend="jnp")
    with pytest.raises(CodegenError, match="src"):
        sess.run("DynSSSP", updateBatch=sc.stream,
                 batchSize=sc.batch_size)   # src missing


def test_missing_scalar_arg_fails_when_arming():
    """Armed mode may only omit the stream and the Batch batch-size;
    scalars the prologue needs still fail loudly up front."""
    from repro.core.dsl.codegen import CodegenError
    sc, csr, _ = _scenario_bits()
    sess = api.compile(program_path("sssp")).bind(csr, backend="jnp")
    with pytest.raises(CodegenError, match="src"):
        sess.run("DynSSSP")                  # src missing, stream omitted
    sess.run("DynSSSP", src=sc.src)          # batchSize omittable: armed
    assert sess.armed


def test_failed_run_leaves_armed_loop_intact():
    """A one-shot run that raises (bad args) must not disarm a live
    Batch loop — later applies keep doing algorithmic repair."""
    from repro.core.dsl.codegen import CodegenError
    sc, csr, ref = _scenario_bits("batch8")
    sess = api.compile(program_path("sssp")).bind(
        csr, backend="jnp", capacity=sc.diff_capacity)
    sess.run("DynSSSP", src=sc.src, batchSize=sc.batch_size)
    with pytest.raises(CodegenError):
        sess.run("DynSSSP", updateBatch=sc.stream,
                 batchSize=sc.batch_size, src=sc.src, bogus=1)
    assert sess.armed
    for batch in sc.stream.batches(sc.batch_size):
        sess.apply(batch)
    np.testing.assert_array_equal(_as_oracle(sess.props["dist"]), ref)


def test_apply_without_arm_is_structural():
    """apply on a DSL session with nothing armed falls back to the
    structural path (graph updated, no algorithm state)."""
    sc, csr, _ = _scenario_bits()
    sess = api.compile(program_path("sssp")).bind(
        csr, backend="jnp", capacity=sc.diff_capacity)
    assert not sess.armed
    batch = sc.stream.batch(0, sc.batch_size)
    sess.apply(batch)
    from repro.graph import diffcsr
    used = int(np.asarray(diffcsr.pool_counters(sess.handle))[1])
    assert used > 0 or sc.stream.num_adds == 0
