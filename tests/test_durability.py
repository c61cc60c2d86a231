"""Durable + elastic sessions: save/restore roundtrips through the
public api surface, the ``run_elastic_session`` tear-down → restore
loop, and the dist backend's elastic re-mesh (restore onto a different
device count, in a subprocess since jax pins the host device count at
first init).

The cross-backend and per-backend bit-exactness cells live in the
test_conformance_*.py files; this file covers the session *mechanics*:
cursor bookkeeping, armed-frame serialization, step selection,
engine-option guards, and the elastic retry loop.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax

import repro.api as api
from repro.algos import oracles, sssp as hand_sssp
from repro.ckpt import checkpoint as ckpt
from repro.dsl_programs import path as program_path
from repro.graph import build_csr, random_updates
from repro.launch.elastic import run_elastic_session

from conftest import random_digraph


def _scenario(batch_size=4):
    n, csr, edges, w = random_digraph(n=40, deg=4, seed=21, max_w=60)
    stream = random_updates(csr, percent=15, seed=8)
    return n, csr, edges, w, stream, list(stream.batches(batch_size))


# ---------------------------------------------------------------------------
# GraphSession (hand-staged) roundtrip
# ---------------------------------------------------------------------------

def test_graphsession_roundtrip_bit_exact(tmp_path):
    n, csr, _, _, stream, batches = _scenario()
    sess = api.bind_graph(csr, backend="jnp", capacity=64)
    props0 = sess.call(hand_sssp.static_sssp, 0)
    sess.run_stream(stream, 4, hand_sssp.stream_step, props0)
    assert sess.stream_cursor == len(batches)
    sess.save(tmp_path)

    res = api.restore_session(tmp_path)
    assert type(res) is api.GraphSession      # no program in the manifest
    assert res.stream_cursor == len(batches)
    np.testing.assert_array_equal(np.asarray(res.props.host("dist")),
                                  np.asarray(sess.props.host("dist")))
    # the resident handle itself roundtrips bit-exactly, pool layout and
    # tombstones included
    t1, m1 = sess._engine.pack_state(sess._handle)
    t2, m2 = res._engine.pack_state(res._handle)
    assert m1 == m2
    l1 = jax.tree_util.tree_leaves(t1)
    l2 = jax.tree_util.tree_leaves(t2)
    assert len(l1) == len(l2)
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_explicit_and_latest_step(tmp_path):
    _, csr, _, _, _, batches = _scenario()
    sess = api.bind_graph(csr, backend="jnp", capacity=64)
    sess.call(hand_sssp.static_sssp, 0)
    sess.apply(batches[0])
    sess.save(tmp_path, keep=5)               # step 1
    sess.apply(batches[1])
    sess.save(tmp_path, keep=5)               # step 2
    assert ckpt.latest_step(tmp_path) == 2

    old = api.restore_session(tmp_path, step=1)
    assert old.stream_cursor == 1
    new = api.restore_session(tmp_path)
    assert new.stream_cursor == 2


def test_restore_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        api.restore_session(tmp_path)


def test_pallas_block_mismatch_refused(tmp_path):
    """Raw ELL leaves are only valid at the k they were packed with —
    restoring onto a pallas engine with a different k must fail loudly,
    not silently mis-index lanes."""
    _, csr, _, _, _, batches = _scenario()
    sess = api.bind_graph(csr, backend="pallas", capacity=64)
    sess.call(hand_sssp.static_sssp, 0)
    sess.apply(batches[0])
    sess.save(tmp_path)
    with pytest.raises(ValueError, match="k"):
        api.restore_session(tmp_path, backend="pallas", k=16)


# ---------------------------------------------------------------------------
# Armed Session: epilogue value + program identity
# ---------------------------------------------------------------------------

def test_armed_restore_preserves_epilogue_value(tmp_path):
    """DynTC returns its count from the epilogue: a restored armed
    session must evaluate .value exactly like the uninterrupted one."""
    from conformance import sym_scenario
    sc = sym_scenario("sym_batch16")
    csr = build_csr(sc.n, sc.edges, sc.w)
    batches = list(sc.stream.batches(sc.batch_size))
    k = max(1, len(batches) // 2)

    ref = api.compile(program_path("tc")).bind(
        csr, backend="jnp", capacity=sc.diff_capacity)
    ref.run("DynTC", batchSize=sc.batch_size)
    for b in batches:
        ref.apply(b)
    want = int(ref.value)

    sess = api.compile(program_path("tc")).bind(
        csr, backend="jnp", capacity=sc.diff_capacity)
    sess.run("DynTC", batchSize=sc.batch_size)
    for b in batches[:k]:
        sess.apply(b)
    sess.save(tmp_path)
    del sess

    res = api.restore_session(tmp_path)
    assert isinstance(res, api.Session) and res.armed
    for b in batches[k:]:
        res.apply(b)
    assert int(res.value) == want
    e2, _ = oracles.edges_after_updates(sc.n, sc.edges, sc.w,
                                        sc.stream.adds, sc.stream.dels)
    assert want == oracles.tc_oracle(sc.n, e2)


def test_armed_restore_after_single_batch(tmp_path):
    """Kill-after-first-batch: the deserialized frame must carry the
    armed batchSize and per-vertex props so the remaining applies land
    on the oracle."""
    n, csr, edges, w, stream, batches = _scenario()
    sess = api.compile(program_path("sssp")).bind(csr, backend="jnp",
                                                  capacity=64)
    sess.run("DynSSSP", batchSize=4, src=0)
    for b in batches[:1]:
        sess.apply(b)
    sess.save(tmp_path)
    del sess

    res = api.restore_session(tmp_path)
    for b in batches[res.stream_cursor:]:
        res.apply(b)
    e2, w2 = oracles.edges_after_updates(n, edges, w, stream.adds,
                                         stream.dels)
    ref = oracles.sssp_oracle(n, e2, w2, 0)
    got = np.minimum(
        np.asarray(res.props.host("dist")).astype(np.int64), oracles.INF)
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# Elastic loop: injected preemption mid-stream, restore, finish
# ---------------------------------------------------------------------------

def test_run_elastic_session_resumes_bit_exact(tmp_path):
    n, csr, edges, w, stream, batches = _scenario()

    ref_sess = api.compile(program_path("sssp")).bind(csr, backend="jnp",
                                                      capacity=64)
    ref_sess.run("DynSSSP", batchSize=4, src=0)
    for b in batches:
        ref_sess.apply(b)
    ref = np.asarray(ref_sess.props.host("dist"))

    crash = {"armed": True}

    def make_session(attempt):
        if attempt == 0:
            s = api.compile(program_path("sssp")).bind(
                csr, backend="jnp", capacity=64)
            s.run("DynSSSP", batchSize=4, src=0)
            return s
        return api.restore_session(tmp_path)

    def work(sess):
        for i, b in enumerate(batches):
            if i < sess.stream_cursor:
                continue               # already applied before the kill
            sess.apply(b)
            sess.save(tmp_path)
            if i == 1 and crash["armed"]:
                crash["armed"] = False
                raise RuntimeError("injected preemption")
        return np.asarray(sess.props.host("dist"))

    got = run_elastic_session(make_session, work, max_restarts=2)
    assert not crash["armed"], "fault injection never fired"
    np.testing.assert_array_equal(got, ref)


def test_run_elastic_session_gives_up(tmp_path):
    def make_session(attempt):
        return object()

    def work(sess):
        raise RuntimeError("permanent failure")

    with pytest.raises(RuntimeError, match="permanent"):
        run_elastic_session(make_session, work, max_restarts=2)


# ---------------------------------------------------------------------------
# Elastic re-mesh: dist save on P=4, restore on P=2 (different device
# count) — subprocess so the 8-virtual-device jax init stays isolated
# ---------------------------------------------------------------------------

_REMESH_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[2])
    import numpy as np
    from conftest import random_digraph
    import repro.api as api
    from repro.dsl_programs import path as program_path
    from repro.graph import random_updates
    from repro.algos import oracles

    ckpt_dir = sys.argv[3]
    n, csr, edges, w = random_digraph(n=48, deg=4, seed=33, max_w=60)
    stream = random_updates(csr, percent=15, seed=11)
    batches = list(stream.batches(8))
    k = max(1, len(batches) // 2)

    # uninterrupted single-backend reference on jnp
    ref = api.compile(program_path("sssp")).bind(csr, backend="jnp",
                                                 capacity=64)
    ref.run("DynSSSP", batchSize=8, src=0)
    for b in batches:
        ref.apply(b)
    want = np.asarray(ref.props.host("dist"))

    # save armed mid-stream on a 4-shard mesh
    sess = api.compile(program_path("sssp")).bind(
        csr, backend="dist", capacity=64, num_shards=4)
    sess.run("DynSSSP", batchSize=8, src=0)
    for b in batches[:k]:
        sess.apply(b)
    sess.save(ckpt_dir)
    del sess

    # "two hosts died": restore onto a 2-shard mesh and finish
    res = api.restore_session(ckpt_dir, backend="dist", num_shards=2)
    assert res.armed and res.stream_cursor == k
    for b in batches[k:]:
        res.apply(b)
    got = np.asarray(res.props.host("dist"))
    np.testing.assert_array_equal(got, want)

    e2, w2 = oracles.edges_after_updates(n, edges, w, stream.adds,
                                         stream.dels)
    np.testing.assert_array_equal(
        np.minimum(got.astype(np.int64), oracles.INF),
        oracles.sssp_oracle(n, e2, w2, 0))
    print("REMESH-OK")
""")


@pytest.mark.slow
def test_dist_elastic_remesh_4_to_2(tmp_path, time_limit):
    here = pathlib.Path(__file__).resolve()
    src = str(here.parents[1] / "src")
    script = tmp_path / "remesh.py"
    script.write_text(_REMESH_SUBPROC)
    r = subprocess.run(
        [sys.executable, str(script), src, str(here.parent),
         str(tmp_path / "ckpt")],
        capture_output=True, text=True, timeout=time_limit - 10,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "REMESH-OK" in r.stdout, r.stdout + r.stderr
