"""Backend equivalence: the same algorithm spec must produce identical
results on all three lowerings (the paper's core claim, §4).

DistEngine runs in-process over however many devices exist (1 on plain
CPU); an 8-virtual-device sweep runs in a subprocess since jax locks the
device count at first init.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import random_digraph, random_symgraph, sym_stream
from repro.graph import random_updates
from repro.core.engine import JnpEngine
from repro.core.dist import DistEngine
from repro.core.pallas_engine import PallasEngine
from repro.core.frontier_engine import FrontierEngine
from repro.algos import sssp, pagerank, triangles, oracles

# the dist cells run in the full lane, while conformance keeps a fast
# dist cell per program.
ENGINES = [JnpEngine,
           pytest.param(DistEngine, marks=pytest.mark.slow),
           PallasEngine, FrontierEngine]


@pytest.mark.parametrize("engine_cls", ENGINES, ids=lambda c: c.name)
def test_sssp_all_backends(engine_cls):
    n, csr, edges, w = random_digraph(seed=11)
    eng = engine_cls()
    g = eng.prepare(csr, diff_capacity=64)
    ups = random_updates(csr, percent=15, seed=2)
    _, props = sssp.dyn_sssp(eng, g, 0, ups, batch_size=8)
    e2, w2 = oracles.edges_after_updates(n, edges, w, ups.adds, ups.dels)
    ref = oracles.sssp_oracle(n, e2, w2, 0)
    got = np.minimum(np.asarray(props["dist"]).astype(np.int64)[:n],
                     oracles.INF)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("engine_cls", ENGINES, ids=lambda c: c.name)
def test_pr_all_backends(engine_cls):
    n, csr, edges, w = random_digraph(seed=12)
    eng = engine_cls()
    g = eng.prepare(csr, diff_capacity=64)
    ups = random_updates(csr, percent=10, seed=3)
    _, props = pagerank.dyn_pr(eng, g, ups, batch_size=8)
    e2, _ = oracles.edges_after_updates(n, edges, w, ups.adds, ups.dels)
    ref = oracles.pagerank_oracle(n, e2)
    np.testing.assert_allclose(np.asarray(props["pr"])[:n], ref,
                               rtol=5e-2, atol=1e-4)


@pytest.mark.parametrize("engine_cls", ENGINES, ids=lambda c: c.name)
def test_tc_all_backends(engine_cls):
    n, csr, edges = random_symgraph(seed=4)
    eng = engine_cls()
    g = eng.prepare(csr, diff_capacity=256)
    ups = sym_stream(csr, percent=15, seed=6)
    _, c = triangles.dyn_tc(eng, g, ups, batch_size=16)
    e2, _ = oracles.edges_after_updates(
        n, edges, np.ones(len(edges), np.int32), ups.adds, ups.dels)
    assert int(c) == oracles.tc_oracle(n, e2)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[2])
    import numpy as np
    from conftest import random_digraph, random_symgraph, sym_stream
    from repro.graph import random_updates
    from repro.core.dist import DistEngine
    from repro.algos import sssp, pagerank, triangles, oracles
    import jax
    assert len(jax.devices()) == 8

    n, csr, edges, w = random_digraph(seed=21)
    eng = DistEngine()
    assert eng.P == 8
    g = eng.prepare(csr, diff_capacity=64)
    ups = random_updates(csr, percent=15, seed=2)
    _, props = sssp.dyn_sssp(eng, g, 0, ups, batch_size=8)
    e2, w2 = oracles.edges_after_updates(n, edges, w, ups.adds, ups.dels)
    ref = oracles.sssp_oracle(n, e2, w2, 0)
    got = np.minimum(np.asarray(props["dist"]).astype(np.int64)[:n],
                     oracles.INF)
    assert np.array_equal(got, ref), "dist-8dev SSSP mismatch"

    n, csr, edges = random_symgraph(seed=4)
    eng = DistEngine()
    g = eng.prepare(csr, diff_capacity=256)
    ups = sym_stream(csr, percent=15, seed=6)
    _, c = triangles.dyn_tc(eng, g, ups, batch_size=16)
    e2, _ = oracles.edges_after_updates(
        n, edges, np.ones(len(edges), np.int32), ups.adds, ups.dels)
    assert int(c) == oracles.tc_oracle(n, e2), "dist-8dev TC mismatch"
    print("8DEV-OK")
""")


@pytest.mark.slow
def test_dist_8_virtual_devices(tmp_path, time_limit):
    import pathlib
    here = pathlib.Path(__file__).resolve()
    src = str(here.parents[1] / "src")
    script = tmp_path / "run8.py"
    script.write_text(_SUBPROC)
    r = subprocess.run(
        [sys.executable, str(script), src, str(here.parent)],
        capture_output=True, text=True, timeout=time_limit - 10,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "8DEV-OK" in r.stdout, r.stdout + r.stderr
