import pathlib
import signal
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import numpy as np
import pytest

from repro.graph import build_csr
from repro.algos import oracles

# Every test runs under a time limit of its own: SIGALRM raises
# TimeoutError in the test once it has run TIME_LIMIT_S seconds (xdist
# workers run tests on their main thread, where the signal lands).  The
# slowest test takes about two minutes on a CPU worker beside five
# others, so the limit is three times that; a test that needs another
# limit carries ``@pytest.mark.timeout(seconds)``.  A test that starts a
# subprocess derives that child's own timeout from the ``time_limit``
# fixture.
TIME_LIMIT_S = 360


@pytest.fixture(autouse=True)
def time_limit(request):
    marker = request.node.get_closest_marker("timeout")
    limit = marker.args[0] if marker else TIME_LIMIT_S

    def expire(signum, frame):
        raise TimeoutError(f"test ran past its {limit} s time limit")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield limit
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


def random_digraph(n=60, deg=4, seed=3, max_w=100):
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, size=(n * deg, 2)).astype(np.int64)
    edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.integers(1, max_w, size=edges.shape[0]).astype(np.int32)
    csr = build_csr(n, edges, w)
    edges = np.stack([np.asarray(csr.src), np.asarray(csr.dst)], 1) \
        .astype(np.int64)
    return n, csr, edges, np.asarray(csr.w)


def random_symgraph(n=40, m=160, seed=0):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2))
    e = e[e[:, 0] != e[:, 1]]
    e, w = oracles.symmetrize(e, np.ones(len(e), np.int32))
    csr = build_csr(n, e)
    edges = np.stack([np.asarray(csr.src), np.asarray(csr.dst)], 1) \
        .astype(np.int64)
    return n, csr, edges


def sym_stream(csr, percent, seed):
    """Symmetric update stream with paired directions in the same batch."""
    from repro.graph import random_updates
    from repro.graph.updates import UpdateStream
    ups = random_updates(csr, percent=percent, seed=seed)
    adds, dels = ups.adds, ups.dels
    adds = np.stack([adds, adds[:, [1, 0, 2]]], axis=1).reshape(-1, 3)
    dels = np.stack([dels, dels[:, [1, 0]]], axis=1).reshape(-1, 2)
    return UpdateStream(adds=adds, dels=dels)
