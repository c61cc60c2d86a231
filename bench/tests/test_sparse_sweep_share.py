"""The ``sparse_sweep_share`` reader: sparse iterations over all
iterations in the window, and nothing where the session does not count
them."""
import manifest

READER = manifest.Cell.reader({"name": "sparse_sweep_share"})


def _obs(h0, h1):
    return {"health": (h0, h1)}


def test_share_of_the_window_s_iterations():
    h0 = {"sweeps_dense": 10, "sweeps_sparse": 4, "pool_grows": 0}
    h1 = {"sweeps_dense": 13, "sweeps_sparse": 13, "pool_grows": 0}
    assert READER.read(_obs(h0, h1)) == 75.0


def test_zero_where_every_iteration_was_dense():
    h0 = {"sweeps_dense": 5, "sweeps_sparse": 2}
    h1 = {"sweeps_dense": 9, "sweeps_sparse": 2}
    assert READER.read(_obs(h0, h1)) == 0.0


def test_none_without_the_counters_or_without_iterations():
    assert READER.read(_obs({"pool_grows": 0}, {"pool_grows": 1})) is None
    same = {"sweeps_dense": 3, "sweeps_sparse": 1}
    assert READER.read(_obs(same, dict(same))) is None
