"""A program module that names a returned value (``VALUE``): the harness
blocks on it every batch, reads it back after the window and compares it.
``DynTC``'s triangle count, on the CPU at a small size."""
import pytest

import manifest
import test_faults

TC = manifest.load_module(manifest.BENCH / "tests" / "tc_program.py")


def tc_cell():
    """The small SSSP cell's graph and stream under ``DynTC``, read
    through ``tc_program.py``."""
    cell = test_faults.small_cell("sssp-kron18-mixed")
    cell.config = {**cell.config, "program": "tc", "function": "DynTC",
                   "args": {}, "check": "tc_program",
                   "limits": {"edges_wrong": 0, "triangles_wrong": 0}}
    cell.program = TC
    return cell


def run_tc(fault=None):
    return test_faults.run_small(None, fault=fault, cell=tc_cell())


def count_off_by_one():
    """The count raised by one where the armed program keeps it, after
    the first batch; later batches add their changes to it."""
    done = []

    def fault(sess, batch):
        sess.apply(batch)
        if not done:
            env = sess._armed.frame.env
            env[TC.VALUE] = env[TC.VALUE] + 1
            done.append(True)
    return fault


def test_returned_value_is_blocked_on_read_and_correct(monkeypatch):
    import repro.api
    reads = []
    value = repro.api.Session.value
    monkeypatch.setattr(repro.api.Session, "value", property(
        lambda s: reads.append(1) or value.fget(s)))
    res = run_tc()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == {"edges_wrong", "triangles_wrong"}
    assert set(res["metrics"]) == {"updates_per_s", "setup_s"}
    # the prologue, every warm-up and window batch, and the read-back
    assert len(reads) >= res["attempted"] + 3


@pytest.mark.parametrize("fault", ["off_by_one", "unchanged"])
def test_broken_count_is_not_correct(fault):
    f = {"off_by_one": count_off_by_one(),
         "unchanged": test_faults.unchanged}[fault]
    res = run_tc(fault=f)
    assert not res["correct"], res["checks"]
    assert res["checks"]["triangles_wrong"]["value"] > 0
