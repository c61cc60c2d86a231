"""The trace reduction: busy time is the union of device op intervals in
the window the ``bench.*`` spans mark, and each idle gap is labelled with
the span open on the host."""
import os

import pytest

import trace

MS = 1_000_000


def test_union_merges_overlaps_and_clips_to_the_window():
    u = trace.union([("a", 0, 5), ("b", 3, 8), ("c", 10, 12), ("d", 11, 30)],
                    2, 20)
    assert u.tolist() == [[2, 8], [10, 20]]


def test_reduce_synthetic_window():
    ops = {"/device:TPU:0": [("while.1", 10 * MS, 60 * MS),
                             ("fusion.1", 10 * MS, 40 * MS),
                             ("fusion.2", 40 * MS, 55 * MS),
                             ("scatter", 80 * MS, 100 * MS)]}
    spans = [("bench.batch", 0, 10 * MS), ("bench.apply", 10 * MS, 90 * MS),
             ("bench.readback", 90 * MS, 100 * MS)]
    r = trace.reduce(ops, spans)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.07)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.03, "fusion.2": 0.015, "scatter": 0.02,
         "while.1": 0.005})
    assert r["idle_gaps"] == [["apply", pytest.approx(0.02)],
                              ["batch", pytest.approx(0.01)]]


def test_reduce_finds_nothing_without_device_ops_or_spans():
    assert trace.reduce({}, [("bench.apply", 0, 1)]) is None
    assert trace.reduce({"/device:TPU:0": [("x", 0, 1)]}, []) is None


def test_reduce_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three rounds of batch / apply
    (a segment_min and a cumsum, 2 ms of host sleep) / readback."""
    ops, spans = trace.read(os.path.join(os.path.dirname(__file__), "data",
                                         "tpu_small.xplane.pb.gz"))
    assert list(ops) == ["/device:TPU:0"]
    assert [s[0] for s in spans].count("bench.apply") == 3
    r = trace.reduce(ops, spans)
    assert 0 < r["busy_s"] < r["window_s"] < 0.1
    top, secs = r["device_ops"][0]
    assert top == "jit__lambda/fusion fusion f32[4096]"
    assert secs == pytest.approx(0.0017173560, rel=1e-6)
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
    assert {g[0] for g in r["idle_gaps"]} <= {"batch", "apply", "readback"}
    assert r["idle_gaps"][0][0] == "batch"


def test_short_names():
    assert trace.short_name(
        "%fusion.42 = s32[16131072]{0:T(1024)} fusion(s32[1048576]{0} %a)"
    ) == "fusion.42 fusion s32[16131072]"
    assert trace.short_name(
        "%while.2 = (s32[], pred[4]{0}) while((s32[], pred[4]{0}) %t)"
    ) == "while.2 while tuple"
