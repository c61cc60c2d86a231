"""BENCHMARK.json names files, and the harness finds each one by name."""
import json
import os

import manifest

BENCH = json.load(open(manifest.ROOT / "BENCHMARK.json"))


def test_every_cell_finds_its_config_traffic_program_and_metrics():
    for w in BENCH["workloads"]:
        cell = manifest.Cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["batch"] >= max(cell.traffic["inserts"],
                                            cell.traffic["deletes"])
        assert callable(cell.program.run_args)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(manifest.Cell.reader(m).read)


def test_each_metric_has_a_reader_and_each_reader_a_metric():
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    files = {f[:-3] for f in os.listdir(manifest.BENCH / "metrics")
             if f.endswith(".py")}
    assert names == files


def test_config_files_hold_the_limits_of_their_checks():
    for c in BENCH["configs"]:
        cfg = json.load(open(manifest.ROOT / c["file"]))
        assert cfg["name"] == c["name"]
        assert "edges_wrong" in cfg["limits"]
        for key in c["reduced"]:
            assert key in cfg["changed_from_source"]


def test_unknown_workload_is_refused():
    try:
        manifest.Cell("no-such-cell")
    except KeyError as e:
        assert "no-such-cell" in str(e)
    else:
        raise AssertionError("an unknown workload was accepted")


def test_readers_return_nothing_when_there_is_nothing_to_read():
    obs = {"setup_s": 1.0, "window_s": 2.0, "batches": 0, "updates": 0,
           "peak_bytes": None, "events": {}, "trace": None,
           "health": ({"pool_grows": 0}, {"pool_grows": 0})}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        v = manifest.Cell.reader(m).read(obs)
        assert v is None or m["name"] in ("setup_s", "pool_grows"), m
