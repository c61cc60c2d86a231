"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the root of the checkout names everything; each
piece is a file of its own under ``bench/``:

  configs/<config>.json   the deployment: graph, program, arguments,
                          the limits of the comparison that decides
                          ``correct``; its reference is ``programs/<check>.py``
  programs/<check>.py     what a run reads back of the program, and its
                          reference and comparison (below)
  traffic/<traffic>.json  the ΔG stream's parameters, read by ``gen.py``
  metrics/<metric>.py     ``read(obs) -> float | None`` for one metric
  peaks.json              the devices the benchmark knows, by device_kind

A program module provides:

  PROPS                   names of the vertex properties that make the
                          answer; blocked on after every batch, read back
                          after the window
  VALUE                   optional: the name of the Dynamic function's
                          returned value (``Session.value``), blocked on
                          with ``PROPS`` after every batch and read back
                          once after the window as a numpy scalar; where
                          it is set, ``PROPS`` may be empty
  run_args(cfg, perm)     the Dynamic function's arguments, the vertices
                          named in them renamed by the run's permutation
  reference_props(n, edges, weights, args, control=False)
                          the reference answer on the reference edge set,
                          by the same names (``control``: one precision
                          down, for ``control.py``)
  compare(got, ref, edges, weights, args)
                          ``{number: reading}``, each held to the limit of
                          the same name in the configuration; ``got`` maps
                          each name of ``PROPS`` and ``VALUE`` to its
                          read-back

So a later cell is a ``workloads`` entry plus files, and no edit.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One ``workloads`` entry with everything it names, loaded."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {', '.join(sorted(cells))})")
        self.name = name
        self.entry = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(ROOT / configs[self.entry["config"]]["file"])
        self.traffic = load_json(BENCH / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.program = load_module(BENCH / "programs"
                                   / f"{self.config['check']}.py")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m, name)]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    @staticmethod
    def reader(metric: dict):
        return load_module(BENCH / "metrics" / f"{metric['name']}.py")


def peaks() -> dict:
    return load_json(BENCH / "peaks.json")["devices"]
