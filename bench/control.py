"""The control of a cell's comparison: the reference, computed in a lower
precision, in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --batches 10

For each seed it builds the cell's graph and stream at the cell's own
size, applies the first ``--batches`` stream batches to the reference edge
set, and compares the program module's control (``reference_props(...,
control=True)``) with the reference, as a run compares the program.  It
prints one JSON line per seed with each number and its limit; the limit
has to fail on every seed.  The benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gen  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402


def readings(cell: manifest.Cell, seed: int, batches: int) -> dict:
    n, e, w, s, perm = gen.make_inputs(cell.config, cell.traffic, seed)
    args = cell.program.run_args(cell.config, perm)
    e2, w2 = reference.edges_after_updates(e, w, *s.net(batches))
    ref = cell.program.reference_props(n, e2, w2, args)
    ctl = cell.program.reference_props(n, e2, w2, args, control=True)
    return cell.program.compare(ctl, ref, e2, w2, args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--batches", type=int, default=10)
    args = ap.parse_args(argv)
    cell = manifest.Cell(args.workload)
    limits = cell.config["limits"]
    fails = True
    for seed in map(int, args.seeds.split(",")):
        got = readings(cell, seed, args.batches)
        failed = any(limits[c] is None or v > limits[c]
                     for c, v in got.items())
        fails &= failed
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_failed": failed,
                          "checks": {c: {"value": v, "limit": limits[c]}
                                     for c, v in got.items()}}), flush=True)
    return 0 if fails else 1


if __name__ == "__main__":
    sys.exit(main())
