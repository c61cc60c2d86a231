"""The control, the reference computed in a lower precision in the program's
place, fails the comparison that decides ``correct``."""
import numpy as np
import pytest

import gen
import manifest
import reference

CELLS = ("sssp-kron18-mixed", "pr-kron18-mixed")


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 3, 99])
@pytest.mark.parametrize("graph_seed", [19, 5])
def test_control_fails(name, seed, graph_seed):
    cell = manifest.Cell(name)
    cfg = {**cell.config, "scale": 12, "graph_seed": graph_seed}
    if "src" in cfg["args"]:
        cfg["args"] = {**cfg["args"], "src": gen.search_key(cfg)}
    traffic = {**cell.traffic, "batch": 512, "inserts": 512, "deletes": 512,
               "stream_batches": 8}
    n, e, w, s, perm = gen.make_inputs(cfg, traffic, seed)
    e2, w2 = reference.edges_after_updates(e, w, *s.net(6))
    prog = cell.program
    args = prog.run_args(cfg, perm)
    ref = prog.reference_props(n, e2, w2, args)
    ctl = prog.reference_props(n, e2, w2, args, control=True)
    checks = prog.compare(ctl, ref, e2, w2, args)
    limits = cfg["limits"]
    assert any(v > limits[c] for c, v in checks.items()), checks
