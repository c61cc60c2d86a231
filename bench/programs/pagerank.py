"""DynPR (``pagerank.sp``): what is read back, reference and comparison.

The configuration states float32 ranks.  Two numbers are compared with a
float64 power iteration on the reference edge set, run with the same
beta, delta and iteration cap: ``rank_l1``, the L1 distance of the served
ranks, and ``top_rank_rel``, the largest error of one of the ``TOP``
highest reference ranks over that rank.  The top ranks are the answers
users read, and one of them altered moves ``top_rank_rel`` where it moves
the L1 distance by less than the sound runs' own.  Their limits are set
in the configuration from the program's readings and the control's.
The control runs the same power iteration in bfloat16.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

import reference

PROPS = ("pageRank",)
TOP = 100


def run_args(cfg: dict, perm) -> dict:
    """The Dynamic function's arguments (none names a vertex)."""
    return dict(cfg["args"])


def reference_props(n: int, edges, weights, args: dict, control=False):
    dtype = ml_dtypes.bfloat16 if control else np.float64
    return {"pageRank": reference.pagerank(n, edges, args["beta"],
                                           args["delta"], args["maxIter"],
                                           dtype=dtype)}


def compare(got: dict, ref: dict, edges, weights, args: dict) -> dict:
    want = ref["pageRank"]
    err = np.abs(np.asarray(got["pageRank"], np.float64) - want)
    top = np.argsort(want)[-TOP:]
    return {"rank_l1": float(err.sum()),
            "top_rank_rel": float((err[top] / want[top]).max())}
