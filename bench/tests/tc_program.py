"""DynTC (``tc.sp``) read through its returned count: a program module of
the harness's contract (``manifest.py``) with ``PROPS`` empty and
``VALUE`` set, kept beside the tests that drive it.

The answer is the scalar ``triangle_count`` that ``DynTC`` returns.  One
number is compared: ``triangles_wrong``, the distance of the served count
from ``trace(A³) / 6`` of the dense adjacency matrix of the reference
edge set (each undirected edge held both ways).
"""
from __future__ import annotations

import numpy as np

PROPS = ()
VALUE = "triangle_count"


def run_args(cfg: dict, perm) -> dict:
    """The Dynamic function's arguments (none names a vertex)."""
    return dict(cfg["args"])


def reference_props(n: int, edges, weights, args: dict, control=False):
    a = np.zeros((n, n))
    a[edges[:, 0], edges[:, 1]] = 1.0
    return {VALUE: int(round(np.trace(a @ a @ a))) // 6}


def compare(got: dict, ref: dict, edges, weights, args: dict) -> dict:
    return {"triangles_wrong": abs(int(got[VALUE]) - ref[VALUE])}
