"""DynSSSP (``sssp.sp``): what is read back, reference and comparison.

The configuration states exact int32 distances.  Three numbers are
compared, each with the limit the configuration gives (all 0):
``dist_wrong`` vertices whose distance differs from Bellman-Ford on the
reference edge set, ``parent_bad`` reachable vertices whose parent is not
a tight in-edge (ties may pick any such parent), and the harness's
``edges_wrong``.  The control runs the same Bellman-Ford with int16
distances, the step below the stated int32, saturating at 32767: the
weights are Graph500's reals held as 20-bit fixed point, so a distance
needs more than 16 bits.
"""
from __future__ import annotations

import numpy as np

import reference

PROPS = ("dist", "parent")


def run_args(cfg: dict, perm) -> dict:
    """The Dynamic function's arguments, the source renamed as the run's
    vertices are."""
    return {**cfg["args"], "src": int(perm[cfg["args"]["src"]])}


def reference_props(n: int, edges, weights, args: dict, control=False):
    dtype = np.int16 if control else np.int64
    return {"dist": reference.sssp(n, edges, weights, args["src"],
                                   dtype=dtype)}


def compare(got: dict, ref: dict, edges, weights, args: dict) -> dict:
    dist = np.minimum(np.asarray(got["dist"]).astype(np.int64),
                      reference.INF)
    out = {"dist_wrong": int((dist != ref["dist"]).sum())}
    parent = got.get("parent")
    if parent is not None:
        out["parent_bad"] = _bad_parents(dist, np.asarray(parent), edges,
                                         weights, args["src"])
    return out


def _bad_parents(dist, parent, edges, weights, src: int) -> int:
    """Reachable vertices other than ``src`` whose parent edge is missing
    or does not close its distance exactly."""
    n = len(dist)
    v = np.flatnonzero((dist < reference.INF) & (np.arange(n) != src))
    p = parent[v].astype(np.int64)
    ok = (p >= 0) & (p < n)
    keys = edges[:, 0].astype(np.int64) * n + edges[:, 1]
    q = np.where(ok, p, 0) * n + v
    pos = np.minimum(np.searchsorted(keys, q), len(keys) - 1)
    ok &= keys[pos] == q
    ok &= dist[np.where(ok, p, 0)] + weights[pos].astype(np.int64) == dist[v]
    return int((~ok).sum())
