"""Reduce a JAX profiler trace (``*.xplane.pb``) to the traced run's numbers.

The window is the stretch from the start of the first ``bench.*`` host
span to the end of the last one.  Busy time is the union of the device's
op intervals inside it (the ``XLA Ops`` line of each TPU plane), averaged
over the cell's chips.  Each op is charged its self time (a ``while``
op's body ops lie inside it on the same line) under a short name,
``<module>/<op> <opcode> <shape>``.  Idle gaps are what the union leaves
on the first chip, each labelled with the ``bench.*`` span that was open
on the host at its midpoint.
"""
from __future__ import annotations

import collections
import glob
import gzip
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TOP = 10


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(hlo: str) -> str:
    """``fusion.42 fusion s32[16131072]`` from an op's HLO text."""
    head, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:80]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, tail = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
        shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{head.lstrip('%')} {tail.split('(', 1)[0]} {shape}"


def read(path: str, chips: int = 1):
    """(device ops {plane: [(name, start_ns, end_ns)]} of TPUs 0 to
    ``chips`` - 1, host spans [(name, start_ns, end_ns)]) from one trace
    file (``.gz`` allowed)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev and int(dev.group(1)) < chips:
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.name.split("(")[0])
                          for e in lines.get(MODULES_LINE, []))
            starts = [s for s, _ in mods]
            evs = []
            for e in lines.get(OPS_LINE, []):
                i = np.searchsorted(starts, e.start_ns, side="right") - 1
                mod = mods[i][1] + "/" if i >= 0 else ""
                evs.append((mod + short_name(e.name), e.start_ns,
                            e.start_ns + e.duration_ns))
            ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return ops, spans


def union(intervals, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted (k, 2) intervals covering ``intervals`` clipped to
    [lo, hi]."""
    iv = np.asarray([(max(s, lo), min(e, hi)) for _, s, e in intervals],
                    np.float64).reshape(-1, 2)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    last = np.flatnonzero(np.r_[new[1:], True])
    return np.stack([iv[new, 0], ends[last]], axis=1)


def self_times(evs, lo: float, hi: float) -> collections.Counter:
    """Each op's time inside [lo, hi] less that of the ops nested in it."""
    out = collections.Counter()
    stack = []                      # [name, end, self time]
    for name, s, e in sorted(evs, key=lambda t: (t[1], -t[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            n, _, t = stack.pop()
            out[n] += t
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    for n, _, t in stack:
        out[n] += t
    return out


def reduce(ops: dict, spans: list) -> dict | None:
    """busy_s, window_s, device_ops and idle_gaps of one traced window, or
    None where the trace holds no device op or no ``bench.*`` span."""
    if not spans or not any(ops.values()):
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy, per_op = [], collections.Counter()
    first = min(ops)
    for plane, evs in ops.items():
        u = union(evs, lo, hi)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0)
        per_op.update(self_times(evs, lo, hi))
        if plane == first:
            gaps = _gaps(u, lo, hi)
    spans = sorted(spans, key=lambda t: t[1])
    labelled = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [n for n, a, b in spans if a <= mid < b]
        label = open_[-1][len(SPAN_PREFIX):] if open_ else "outside"
        labelled.append([label, (e - s) * 1e-9])
    labelled.sort(key=lambda t: -t[1])
    return {
        "busy_s": float(np.mean(busy)) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, t * 1e-9 / len(ops)]
                       for n, t in per_op.most_common(TOP)],
        "idle_gaps": labelled[:TOP],
    }


def _gaps(u: np.ndarray, lo: float, hi: float):
    edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    return [(s, e) for s, e in edges if e > s]
