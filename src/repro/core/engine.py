"""Execution engines: one algorithm spec → three backends.

This is the paper's code-generator layer.  The table in DESIGN.md §2 maps
StarPlat's OpenMP / MPI / CUDA generators to:

  * :class:`JnpEngine`   — single-device XLA (OpenMP analogue),
  * ``DistEngine``       — shard_map + collectives (MPI analogue,
                           see core/dist.py),
  * ``PallasEngine``     — hand-tiled TPU kernels for the hot loops
                           (CUDA analogue, see core/pallas_engine.py).

All three consume the same :class:`repro.core.ir.EdgeSweep` programs; the
algorithms in ``repro.algos`` never mention a backend.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.ir import EdgeSweep, Reduce
from repro.graph.csr import CSR, INT, INF_W, build_csr
from repro.graph import diffcsr
from repro.graph.diffcsr import DynGraph, BOOL
from repro.graph.updates import UpdateBatch
from repro.runtime import faults as _faults

Props = Dict[str, jax.Array]

# Guards every engine's per-instance ``_stream_cache`` (compiled stream
# executables): a session pool applies batches from worker threads, and
# an unguarded dict get/compile/set races into duplicate compilations —
# or, interleaved with ``grow``'s eviction sweep, a RuntimeError from
# mutating the dict mid-iteration.  One process-wide lock (not
# per-instance) keeps lazy lock creation itself race-free; the critical
# sections are dict ops only, so contention is negligible.
_STREAM_CACHE_LOCK = threading.Lock()


class Collectives:
    """Global-reduction helpers handed to fixed-point conditions.

    On the single-device backend these are plain jnp reductions; the
    distributed backend overrides them with psum/pmax over the mesh so the
    *same algorithm text* stays correct — the paper's 'same DSL, different
    synchronization per backend' point, in miniature.
    """

    def any(self, x):
        return jnp.any(x)

    def sum(self, x):
        return jnp.sum(x)

    def max(self, x):
        return jnp.max(x)


def edge_lane_flags(g: DynGraph, qs, qd, mask=None) -> jax.Array:
    """Boolean flags over the (E+D,) edge lanes for a batch of edges —
    the propEdge<bool> ``modified`` marking used by OnAdd/OnDelete."""
    qs = jnp.asarray(qs, INT)
    qd = jnp.asarray(qd, INT)
    if mask is None:
        mask = jnp.ones(qs.shape, BOOL)
    E, D = g.main_capacity, g.diff_capacity
    p1, f1 = diffcsr._locate_main(g, qs, qd)
    p2, f2 = diffcsr._locate_diff(g, qs, qd)
    flags = jnp.zeros((E + D,), BOOL)
    flags = flags.at[jnp.where(f1 & mask, p1, E + D)].set(True, mode="drop")
    flags = flags.at[jnp.where(f2 & mask & ~f1, E + p2, E + D)].set(
        True, mode="drop")
    return flags


# Reductions whose result is the same whichever lanes are left out, so
# long as those lanes are ineligible, and whatever order the rest are
# combined in: a frontier-only sweep is then bit-identical to the dense
# one.  A float ``sum`` is neither.
_ORDER_FREE = frozenset({"min", "max", "argmin", "or"})


def sparse_lane_capacity(main_capacity: int) -> int:
    """Edge lanes a frontier-only sweep holds: next_pow2(E / 64), so the
    sparse branch does about a 64th of a dense sweep's lane work."""
    return 1 << max(main_capacity // 64 - 1, 0).bit_length()


def frontier_lanes(g: DynGraph, ends, deg, main_deg, cap: int):
    """The (esrc, edst, ew, ealive) lanes of the frontier's vertices,
    padded to ``cap`` with dead lanes.  ``ends`` is the inclusive
    prefix sum of the frontier's lane counts over the vertices (``deg``
    where a vertex is in the frontier, else 0), and its last entry is
    at most ``cap``.  Reads O(n + cap) elements and no (E+D,) array:
    lane ``j`` belongs to the first vertex whose end exceeds ``j``."""
    n = g.n
    lane = jnp.arange(cap, dtype=INT)
    u = jnp.minimum(jnp.searchsorted(ends, lane, side="right"),
                    n - 1).astype(INT)
    r = lane - (ends[u] - deg[u])
    m_deg = main_deg[u]
    in_main = r < m_deg
    mi = jnp.clip(g.offsets[u] + r, 0, g.main_capacity - 1)
    di = jnp.clip(g.d_offsets[u] + r - m_deg, 0, g.diff_capacity - 1)
    edst = jnp.where(in_main, g.dst[mi], g.d_dst[di])
    ew = jnp.where(in_main, g.w[mi], g.d_w[di])
    ealive = (lane < ends[-1]) & jnp.where(in_main, g.alive[mi],
                                            g.d_alive[di])
    return u, edst, ew, ealive


class _StreamView:
    """Engine facade handed to stream steps inside ``run_stream``.

    Semantics are identical to the wrapped engine; the only difference is
    that ``count_wedges`` runs with host-precomputed static degree bounds
    (``bounds``) so wedge enumeration never syncs to host mid-scan.
    Engines whose interactive paths are host-driven (FrontierEngine's
    direction optimization) subclass this to swap in their jit-safe
    lowering."""

    def __init__(self, engine: "Engine", bounds=None):
        self._engine = engine
        self._bounds = bounds

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def count_wedges(self, handle, pair_fn, lane_flags, out_example,
                     bounds=None):
        return self._engine.count_wedges(
            handle, pair_fn, lane_flags, out_example,
            bounds=bounds if bounds is not None else self._bounds)


class WedgeCtx:
    """Per-iteration context handed to wedge pair functions (TC)."""

    def __init__(self, g: DynGraph, lane_flags: Dict[str, jax.Array],
                 nbr_lane: jax.Array, is_edge_fn, edge_flag_fn):
        self.g = g
        self._lane_flags = lane_flags
        self._nbr_lane = nbr_lane
        self.is_edge = is_edge_fn          # (qs, qd) -> bool lanes
        self.edge_flag = edge_flag_fn      # (name, qs, qd) -> bool lanes

    def nbr_flag(self, name: str) -> jax.Array:
        fl = self._lane_flags[name]
        return fl[jnp.clip(self._nbr_lane, 0, fl.shape[0] - 1)]

    def lane_flag(self, name: str) -> jax.Array:
        return self._lane_flags[name]


class Engine:
    """Backend-neutral interface (the 'generated program' surface)."""

    name = "base"
    # (dense, sparse) fixed-point iterations run so far, on the device;
    # set by engines that count them (JnpEngine.fixed_point), read by
    # the session with its per-batch pool-counter readback.
    sweep_counts: Optional[jax.Array] = None

    # -- construction ------------------------------------------------------
    def prepare(self, csr: CSR, diff_capacity: int) -> Any:
        raise NotImplementedError

    def merge(self, handle) -> Any:
        raise NotImplementedError

    @property
    def n_pad(self) -> int:
        raise NotImplementedError

    @property
    def n_real(self) -> int:
        return self._n

    def out_degrees(self, handle) -> jax.Array:
        raise NotImplementedError

    def full(self, value, dtype) -> jax.Array:
        """Allocate a vertex property (paper: attachNodeProperty)."""
        return jnp.full((self.n_pad,), value, dtype=dtype)

    def read_props(self, props: Props) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)[: self._n] for k, v in props.items()}

    # -- aggregate ops -----------------------------------------------------
    def sweep(self, handle, sw: EdgeSweep, props: Props) -> Props:
        raise NotImplementedError

    def fixed_point(self, handle, sw: EdgeSweep, props: Props,
                    cond_fn: Callable, max_iter: int) -> Props:
        raise NotImplementedError

    def vertex_map(self, handle, fn: Callable, props: Props) -> Props:
        raise NotImplementedError

    def count_wedges(self, handle, pair_fn: Callable,
                     lane_flags: Dict[str, jax.Array], out_example,
                     bounds=None) -> Any:
        raise NotImplementedError

    # -- dynamic updates ---------------------------------------------------
    def update_del(self, handle, batch: UpdateBatch):
        raise NotImplementedError

    def update_add(self, handle, batch: UpdateBatch):
        raise NotImplementedError

    def batch_edge_flags(self, handle, qs, qd, mask) -> jax.Array:
        raise NotImplementedError

    # -- streaming executor (DESIGN.md §3) ---------------------------------
    # A *stream step* is the engine-neutral per-batch body
    #     step_fn(engine, handle, batch, carry) -> (handle, carry)
    # (update → affected-seed → incremental repair).  ``run_stream`` drives
    # a whole padded batch stream through it; engines with a fused path
    # override it with one jitted lax.scan per stream segment, checking
    # the diff-pool counters once per segment instead of once per batch.

    def handle_graph(self, handle) -> DynGraph:
        """The DynGraph inside an engine handle (identity for raw graphs)."""
        return handle

    def handle_counters(self, handle) -> jax.Array:
        """(overflow, used, dead) pool counters, on device."""
        return diffcsr.pool_counters(self.handle_graph(handle))

    def grow(self, handle, factor: float = 2.0):
        """Host-side merge with grown diff capacity — the one remaining
        numpy exit, reserved for true pool overflow."""
        raise NotImplementedError

    def compact_handle(self, handle):
        """Device-side reclamation of tombstoned diff slots."""
        raise NotImplementedError

    def stream_view(self, bounds=None) -> "Engine":
        """The engine facade handed to stream steps (see _StreamView)."""
        return _StreamView(self, bounds)

    # -- durable state (DESIGN.md §5: session durability contract) ---------
    # Every engine exposes its resident handle as a (nested-dict array
    # tree, JSON-able meta) pair.  ``state_kind`` names the tree layout;
    # a same-kind ``unpack_state`` is *bit-exact* (raw leaves restored,
    # pool layout preserved), while a cross-kind restore goes through the
    # module-level ``state_to_csr`` + ``prepare`` (value-preserving, pool
    # layout reset).

    state_kind = "none"

    def pack_state(self, handle) -> Tuple[Dict[str, Any], dict]:
        """Flattenable snapshot of the resident graph handle."""
        raise NotImplementedError

    def unpack_state(self, tree: Dict[str, Any], meta: dict):
        """Rebuild a handle from ``pack_state`` output on THIS engine;
        must also restore the engine's host-side shape state (_n)."""
        raise NotImplementedError

    def put_vertex_array(self, arr) -> jax.Array:
        """Place a restored (n_pad,) vertex property the way this
        engine's lowerings expect it (dist: sharded over the mesh)."""
        return jnp.asarray(arr)

    def static_wedge_bounds(self, handle):
        """Host-static (max_main_deg, max_diff_deg) loop bounds usable
        inside a jitted stream segment.  The main region's offsets only
        change at merge/grow (segment boundaries), so its true max degree
        is static within a segment; the diff region is bounded by its
        capacity."""
        g = self.handle_graph(handle)
        deg = np.asarray(g.offsets[1:] - g.offsets[:-1])
        max_main = int(deg.max()) if deg.size else 0
        return max_main, g.diff_capacity

    def _diff_capacity(self, handle) -> int:
        return self.handle_graph(handle).diff_capacity

    def _handle_shape_key(self, handle) -> tuple:
        """The handle's static capacities (E_cap, D_cap) — the part of a
        compiled stream executable's identity that ``grow`` invalidates."""
        g = self.handle_graph(handle)
        return (g.main_capacity, g.diff_capacity)

    def _evict_stream_cache(self, shape_key: tuple) -> None:
        """Drop compiled stream executables specialized on ``shape_key``.
        Called by ``grow``: the old-capacity executables can never run
        again, so keeping them leaks one per capacity step.  Cache keys
        embed the shape key as a top-level tuple element."""
        cache = getattr(self, "_stream_cache", None)
        if cache:
            with _STREAM_CACHE_LOCK:
                for k in [k for k in cache if shape_key in k]:
                    cache.pop(k, None)

    def _segment_runner(self, step_fn, handle, batch_size: int):
        """Compiled ``(handle, carry, stacked_batches) -> (handle, carry,
        (overflow, used, dead))`` for one fused stream segment."""
        raise NotImplementedError

    def _run_stream_fused(self, handle, stream, batch_size: int, step_fn,
                          carry, segment_size: int, compact_frac: float):
        """Shared fused-stream driver: cut the stream into segments of
        padded batches, run each through ``_segment_runner`` (one
        compiled scan — no host round-trips between batches), and once
        per segment read back the pool counters: overflow rolls the
        segment back, grows capacity host-side (the one numpy exit) and
        replays; heavy tombstoning triggers the on-device compact."""
        nb = stream.num_batches(batch_size)
        if nb == 0:
            return handle, carry
        seg = max(1, min(segment_size or nb, nb))
        of0 = int(np.asarray(self.handle_counters(handle)[0]))
        i = 0
        while i < nb:
            k = min(seg, nb - i)
            # stack the segment ONCE; grow-and-replay retries reuse it
            # (the batch content is capacity-independent)
            stacked = stream.stacked(batch_size, i, k)
            while True:
                snap = (handle, carry)
                _faults.fire("segment_scan", engine=self.name,
                             start=i, count=k)
                run = self._segment_runner(step_fn, handle, batch_size)
                handle, carry, counters = run(handle, carry, stacked)
                of, _used, dead = (int(x) for x in np.asarray(counters))
                if of > of0:
                    # adds were dropped inside the segment: roll back,
                    # grow the pool, replay on the larger shapes.
                    handle, carry = self.grow(snap[0]), snap[1]
                    of0 = 0
                    continue
                break
            of0 = of
            if dead > compact_frac * max(self._diff_capacity(handle), 1):
                handle = self.compact_handle(handle)
            i += k
        return handle, carry

    def run_stream(self, handle, stream, batch_size: int, step_fn,
                   carry, segment_size: int = 8, compact_frac: float = 0.5):
        """Baseline per-batch dispatch: one device round-trip per batch
        (``segment_size`` has no effect — every batch is its own
        segment).  Fused engines override this."""
        view = self.stream_view()
        of0 = int(np.asarray(self.handle_counters(handle)[0]))
        for i in range(stream.num_batches(batch_size)):
            batch = stream.batch(i, batch_size)
            snap = (handle, carry)
            _faults.fire("segment_scan", engine=self.name, start=i, count=1)
            handle, carry = step_fn(view, handle, batch, carry)
            # ONE counter sync per batch (and per replay): read the
            # (overflow, used, dead) triple once, branch on the host copy.
            of, _used, dead = (int(x) for x in
                               np.asarray(self.handle_counters(handle)))
            while of > of0:
                # adds were dropped: roll back, grow capacity, replay.
                handle, carry = self.grow(snap[0]), snap[1]
                of0 = 0
                snap = (handle, carry)
                handle, carry = step_fn(view, handle, batch, carry)
                of, _used, dead = (int(x) for x in
                                   np.asarray(self.handle_counters(handle)))
            of0 = of
            if dead > compact_frac * max(self._diff_capacity(handle), 1):
                handle = self.compact_handle(handle)
        return handle, carry

    # -- library routines shared by all backends ---------------------------
    def propagate_flags(self, handle, props: Props, flag: str,
                        max_iter: int = 1_000_000) -> Props:
        """paper: g.propagateNodeFlags — BFS-spread a boolean property to
        everything reachable from the flagged set."""
        sw = EdgeSweep(
            edge_fn=lambda s, d, w: {flag: (s[flag], s[flag])},
            reduces={flag: Reduce("or")},
            post_fn=lambda p, red, hit: {
                **p,
                flag: p[flag] | red[flag],
                "_changed": red[flag] & ~p[flag],
            },
        )
        props = dict(props)
        props["_changed"] = props[flag]
        props = self.fixed_point(
            handle, sw, props,
            cond_fn=lambda p, it, col: col.any(p["_changed"]),
            max_iter=max_iter)
        props.pop("_changed")
        return props


# ---------------------------------------------------------------------------
# Durable-state helpers shared by every backend
# ---------------------------------------------------------------------------

_DYN_FIELDS = tuple(f.name for f in dataclasses.fields(DynGraph)
                    if f.name != "n")


def dyn_state(g: DynGraph) -> Dict[str, jax.Array]:
    """A DynGraph's array leaves as a flat dict (the 'dyn' tree layout)."""
    return {f: getattr(g, f) for f in _DYN_FIELDS}


def dyn_from_state(tree: Dict[str, Any], n: int) -> DynGraph:
    return DynGraph(**{f: jnp.asarray(tree[f]) for f in _DYN_FIELDS}, n=n)


def state_to_csr(tree: Dict[str, Any], meta: dict) -> Tuple[CSR, int]:
    """Collapse ANY engine's packed state to ``(CSR, diff_capacity)`` —
    the cross-backend restore path.  Value-preserving (the alive edge
    set survives exactly) but pool-layout-resetting: the target engine
    re-``prepare``s, so float summation order may differ from the saved
    run (DESIGN.md §5)."""
    kind, n = meta["kind"], meta["n"]
    if kind == "dist":
        src = np.asarray(tree["src"])
        dst = np.asarray(tree["dst"])
        w = np.asarray(tree["w"])
        cap = int(meta["diff_capacity"])
    elif kind in ("dyn", "pallas", "frontier"):
        g = dyn_from_state(tree if kind == "dyn" else tree["g"], n)
        es, ed, ew, ea = (np.asarray(x) for x in g.edge_arrays())
        keep = ea
        src, dst, w = es[keep], ed[keep], ew[keep]
        cap = g.diff_capacity
    else:
        raise ValueError(f"unknown packed-state kind {kind!r}")
    edges = np.stack([src, dst], axis=1) if len(src) else \
        np.zeros((0, 2), np.int64)
    return build_csr(n, edges, w), max(cap, 1)


# ===========================================================================
# JnpEngine — single-device XLA (the OpenMP analogue)
# ===========================================================================

class JnpEngine(Engine):
    name = "jnp"
    # fixed_point may sweep a small frontier's lanes alone (see there)
    frontier_switch = True

    def __init__(self):
        self._n = None
        # (array, value) pairs keyed by offsets-array identity: updates
        # replace d_offsets (cache invalidates itself), deletions and
        # repeated wedge calls on one handle reuse the cached bound —
        # no per-call host sync in count_wedges.
        self._deg_cache: Dict[str, tuple] = {}
        self._stream_cache: Dict[Any, Callable] = {}

    # -- construction ------------------------------------------------------
    def prepare(self, csr: CSR, diff_capacity: int) -> DynGraph:
        self._n = csr.n
        return diffcsr.from_csr(csr, diff_capacity)

    def merge(self, g: DynGraph) -> DynGraph:
        return diffcsr.merge(g)

    @property
    def n_pad(self) -> int:
        return self._n

    def out_degrees(self, g: DynGraph) -> jax.Array:
        return g.out_degrees()

    # -- durable state -----------------------------------------------------
    state_kind = "dyn"

    def pack_state(self, g: DynGraph):
        return dyn_state(g), {"kind": "dyn", "n": g.n}

    def unpack_state(self, tree, meta) -> DynGraph:
        self._n = meta["n"]
        return dyn_from_state(tree, meta["n"])

    # -- core sweep --------------------------------------------------------
    def _run_sweep(self, g: DynGraph, sw: EdgeSweep, props: Props) -> Props:
        return sw.post_fn(props, *self._reduce_lanes(sw, props,
                                                     *g.edge_arrays()))

    def _reduce_lanes(self, sw: EdgeSweep, props: Props, esrc, edst, ew,
                      ealive):
        """The (reduced, hit) of one sweep over the given edge lanes (all
        E + D of them, or a frontier's); lanes left out must be ones the
        edge function makes ineligible."""
        n = self.n_pad
        sview = {k: v for k, v in props.items()}
        s = _View(sview, esrc)
        d = _View(sview, edst)
        out = sw.edge_fn(s, d, ew)
        reduced, hit = {}, {}
        # value reductions first, arg-reductions second (two-pass argmin).
        for target, red in sw.reduces.items():
            if red.kind == "argmin":
                continue
            val, elig = out[target]
            elig = elig & ealive
            ident = red.identity(val.dtype)
            v = jnp.where(elig, val, ident)
            reduced[target] = red.segment(v, edst, n)
            hit[target] = jax.ops.segment_max(
                elig.astype(INT), edst, num_segments=n) > 0
        for target, red in sw.reduces.items():
            if red.kind != "argmin":
                continue
            of = red.of
            val, elig = out[of]
            elig = elig & ealive
            achieved = elig & (val == reduced[of][edst])
            v = jnp.where(achieved, esrc, jnp.asarray(n, INT))
            reduced[target] = jax.ops.segment_min(v, edst, num_segments=n)
            hit[target] = hit[of]
        return reduced, hit

    def sweep(self, g: DynGraph, sw: EdgeSweep, props: Props) -> Props:
        return self._run_sweep(g, sw, props)

    def _switch_capacity(self, handle, sw: EdgeSweep) -> int:
        """Lanes of the sparse branch of ``fixed_point``, or 0 where the
        sweep keeps the dense branch: no declared frontier, or a
        reduction whose result depends on the order lanes are combined
        in (a float ``sum``)."""
        if not (self.frontier_switch and sw.frontier is not None
                and all(r.kind in _ORDER_FREE for r in sw.reduces.values())):
            return 0
        return sparse_lane_capacity(self.handle_graph(handle).main_capacity)

    def fixed_point(self, g, sw: EdgeSweep, props: Props,
                    cond_fn: Callable, max_iter: int) -> Props:
        """Sweep to a fixed point in one ``while_loop``.  Where the sweep
        declares its frontier, each iteration counts the frontier's edge
        lanes on the device and, when they fit the sparse capacity,
        sweeps only those lanes (identical results: the other lanes are
        ineligible, and the reductions are exact and order-free)."""
        col = Collectives()
        cap = self._switch_capacity(g, sw)

        def cond(state):
            it, p, _ = state
            return (it < max_iter) & cond_fn(p, it, col)

        def body(state):
            it, p, counts = state
            if not cap:
                # an engine's own dense sweep (PallasEngine's ELL kernels)
                return (it + 1, self._run_sweep(g, sw, p),
                        counts + jnp.asarray([1, 0], INT))
            dyn = self.handle_graph(g)
            main_deg = dyn.offsets[1:] - dyn.offsets[:-1]
            deg = main_deg + dyn.d_offsets[1:] - dyn.d_offsets[:-1]
            ends = jnp.cumsum(jnp.where(p[sw.frontier], deg, 0))
            small = ends[-1] <= cap
            red = jax.lax.cond(
                small,
                lambda p: self._reduce_lanes(sw, p, *frontier_lanes(
                    dyn, ends, deg, main_deg, cap)),
                lambda p: self._reduce_lanes(sw, p, *dyn.edge_arrays()), p)
            p = sw.post_fn(p, *red)
            return it + 1, p, counts + jnp.stack([~small, small]).astype(INT)

        _, props, counts = jax.lax.while_loop(
            cond, body, (jnp.zeros((), INT), props, jnp.zeros((2,), INT)))
        # under a trace (a stream scan) the counts are dropped
        if not isinstance(counts, jax.core.Tracer):
            prev = self.sweep_counts
            self.sweep_counts = counts if prev is None else prev + counts
        return props

    def vertex_map(self, g: DynGraph, fn: Callable, props: Props) -> Props:
        return fn(props)

    def _max_deg(self, region: str, offsets: jax.Array) -> int:
        cached = self._deg_cache.get(region)
        if cached is None or cached[0] is not offsets:
            deg = np.asarray(offsets[1:] - offsets[:-1])
            cached = (offsets, int(deg.max()) if deg.size else 0)
            self._deg_cache[region] = cached
        return cached[1]

    # -- wedges (triangle counting) ----------------------------------------
    def count_wedges(self, g: DynGraph, pair_fn: Callable,
                     lane_flags: Dict[str, jax.Array], out_example,
                     bounds=None):
        esrc, edst, ew, ealive = g.edge_arrays()
        E, D = g.main_capacity, g.diff_capacity
        if bounds is not None:
            max_main, max_diff = bounds
        else:
            max_main = self._max_deg("main", g.offsets)
            max_diff = self._max_deg("diff", g.d_offsets)

        def is_edge_fn(qs, qd):
            return diffcsr.is_edge(g, qs, qd)

        def edge_flag_fn(name, qs, qd):
            fl = lane_flags[name]
            p1, f1 = diffcsr._locate_main(g, qs, qd)
            p2, f2 = diffcsr._locate_diff(g, qs, qd)
            r = jnp.zeros(qs.shape, BOOL)
            r = jnp.where(f1 & g.alive[p1], fl[jnp.clip(p1, 0, E + D - 1)], r)
            r = jnp.where(f2 & g.d_alive[p2] & ~f1,
                          fl[jnp.clip(E + p2, 0, E + D - 1)], r)
            return r

        zero = jax.tree_util.tree_map(
            lambda x: jnp.zeros((), jnp.asarray(x).dtype), out_example)

        def accumulate(total, j, region):
            if region == "main":
                pos = g.offsets[esrc] + j
                ok = (pos < g.offsets[esrc + 1])
                safe = jnp.clip(pos, 0, max(E - 1, 0))
                z = g.dst[safe]
                z_ok = ok & g.alive[safe]
                nbr_lane = safe
            else:
                pos = g.d_offsets[esrc] + j
                ok = (pos < g.d_offsets[esrc + 1])
                safe = jnp.clip(pos, 0, max(D - 1, 0))
                z = g.d_dst[safe]
                z_ok = ok & g.d_alive[safe]
                nbr_lane = E + safe
            ctx = WedgeCtx(g, lane_flags, nbr_lane, is_edge_fn, edge_flag_fn)
            contrib = pair_fn(esrc, edst, z, z_ok & ealive, ctx)
            return jax.tree_util.tree_map(
                lambda t, c: t + jnp.sum(c), total, contrib)

        def scan_region(total, count, region):
            if count == 0:
                return total
            def body(j, tot):
                return accumulate(tot, j, region)
            return jax.lax.fori_loop(0, count, body, total)

        total = scan_region(zero, max_main, "main")
        if D:
            total = scan_region(total, max_diff, "diff")
        return total

    # -- updates (jitted: the scatter programs re-trace cheaply and the
    # compiled executables cache on the static (E, D, B) shapes) ----------
    _upd_del = staticmethod(jax.jit(diffcsr.update_csr_del))
    _upd_add = staticmethod(jax.jit(diffcsr.update_csr_add))

    def update_del(self, g: DynGraph, batch: UpdateBatch) -> DynGraph:
        return JnpEngine._upd_del(g, batch.del_src, batch.del_dst,
                                  batch.del_mask)

    def update_add(self, g: DynGraph, batch: UpdateBatch) -> DynGraph:
        return JnpEngine._upd_add(g, batch.add_src, batch.add_dst,
                                  batch.add_w, batch.add_mask)

    def batch_edge_flags(self, g: DynGraph, qs, qd, mask) -> jax.Array:
        return edge_lane_flags(g, qs, qd, mask)

    def src_flags_from_dst(self, g: DynGraph, dst_mask) -> jax.Array:
        """Mark sources having an alive out-edge into the flagged dst set
        (the push-repair boundary; engines without it fall back to a
        dense seed)."""
        esrc, edst, ew, ealive = g.edge_arrays()
        n = self.n_pad
        hit = ealive & (edst < n) & dst_mask[jnp.clip(edst, 0, n - 1)]
        return jnp.zeros((n,), BOOL).at[
            jnp.where(hit, esrc, n)].set(True, mode="drop")

    # -- streaming executor (fused scan) -----------------------------------
    _compact = staticmethod(jax.jit(diffcsr.compact))

    def static_wedge_bounds(self, handle):
        g = self.handle_graph(handle)
        return self._max_deg("main", g.offsets), g.diff_capacity

    def grow(self, g: DynGraph, factor: float = 2.0) -> DynGraph:
        _faults.fire("pool_merge", engine=self.name,
                     diff_capacity=g.diff_capacity)
        # the old-capacity stream executables can never run again
        self._evict_stream_cache((g.main_capacity, g.diff_capacity))
        cap = max(int(g.diff_capacity * factor), g.diff_capacity + 16)
        return diffcsr.merge(g, diff_capacity=cap)

    def compact_handle(self, g: DynGraph) -> DynGraph:
        return JnpEngine._compact(g)

    def _stream_scan(self, step_fn, bounds, shape_key, batch_size):
        """One jitted program scanning a whole stream segment through
        update → affected-seed → incremental repair.  Cached per
        (step_fn, bounds, handle shapes, batch size) so ``grow`` can
        evict the executables its capacity change strands (jit's own
        aval cache would otherwise keep one per capacity step alive
        forever — PR 5 debt #1)."""
        key = (step_fn, bounds, shape_key, batch_size)
        with _STREAM_CACHE_LOCK:
            fn = self._stream_cache.get(key)
            if fn is None:
                view = self.stream_view(bounds)

                def seg_run(handle, carry, batches):
                    def body(state, batch):
                        h, c = step_fn(view, state[0], batch, state[1])
                        return (h, c), None

                    (h, c), _ = jax.lax.scan(body, (handle, carry), batches)
                    return h, c, self.handle_counters(h)

                fn = jax.jit(seg_run)  # wraps only; tracing is deferred
                self._stream_cache[key] = fn
        return fn

    def _segment_runner(self, step_fn, handle, batch_size: int):
        return self._stream_scan(step_fn, self.static_wedge_bounds(handle),
                                 self._handle_shape_key(handle), batch_size)

    def run_stream(self, handle, stream, batch_size: int, step_fn,
                   carry, segment_size: int = 8, compact_frac: float = 0.5):
        """Device-resident streaming executor: the ΔG batch loop becomes
        one lax.scan per stream segment — no host round-trips between
        batches (the shared driver in ``Engine._run_stream_fused``)."""
        return self._run_stream_fused(handle, stream, batch_size, step_fn,
                                      carry, segment_size, compact_frac)


class _View:
    """Gathered endpoint view (no read-logging on the hot path)."""

    __slots__ = ("_p", "_i")

    def __init__(self, props, idx):
        self._p = props
        self._i = idx

    def __getitem__(self, k):
        return self._p[k][self._i]
