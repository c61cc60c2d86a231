"""DistEngine — the MPI backend analogue, on shard_map + collectives.

Faithful mapping of the paper's §3.6/§5.2 distributed design:

  * **vertex ownership**: vertices are block-partitioned over the mesh's
    ``data`` axis; "a process stores only those edges for which the source
    node is owned by that process" — each shard holds its own DynGraph
    (CSR *and* diff-CSR) containing exactly its out-edges;
  * **distributed diff-CSR**: update batches are broadcast and each shard
    applies only the updates whose source it owns — literally re-using the
    single-device ``update_csr_add/del`` code under shard_map;
  * **RMA windows → all_gather**: remote property reads become one
    ``all_gather`` per sweep, restricted to the read set recovered by
    ``trace_read_set`` (the paper's read-set analysis deciding what to
    expose);
  * **MPI_Accumulate(MIN/SUM) → pmin/psum**: each shard reduces its local
    edges' contributions into a dense n-length buffer; a cross-shard
    pmin/psum/pmax produces the globally combined property — the
    shared-lock atomic-accumulate of §5.2, as one deterministic collective;
  * **TC's remote-neighborhood queries** (the paper's admitted MPI
    bottleneck) become query all_gathers + pmax combines per wedge step —
    same asymptotic communication, kept deliberately so the benchmark
    reproduces the paper's TC trend.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, jaxpr_as_fun
from jax.sharding import Mesh, PartitionSpec as P, NamedSharding

from repro.core.ir import EdgeSweep, Reduce
from repro.core.engine import Engine, Collectives, Props, WedgeCtx, \
    edge_lane_flags, _STREAM_CACHE_LOCK
from repro.graph.csr import CSR, INT, build_csr
from repro.graph import diffcsr
from repro.graph.diffcsr import DynGraph, BOOL
from repro.graph.updates import UpdateBatch


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistGraph:
    """Per-shard DynGraphs stacked on a leading (sharded) axis."""

    offsets: jax.Array   # (P, n+1)
    src: jax.Array       # (P, Emax)
    dst: jax.Array
    w: jax.Array
    alive: jax.Array
    d_offsets: jax.Array
    d_src: jax.Array
    d_dst: jax.Array
    d_w: jax.Array
    d_alive: jax.Array
    overflow: jax.Array  # (P,)
    n: int = dataclasses.field(metadata=dict(static=True))


def _local(dg: DistGraph) -> DynGraph:
    """Inside shard_map: strip the leading size-1 shard axis."""
    leaf = lambda x: x[0]
    return DynGraph(
        offsets=leaf(dg.offsets), src=leaf(dg.src), dst=leaf(dg.dst),
        w=leaf(dg.w), alive=leaf(dg.alive), d_offsets=leaf(dg.d_offsets),
        d_src=leaf(dg.d_src), d_dst=leaf(dg.d_dst), d_w=leaf(dg.d_w),
        d_alive=leaf(dg.d_alive), overflow=leaf(dg.overflow), n=dg.n)


def _restack(g: DynGraph) -> DistGraph:
    leaf = lambda x: x[None]
    return DistGraph(
        offsets=leaf(g.offsets), src=leaf(g.src), dst=leaf(g.dst),
        w=leaf(g.w), alive=leaf(g.alive), d_offsets=leaf(g.d_offsets),
        d_src=leaf(g.d_src), d_dst=leaf(g.d_dst), d_w=leaf(g.d_w),
        d_alive=leaf(g.d_alive), overflow=leaf(g.overflow), n=g.n)


class DistCollectives(Collectives):
    def __init__(self, axis: str):
        self.axis = axis

    def any(self, x):
        return jax.lax.pmax(jnp.any(x).astype(jnp.int32), self.axis) > 0

    def sum(self, x):
        return jax.lax.psum(jnp.sum(x), self.axis)

    def max(self, x):
        return jax.lax.pmax(jnp.max(x), self.axis)


def _host_fetch(tree):
    """ONE host transfer for a whole pytree of device arrays.  The
    monkeypatchable seam ``tests/test_perf_debts.py`` pins: pack_state's
    edge harvest must cost one sync per save, not one per shard
    (DESIGN.md §3 debt #6)."""
    return jax.device_get(tree)


def _pcombine(red: Reduce, x, axis: str):
    if red.kind in ("min", "argmin"):
        return jax.lax.pmin(x, axis)
    if red.kind == "max":
        return jax.lax.pmax(x, axis)
    if red.kind == "sum":
        return jax.lax.psum(x, axis)
    if red.kind == "or":
        return jax.lax.pmax(x.astype(jnp.int32), axis).astype(BOOL)
    raise ValueError(red.kind)


class _DistStreamView(Engine):
    """In-shard engine view used by stream steps inside the streaming
    shard_map: the same Engine surface, but every method assumes it is
    already running on ONE shard (local DynGraph, (block,)-local vertex
    props) and synchronizes through collectives — the paper's 'same
    algorithm text, MPI synchronization' point carried into the fused
    scan.  Notably has NO ``src_flags_from_dst``: decremental repair
    falls back to its dense seed, exactly like the outer DistEngine."""

    name = "dist-stream"

    def __init__(self, outer: "DistEngine"):
        self._o = outer

    # -- shapes ------------------------------------------------------------
    # _n delegates to the LIVE engine: the cached segment runner retraces
    # when graph shapes change, and the retrace must see the n of the
    # graph currently prepared, not the one at view construction.
    @property
    def _n(self):
        return self._o._n

    @property
    def n_pad(self) -> int:
        return self._o.n_pad

    def full(self, value, dtype) -> jax.Array:
        # vertex properties inside the stream scan are (block,) shards
        return jnp.full((self._o.block,), value, dtype=dtype)

    # -- aggregate ops: the outer engine's in-shard bodies ----------------
    def vertex_map(self, g, fn, props: Props) -> Props:
        return self._o._vertex_map_local(fn, props)

    def sweep(self, g, sw: EdgeSweep, props: Props) -> Props:
        read_set = frozenset(sw.read_set(props))
        return self._o._sweep_local(g, sw, props, read_set)

    def count_wedges(self, handle, pair_fn, lane_flags, out_example,
                     bounds=None):
        raise NotImplementedError(
            "wedge enumeration (TC) is not supported inside DistEngine's "
            "fused stream scan; use the per-batch dyn_tc path on dist")

    def fixed_point(self, g, sw: EdgeSweep, props: Props, cond_fn,
                    max_iter: int) -> Props:
        return self._o._fixed_point_local(g, sw, props, cond_fn, max_iter)

    def out_degrees(self, g) -> jax.Array:
        return self._o._out_degrees_local(g)

    def update_del(self, g, batch: UpdateBatch):
        return self._o._update_del_local(g, batch)

    def update_add(self, g, batch: UpdateBatch):
        return self._o._update_add_local(g, batch)

    def batch_edge_flags(self, g, qs, qd, mask) -> jax.Array:
        return edge_lane_flags(g, qs, qd, mask)


class DistEngine(Engine):
    name = "dist"

    # shared_engine keys instances of mesh-bound engines by their shard
    # count: an engine prepared for one mesh must never be handed to a
    # tenant expecting another (see registry.shared_engine).
    mesh_scoped = True

    def __init__(self, num_shards: int | None = None, axis: str = "data",
                 devices=None):
        devices = devices if devices is not None else jax.devices()
        if num_shards is None:
            num_shards = len(devices)
        self.P = num_shards
        self.axis = axis
        self.mesh = Mesh(np.array(devices[: self.P]), (axis,))
        self._n = None
        self._block = None
        self._stream_cache = {}

    # ------------------------------------------------------------------ #
    @property
    def n_pad(self) -> int:
        return self._block * self.P

    @property
    def block(self) -> int:
        return self._block

    def _shmap(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _spmd(self, fn, in_specs, out_specs, *args):
        """``jit(shard_map(fn))(*args)``, compiled once per program.

        ``fn`` is a closure built afresh on each call, over a sweep's or
        a DSL statement's callables and over the call's own arrays and
        host scalars, so neither its identity nor its code objects say
        which program it is.  Tracing it to a jaxpr does, and is cheap
        next to a compile: the memo in ``_stream_cache`` is keyed on the
        printed jaxpr, in which host scalars are literals, plus the
        handle's shape key so that ``grow`` evicts what it strands.  The
        arrays the closure captures are the jaxpr's constants and enter
        the compiled function as arguments, so a hit never sees another
        call's values."""
        traced, out_shape = jax.make_jaxpr(
            self._shmap(fn, in_specs, out_specs), return_shape=True)(*args)
        shape_key = (self._handle_shape_key(args[0])
                     if isinstance(args[0], DistGraph) else None)
        key = ("spmd", shape_key, str(traced.jaxpr))
        with _STREAM_CACHE_LOCK:
            run = self._stream_cache.get(key)
            if run is None:
                jaxpr = traced.jaxpr
                run = jax.jit(lambda consts, leaves: jaxpr_as_fun(
                    ClosedJaxpr(jaxpr, consts))(*leaves))
                self._stream_cache[key] = run
        out = run(traced.consts, jax.tree_util.tree_leaves(args))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(out_shape), out)

    def _gspec(self):
        """Sharding spec for a stacked DistGraph pytree."""
        return P(self.axis)

    def _pspec(self):
        return P(self.axis)

    # -- construction ------------------------------------------------------
    def prepare(self, csr: CSR, diff_capacity: int) -> DistGraph:
        self._n = csr.n
        self._block = -(-csr.n // self.P)
        n = csr.n
        src = np.asarray(csr.src)
        dst = np.asarray(csr.dst)
        w = np.asarray(csr.w)
        shards = []
        emax = 0
        for p in range(self.P):
            lo, hi = p * self._block, (p + 1) * self._block
            sel = (src >= lo) & (src < hi)
            emax = max(emax, int(sel.sum()))
        emax = max(emax, 1)
        for p in range(self.P):
            lo, hi = p * self._block, (p + 1) * self._block
            sel = (src >= lo) & (src < hi)
            e = np.stack([src[sel], dst[sel]], axis=1)
            sub = build_csr(n, e, w[sel], dedupe=False)
            k = sub.num_edges
            pad = emax - k
            g = DynGraph(
                offsets=sub.offsets,
                src=jnp.concatenate([sub.src, jnp.zeros(pad, INT)]),
                dst=jnp.concatenate([sub.dst, jnp.zeros(pad, INT)]),
                w=jnp.concatenate([sub.w, jnp.ones(pad, INT)]),
                alive=jnp.concatenate([jnp.ones(k, BOOL), jnp.zeros(pad, BOOL)]),
                d_offsets=jnp.zeros((n + 1,), INT),
                d_src=jnp.full((diff_capacity,), n, INT),
                d_dst=jnp.zeros((diff_capacity,), INT),
                d_w=jnp.zeros((diff_capacity,), INT),
                d_alive=jnp.zeros((diff_capacity,), BOOL),
                overflow=jnp.zeros((), INT),
                n=n,
            )
            shards.append(g)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)
        dg = DistGraph(**{f.name: getattr(stacked, f.name)
                          for f in dataclasses.fields(DynGraph)
                          if f.name != "n"}, n=n)
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), dg)

    def _gather_edges(self, dg: DistGraph):
        """Host-gather the global alive edge set ``(src, dst, w)`` from
        the stacked shards — shared by ``merge`` and ``pack_state``
        (shard-count-independent, so it is also the re-mesh format).

        The concatenations mirror ``DynGraph.edge_arrays`` on the
        stacked ``(P, ·)`` lanes so the whole harvest is ONE host
        transfer instead of one per shard (debt #6); the row-major
        flatten preserves the per-shard ``[main, diff]`` lane order of
        the old per-shard loop bit-exactly."""
        n = dg.n
        es = jnp.concatenate([dg.src, jnp.minimum(dg.d_src, n - 1)], axis=1)
        ed = jnp.concatenate([dg.dst, dg.d_dst], axis=1)
        ew = jnp.concatenate([dg.w, dg.d_w], axis=1)
        ea = jnp.concatenate([dg.alive, dg.d_alive & (dg.d_src < n)], axis=1)
        es, ed, ew, ea = _host_fetch((es, ed, ew, ea))
        keep = np.asarray(ea).reshape(-1)
        return (np.asarray(es).reshape(-1)[keep],
                np.asarray(ed).reshape(-1)[keep],
                np.asarray(ew).reshape(-1)[keep])

    def merge(self, dg: DistGraph,
              diff_capacity: int | None = None) -> DistGraph:
        """Gather alive edges host-side, rebuild, re-partition."""
        src, dst, w = self._gather_edges(dg)
        edges = np.stack([src, dst], 1)
        csr = build_csr(dg.n, edges, w)
        if diff_capacity is None:
            diff_capacity = max(dg.d_src.shape[1], 1)
        return self.prepare(csr, diff_capacity=diff_capacity)

    # -- durable state -----------------------------------------------------
    # The dist snapshot is the CANONICAL global edge list, not the raw
    # (P, ...) shard leaves: restore re-partitions onto whatever mesh the
    # restoring engine owns, so an elastic session can come back on a
    # different device count.  Consequence (DESIGN.md §5): restore is
    # value-exact for order-independent reductions (integer min/max —
    # SSSP), but float sums may re-associate because the pool layout is
    # rebuilt.
    state_kind = "dist"

    def pack_state(self, dg: DistGraph):
        src, dst, w = self._gather_edges(dg)
        tree = {"src": jnp.asarray(src), "dst": jnp.asarray(dst),
                "w": jnp.asarray(w)}
        meta = {"kind": "dist", "n": dg.n,
                "diff_capacity": int(dg.d_src.shape[1]),
                "num_shards": self.P}
        return tree, meta

    def put_vertex_array(self, arr):
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.device_put(jnp.asarray(arr), sh)

    def unpack_state(self, tree, meta) -> DistGraph:
        src = np.asarray(tree["src"])
        dst = np.asarray(tree["dst"])
        w = np.asarray(tree["w"])
        edges = np.stack([src, dst], 1)
        csr = build_csr(meta["n"], edges, w)
        # prepare() blocks over THIS mesh's P — the re-mesh happens here
        return self.prepare(csr,
                            diff_capacity=max(int(meta["diff_capacity"]), 1))

    # -- streaming executor hooks ------------------------------------------
    def handle_counters(self, dg: DistGraph) -> jax.Array:
        """(overflow, used, dead): overflow summed over shards, pool
        occupancy as the worst shard (capacity is per shard)."""
        mat = dg.d_src < dg.n
        used = jnp.max(jnp.sum(mat.astype(INT), axis=1))
        dead = jnp.max(jnp.sum((mat & ~dg.d_alive).astype(INT), axis=1))
        return jnp.stack([jnp.sum(dg.overflow), used, dead])

    def grow(self, dg: DistGraph, factor: float = 2.0) -> DistGraph:
        from repro.runtime import faults as _faults
        _faults.fire("pool_merge", engine=self.name,
                     diff_capacity=int(dg.d_src.shape[1]))
        self._evict_stream_cache(self._handle_shape_key(dg))
        cap = dg.d_src.shape[1]
        return self.merge(dg, diff_capacity=max(int(cap * factor), cap + 16))

    # -- in-shard (un)stacking of the handle; ShardedEngine adds its halo
    # tables and overrides these three --------------------------------------
    _unstack = staticmethod(_local)
    _stack = staticmethod(_restack)

    @staticmethod
    def _compact_local(g: DynGraph) -> DynGraph:
        return diffcsr.compact(g)

    def compact_handle(self, dg: DistGraph) -> DistGraph:
        return self._spmd(
            lambda d: self._stack(self._compact_local(self._unstack(d))),
            (self._gspec(),), self._gspec(), dg)

    def _diff_capacity(self, dg: DistGraph) -> int:
        return int(dg.d_src.shape[1])

    def _handle_shape_key(self, dg: DistGraph) -> tuple:
        return (int(dg.src.shape[1]), int(dg.d_src.shape[1]))

    def _segment_runner(self, step_fn, dg: DistGraph, batch_size: int):
        key = (step_fn, self._handle_shape_key(dg), batch_size)
        with _STREAM_CACHE_LOCK:
            fn = self._stream_cache.get(key)
            if fn is None:
                view = _DistStreamView(self)
                ax = self.axis

                def seg_run(dgl, c0, batches):
                    g = _local(dgl)

                    def body(state, batch):
                        g, c = step_fn(view, state[0], batch, state[1])
                        return (g, c), None

                    (g, c), _ = jax.lax.scan(body, (g, c0), batches)
                    # reduce the per-shard counters to the driver's triple:
                    # overflow summed, occupancy as the worst shard
                    cnt = diffcsr.pool_counters(g)
                    cnt = jnp.stack([jax.lax.psum(cnt[0], ax),
                                     jax.lax.pmax(cnt[1], ax),
                                     jax.lax.pmax(cnt[2], ax)])
                    return _restack(g), c, cnt[None]

                shmapped = jax.jit(self._shmap(
                    seg_run,
                    in_specs=(self._gspec(), self._pspec(), P()),
                    out_specs=(self._gspec(), self._pspec(), P(self.axis))))

                def fn(dg, carry, stacked):
                    dg, carry, counters = shmapped(dg, carry, stacked)
                    return dg, carry, counters[0]

                self._stream_cache[key] = fn
        return fn

    def run_stream(self, dg: DistGraph, stream, batch_size: int, step_fn,
                   carry, segment_size: int = 8, compact_frac: float = 0.5):
        """Fused stream segments under ONE shard_map: the scan keeps the
        sharded graph and (block,)-local vertex props device-resident,
        synchronizing only through the collectives inside the step (the
        shared driver in ``Engine._run_stream_fused``)."""
        return self._run_stream_fused(dg, stream, batch_size, step_fn,
                                      carry, segment_size, compact_frac)

    def _out_degrees_local(self, g: DynGraph) -> jax.Array:
        esrc, _, _, ealive = g.edge_arrays()
        dense = jax.ops.segment_sum(ealive.astype(INT), esrc,
                                    num_segments=self.n_pad)
        dense = jax.lax.psum(dense, self.axis)
        i = jax.lax.axis_index(self.axis)
        return jax.lax.dynamic_slice(dense, (i * self.block,),
                                     (self.block,))

    def out_degrees(self, dg: DistGraph) -> jax.Array:
        return self._spmd(lambda d: self._out_degrees_local(_local(d)),
                          (self._gspec(),), self._pspec(), dg)

    # -- core sweep (inside-shard_map body shared with fixed_point) ---------
    def _sweep_local(self, g: DynGraph, sw: EdgeSweep, lp: Props,
                     read_set) -> Props:
        n_pad = self.n_pad
        i = jax.lax.axis_index(self.axis)
        # "RMA window": gather only the properties the edge_fn reads.
        full = {k: (jax.lax.all_gather(v, self.axis, tiled=True)
                    if k in read_set else None) for k, v in lp.items()}
        full = {k: v for k, v in full.items() if v is not None}
        esrc, edst, ew, ealive = g.edge_arrays()
        s = _DView(full, esrc)
        d = _DView(full, edst)
        out = sw.edge_fn(s, d, ew)
        reduced, hit = {}, {}
        for target, red in sw.reduces.items():
            if red.kind == "argmin":
                continue
            val, elig = out[target]
            elig = elig & ealive
            ident = red.identity(val.dtype)
            v = jnp.where(elig, val, ident)
            dense = red.segment(v, edst, n_pad)
            dense = _pcombine(red, dense, self.axis)
            reduced[target] = dense
            h = jax.ops.segment_max(elig.astype(INT), edst,
                                    num_segments=n_pad)
            hit[target] = (jax.lax.pmax(h, self.axis) > 0)
        for target, red in sw.reduces.items():
            if red.kind != "argmin":
                continue
            of = red.of
            val, elig = out[of]
            elig = elig & ealive
            achieved = elig & (val == reduced[of][edst])
            v = jnp.where(achieved, esrc, jnp.asarray(n_pad, INT))
            dense = jax.ops.segment_min(v, edst, num_segments=n_pad)
            reduced[target] = jax.lax.pmin(dense, self.axis)
            hit[target] = hit[of]
        blk = lambda x: jax.lax.dynamic_slice(x, (i * self.block,),
                                              (self.block,))
        reduced = {k: blk(v) for k, v in reduced.items()}
        hit = {k: blk(v) for k, v in hit.items()}
        return sw.post_fn(lp, reduced, hit)

    def _fixed_point_local(self, g, sw: EdgeSweep, props: Props,
                           cond_fn: Callable, max_iter: int) -> Props:
        read_set = frozenset(sw.read_set(props))
        col = DistCollectives(self.axis)

        def cond(state):
            it, p = state
            return (it < max_iter) & cond_fn(p, it, col)

        def body(state):
            it, p = state
            return it + 1, self._sweep_local(g, sw, p, read_set)

        _, out = jax.lax.while_loop(cond, body, (jnp.zeros((), INT), props))
        return out

    def _vertex_map_local(self, fn: Callable, props: Props) -> Props:
        full = {k: jax.lax.all_gather(v, self.axis, tiled=True)
                for k, v in props.items()}
        out = fn(full)
        i = jax.lax.axis_index(self.axis)
        return {k: jax.lax.dynamic_slice(v, (i * self.block,),
                                         (self.block,))
                for k, v in out.items()}

    def sweep(self, dg: DistGraph, sw: EdgeSweep, props: Props) -> Props:
        read_set = frozenset(sw.read_set(props))
        return self._spmd(
            lambda d, p: self._sweep_local(self._unstack(d), sw, p,
                                           read_set),
            (self._gspec(), self._pspec()), self._pspec(), dg, props)

    def fixed_point(self, dg: DistGraph, sw: EdgeSweep, props: Props,
                    cond_fn: Callable, max_iter: int) -> Props:
        return self._spmd(
            lambda d, p: self._fixed_point_local(self._unstack(d), sw, p,
                                                 cond_fn, max_iter),
            (self._gspec(), self._pspec()), self._pspec(), dg, props)

    def vertex_map(self, dg: DistGraph, fn: Callable, props: Props) -> Props:
        return self._spmd(lambda p: self._vertex_map_local(fn, p),
                          (self._pspec(),), self._pspec(), props)

    # -- wedges --------------------------------------------------------------
    def _count_wedges_local(self, g: DynGraph, flags: Dict[str, jax.Array],
                            pair_fn: Callable, out_example,
                            max_main: int, max_diff: int):
        """In-shard wedge-count body (already inside shard_map): local
        wedge enumeration plus all_gather+pmax remote-edge queries — the
        paper's admitted MPI TC bottleneck, kept deliberately.  Shared
        with the sharded engine's stream view, which calls it with
        segment-static bounds."""
        axis = self.axis
        E, D = g.main_capacity, g.diff_capacity
        esrc, edst, ew, ealive = g.edge_arrays()

        def global_is_edge(qs, qd):
            qg = jax.lax.all_gather(jnp.stack([qs, qd]), axis)  # (P,2,L)
            ans = diffcsr.is_edge(g, qg[:, 0], qg[:, 1])
            ans = jax.lax.pmax(ans.astype(INT), axis)
            i = jax.lax.axis_index(axis)
            return ans[i].astype(BOOL)

        def global_edge_flag(name, qs, qd):
            fl = flags[name]
            qg = jax.lax.all_gather(jnp.stack([qs, qd]), axis)
            p1, f1 = diffcsr._locate_main(g, qg[:, 0], qg[:, 1])
            p2, f2 = diffcsr._locate_diff(g, qg[:, 0], qg[:, 1])
            r = jnp.zeros(qg.shape[0:1] + qs.shape, BOOL)
            r = jnp.where(f1 & g.alive[p1],
                          fl[jnp.clip(p1, 0, E + D - 1)], r)
            r = jnp.where(f2 & g.d_alive[p2] & ~f1,
                          fl[jnp.clip(E + p2, 0, E + D - 1)], r)
            r = jax.lax.pmax(r.astype(INT), axis)
            i = jax.lax.axis_index(axis)
            return r[i].astype(BOOL)

        zero = jax.tree_util.tree_map(
            lambda x: jnp.zeros((), jnp.asarray(x).dtype), out_example)

        def accumulate(total, j, region):
            if region == "main":
                pos = g.offsets[esrc] + j
                ok = pos < g.offsets[esrc + 1]
                safe = jnp.clip(pos, 0, max(E - 1, 0))
                z = g.dst[safe]
                z_ok = ok & g.alive[safe]
                nbr_lane = safe
            else:
                pos = g.d_offsets[esrc] + j
                ok = pos < g.d_offsets[esrc + 1]
                safe = jnp.clip(pos, 0, max(D - 1, 0))
                z = g.d_dst[safe]
                z_ok = ok & g.d_alive[safe]
                nbr_lane = E + safe
            ctx = WedgeCtx(g, flags, nbr_lane, global_is_edge,
                           global_edge_flag)
            contrib = pair_fn(esrc, edst, z, z_ok & ealive, ctx)
            return jax.tree_util.tree_map(
                lambda t, c: t + jnp.sum(c), total, contrib)

        total = zero
        if max_main:
            total = jax.lax.fori_loop(
                0, max_main, lambda j, t: accumulate(t, j, "main"), total)
        if max_diff and D:
            total = jax.lax.fori_loop(
                0, max_diff, lambda j, t: accumulate(t, j, "diff"), total)
        return jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t, axis), total)

    def count_wedges(self, dg: DistGraph, pair_fn: Callable,
                     lane_flags: Dict[str, jax.Array], out_example,
                     bounds=None):
        if bounds is not None:
            max_main, max_diff = bounds
        else:
            # host-side loop bounds from the stacked offsets
            offs = np.asarray(dg.offsets)
            doffs = np.asarray(dg.d_offsets)
            max_main = int((offs[:, 1:] - offs[:, :-1]).max()) \
                if offs.size else 0
            max_diff = int((doffs[:, 1:] - doffs[:, :-1]).max()) \
                if doffs.size else 0

        def fn(dgl, flags):
            flags = {k: v[0] for k, v in flags.items()}
            return self._count_wedges_local(_local(dgl), flags, pair_fn,
                                            out_example, max_main, max_diff)

        return self._spmd(
            fn, (self._gspec(), {k: P(self.axis) for k in lane_flags}),
            jax.tree_util.tree_map(lambda _: P(), out_example),
            dg, lane_flags)

    # -- updates (ownership-masked, straight onto the local graph) ------------
    def _update_del_local(self, g: DynGraph, b: UpdateBatch) -> DynGraph:
        own = (b.del_src // self.block) == jax.lax.axis_index(self.axis)
        return diffcsr.update_csr_del(g, b.del_src, b.del_dst,
                                      b.del_mask & own)

    def _update_add_local(self, g: DynGraph, b: UpdateBatch) -> DynGraph:
        own = (b.add_src // self.block) == jax.lax.axis_index(self.axis)
        return diffcsr.update_csr_add(g, b.add_src, b.add_dst, b.add_w,
                                      b.add_mask & own)

    def update_del(self, dg: DistGraph, batch: UpdateBatch) -> DistGraph:
        return self._spmd(
            lambda d, b: self._stack(self._update_del_local(
                self._unstack(d), b)),
            (self._gspec(), P()), self._gspec(), dg, batch)

    def update_add(self, dg: DistGraph, batch: UpdateBatch) -> DistGraph:
        return self._spmd(
            lambda d, b: self._stack(self._update_add_local(
                self._unstack(d), b)),
            (self._gspec(), P()), self._gspec(), dg, batch)

    def batch_edge_flags(self, dg: DistGraph, qs, qd, mask) -> jax.Array:
        return self._spmd(
            lambda d: edge_lane_flags(_local(d), qs, qd, mask)[None],
            (self._gspec(),), P(self.axis), dg)


class _DView:
    __slots__ = ("_p", "_i")

    def __init__(self, props, idx):
        self._p = props
        self._i = idx

    def __getitem__(self, k):
        return self._p[k][self._i]
