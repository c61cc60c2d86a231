"""ShardedEngine — the graph ITSELF distributed, not just the work.

``DistEngine`` (core/dist.py) reproduces the paper's MPI backend but
replicates the diff-CSR: every device holds the full edge set, so the
largest servable graph is bounded by ONE device's memory.  This engine
partitions the data structure (ROADMAP item 1):

  * **row ownership**: each shard stores only the out-edge rows of its
    partition range (``graph/partition.py`` — ``block`` or
    degree-balanced, a schedule knob in the GraphIt sense); property
    ownership stays block-identity so the single-device algorithm text
    (global-id-indexed vertex properties) remains valid unchanged;
  * **halo region**: each shard keeps a replicated strip of *ghost*
    property slots for the foreign endpoints of its rows — the
    pyop2/firedrake diagonal-vs-off-process split (``graph/halo.py``);
  * **halo exchange**: a repair sweep does ONE packed ``all_to_all``
    per direction per dtype group — owners push boundary property
    values into ghosts (forward), ghost-side partial reductions fold
    back into owners (reverse).  Only boundary values cross shards, in
    static-shape send buffers, so the whole update→seed→repair segment
    stays inside one jitted ``shard_map`` scan;
  * **halo misses ride the overflow channel**: a ΔG insert whose
    endpoint is not yet in the halo tables records the id in a
    per-shard miss buffer and bumps a miss counter that is folded into
    the overflow counter the stream driver already polls — the stock
    rollback → rebuild → replay loop then rebuilds the partition with
    the missed ids as ghost hints, exactly like a pool overflow grows
    capacity.  Sweeps may drop unresolved edges *only* inside a
    segment that is guaranteed to be rolled back and replayed, so
    delivered results always come from a fully-resolved replay.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.core.ir import EdgeSweep
from repro.core.engine import Props, edge_lane_flags, _STREAM_CACHE_LOCK
from repro.core.dist import DistEngine, DistGraph, _DistStreamView, _DView
from repro.graph.csr import CSR, INT, build_csr
from repro.graph import diffcsr
from repro.graph.diffcsr import DynGraph, BOOL
from repro.graph.updates import UpdateBatch
from repro.graph.partition import PARTITIONERS, make_partition
from repro.graph.halo import build_plan, ghost_sets

_DYN = tuple(f.name for f in dataclasses.fields(DynGraph) if f.name != "n")
_HALO = ("row_starts", "ghosts", "send_idx", "recv_tgt", "hmiss", "miss_buf")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardGraph(DistGraph):
    """A DistGraph plus the static halo-exchange tables and the
    per-shard miss channel, all stacked on the sharded axis."""

    row_starts: jax.Array   # (P, P+1) row-ownership boundaries (replicated)
    ghosts: jax.Array       # (P, H)   sorted ghost ids, pad n_pad
    send_idx: jax.Array     # (P, P, Hs) owner-local slots per reader, pad blk
    recv_tgt: jax.Array     # (P, P, Hs) halo slots per owner packet, pad H
    hmiss: jax.Array        # (P,)     cumulative halo-miss counter
    miss_buf: jax.Array     # (P, K)   missed global ids (ghost hints)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LocalShard:
    """One shard's view inside shard_map: its DynGraph slice plus its
    halo tables.  Reads of DynGraph attributes fall through to ``g`` so
    graph-shaped helpers keep working on it."""

    g: DynGraph
    row_starts: jax.Array   # (P+1,)
    ghosts: jax.Array       # (H,)
    send_idx: jax.Array     # (P, Hs)  what I (owner) send to each reader
    recv_tgt: jax.Array     # (P, Hs)  where each owner's packet lands in my halo
    hmiss: jax.Array        # ()
    miss_buf: jax.Array     # (K,)

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "g"), name)


def _slocal(sg: ShardGraph) -> LocalShard:
    g = DynGraph(**{f: getattr(sg, f)[0] for f in _DYN}, n=sg.n)
    return LocalShard(g=g, **{f: getattr(sg, f)[0] for f in _HALO})


def _srestack(ls: LocalShard) -> ShardGraph:
    g = ls.g
    return ShardGraph(**{f: getattr(g, f)[None] for f in _DYN},
                      **{f: getattr(ls, f)[None] for f in _HALO}, n=g.n)


def _pack_dtype(dt):
    """Exchange-buffer dtype: ints and bools pack as int32; floats keep
    their exact dtype (int32 weights like INF_W exceed float32's exact
    integer range, so cross-casting is never safe)."""
    dt = np.dtype(dt)
    return dt if dt.kind == "f" else np.dtype(np.int32)


def _dtype_groups(vals: Dict[str, jax.Array]):
    groups: Dict[np.dtype, list] = {}
    for k in sorted(vals):
        groups.setdefault(_pack_dtype(vals[k].dtype), []).append(k)
    return sorted(groups.items(), key=lambda kv: kv[0].str)


class _ShardStreamView(_DistStreamView):
    """In-scan facade for the sharded engine: graph state inside the
    fused stream scan is a LocalShard, updates are row-ownership-masked
    with halo-miss recording, and wedge enumeration (TC) works — the
    halo'd shards reuse the distributed wedge body with segment-static
    bounds, which plain DistEngine's view refuses."""

    name = "dist_sharded-stream"

    def __init__(self, outer: "ShardedEngine", bounds=None):
        super().__init__(outer)
        self._bounds = bounds

    def batch_edge_flags(self, ls: LocalShard, qs, qd, mask) -> jax.Array:
        return edge_lane_flags(ls.g, qs, qd, mask)

    def count_wedges(self, ls: LocalShard, pair_fn, lane_flags, out_example,
                     bounds=None):
        b = bounds if bounds is not None else self._bounds
        if b is None:
            raise NotImplementedError(
                "wedge enumeration inside the sharded stream scan needs "
                "segment-static degree bounds")
        return self._o._count_wedges_local(ls.g, lane_flags, pair_fn,
                                           out_example, b[0], b[1])


class ShardedEngine(DistEngine):
    """Backend ``dist_sharded``: partitioned diff-CSR + halo exchange."""

    name = "dist_sharded"
    MISS_SLOTS = 256

    def __init__(self, num_shards: int | None = None, axis: str = "data",
                 devices=None, partitioner: str = "block"):
        super().__init__(num_shards=num_shards, axis=axis, devices=devices)
        if partitioner not in PARTITIONERS:
            raise ValueError(
                f"unknown partitioner {partitioner!r}; "
                f"expected one of {PARTITIONERS}")
        self.partitioner = partitioner
        self._partition = None
        self._plan = None
        # ghost hints accumulate across halo-miss rebuilds: a rebuild of
        # the ROLLED-BACK snapshot cannot see the edges whose insert
        # triggered the miss, so the missed ids must be force-added as
        # ghosts everywhere (and kept — consecutive rebuild rounds must
        # not forget each other's ids, or >MISS_SLOTS distinct misses
        # could livelock the replay loop).
        self._ghost_hints: np.ndarray | None = None
        self._last_miss = None

    # -- construction ------------------------------------------------------
    def prepare(self, csr: CSR, diff_capacity: int) -> ShardGraph:
        self._n = csr.n
        self._block = -(-csr.n // self.P)
        n, blk = csr.n, self._block
        src = np.asarray(csr.src)
        dst = np.asarray(csr.dst)
        w = np.asarray(csr.w)
        part = make_partition(self.partitioner, n, self.P, src)
        self._partition = part
        owner = part.owner_of(src) if src.size else np.zeros(0, np.int64)
        sels = [owner == p for p in range(self.P)]
        emax = max([1] + [int(s.sum()) for s in sels])
        shards = []
        for p, sel in enumerate(sels):
            e = np.stack([src[sel], dst[sel]], axis=1)
            sub = build_csr(n, e, w[sel], dedupe=False)
            k = sub.num_edges
            pad = emax - k
            shards.append(DynGraph(
                offsets=sub.offsets,
                src=jnp.concatenate([sub.src, jnp.zeros(pad, INT)]),
                dst=jnp.concatenate([sub.dst, jnp.zeros(pad, INT)]),
                w=jnp.concatenate([sub.w, jnp.ones(pad, INT)]),
                alive=jnp.concatenate([jnp.ones(k, BOOL),
                                       jnp.zeros(pad, BOOL)]),
                d_offsets=jnp.zeros((n + 1,), INT),
                d_src=jnp.full((diff_capacity,), n, INT),
                d_dst=jnp.zeros((diff_capacity,), INT),
                d_w=jnp.zeros((diff_capacity,), INT),
                d_alive=jnp.zeros((diff_capacity,), BOOL),
                overflow=jnp.zeros((), INT),
                n=n))
        gsets = ghost_sets(src, dst, owner, blk, self.P,
                           hints=self._ghost_hints)
        plan = build_plan(gsets, self.P, blk, self.n_pad)
        self._plan = plan
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)
        sg = ShardGraph(
            **{f: getattr(stacked, f) for f in _DYN},
            row_starts=jnp.asarray(
                np.tile(part.starts.astype(np.int32)[None], (self.P, 1))),
            ghosts=jnp.asarray(plan.ghosts),
            send_idx=jnp.asarray(plan.send_idx),
            recv_tgt=jnp.asarray(plan.recv_tgt),
            hmiss=jnp.zeros((self.P,), INT),
            miss_buf=jnp.full((self.P, self.MISS_SLOTS), self.n_pad, INT),
            n=n)
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), sg)

    def per_shard_bytes(self, sg: ShardGraph) -> int:
        """Resident bytes on ONE shard (the memory-scaling metric: a
        single-device DynGraph holds the whole edge set, a shard holds
        its rows plus the halo tables)."""
        total = 0
        for f in dataclasses.fields(ShardGraph):
            if f.name == "n":
                continue
            a = getattr(sg, f.name)
            total += int(np.prod(a.shape[1:], dtype=np.int64)
                         if a.ndim > 1 else 1) * a.dtype.itemsize
        return total

    # -- durable state -----------------------------------------------------
    # Inherits the shard-count-independent global-edge-list snapshot
    # ("dist" kind): saving on N shards and restoring onto M is the same
    # elastic path DistEngine has; the restoring engine re-partitions
    # with ITS partitioner knob (a schedule choice, not graph state).
    def pack_state(self, sg: ShardGraph):
        tree, meta = super().pack_state(sg)
        meta["partitioner"] = self.partitioner
        return tree, meta

    # -- streaming executor hooks ------------------------------------------
    def handle_counters(self, sg: ShardGraph) -> jax.Array:
        """(overflow + halo misses, used, dead): folding the miss count
        into the overflow lane makes the stock drivers' rollback-grow-
        replay loop service halo rebuilds with zero driver changes."""
        mat = sg.d_src < sg.n
        used = jnp.max(jnp.sum(mat.astype(INT), axis=1))
        dead = jnp.max(jnp.sum((mat & ~sg.d_alive).astype(INT), axis=1))
        if not isinstance(sg.hmiss, jax.core.Tracer):
            self._last_miss = (sg.hmiss, sg.miss_buf, sg.overflow)
        return jnp.stack([jnp.sum(sg.overflow) + jnp.sum(sg.hmiss),
                          used, dead])

    def grow(self, sg: ShardGraph, factor: float = 2.0) -> ShardGraph:
        """Rollback servicing: distinguish a true pool overflow (grow
        diff capacity) from a pure halo miss (rebuild the partition at
        the SAME capacity, with the missed ids as ghost hints).  One
        fused host transfer reads the stashed post-run counters."""
        from repro.runtime import faults as _faults
        cap = int(sg.d_src.shape[1])
        _faults.fire("pool_merge", engine=self.name, diff_capacity=cap)
        self._evict_stream_cache(self._handle_shape_key(sg))
        stash, self._last_miss = self._last_miss, None
        halo_only = False
        if stash is not None:
            (post_hm, post_buf, post_of), (pre_hm, pre_of) = jax.device_get(
                (stash, (sg.hmiss, sg.overflow)))
            ids = np.asarray(post_buf).ravel()
            ids = ids[(ids >= 0) & (ids < self.n_pad)]
            if ids.size:
                prev = (self._ghost_hints
                        if self._ghost_hints is not None else ids[:0])
                self._ghost_hints = np.union1d(prev, ids)
            halo_only = (
                int(np.sum(np.asarray(post_hm))) >
                int(np.sum(np.asarray(pre_hm)))
                and int(np.sum(np.asarray(post_of))) <=
                int(np.sum(np.asarray(pre_of))))
        new_cap = cap if halo_only else max(int(cap * factor), cap + 16)
        return self.merge(sg, diff_capacity=new_cap)

    _unstack = staticmethod(_slocal)
    _stack = staticmethod(_srestack)

    @staticmethod
    def _compact_local(ls: LocalShard) -> LocalShard:
        return dataclasses.replace(ls, g=diffcsr.compact(ls.g))

    def _handle_shape_key(self, sg: ShardGraph) -> tuple:
        return (int(sg.src.shape[1]), int(sg.d_src.shape[1]),
                int(sg.ghosts.shape[1]), int(sg.send_idx.shape[2]))

    def static_wedge_bounds(self, sg: ShardGraph):
        offs = np.asarray(sg.offsets)
        max_main = int((offs[:, 1:] - offs[:, :-1]).max()) if offs.size else 0
        return max_main, int(sg.d_src.shape[1])

    def _segment_runner(self, step_fn, sg: ShardGraph, batch_size: int):
        bounds = self.static_wedge_bounds(sg)
        key = (step_fn, bounds, self._handle_shape_key(sg), batch_size)
        with _STREAM_CACHE_LOCK:
            fn = self._stream_cache.get(key)
            if fn is None:
                view = _ShardStreamView(self, bounds)
                ax = self.axis
                compiled = {}

                def seg_run(sgl, c0, batches):
                    ls = _slocal(sgl)

                    def body(state, batch):
                        ls, c = step_fn(view, state[0], batch, state[1])
                        return (ls, c), None

                    (ls, c), _ = jax.lax.scan(body, (ls, c0), batches)
                    cnt = diffcsr.pool_counters(ls.g)
                    cnt = jnp.stack([
                        jax.lax.psum(cnt[0], ax) + jax.lax.psum(ls.hmiss, ax),
                        jax.lax.pmax(cnt[1], ax),
                        jax.lax.pmax(cnt[2], ax)])
                    return _srestack(ls), c, cnt[None]

                def fn(sg, carry, stacked):
                    # carry specs are per-leaf: vertex-property carries
                    # shard over the axis, scalar carries (TC's count)
                    # stay replicated — DistEngine's blanket P(axis)
                    # carry spec cannot express the latter.
                    cid = tuple(jnp.ndim(l) == 0
                                for l in jax.tree_util.tree_leaves(carry))
                    run = compiled.get(cid)
                    if run is None:
                        cspec = jax.tree_util.tree_map(
                            lambda l: P() if jnp.ndim(l) == 0 else P(ax),
                            carry)
                        run = jax.jit(self._shmap(
                            seg_run,
                            in_specs=(self._gspec(), cspec, P()),
                            out_specs=(self._gspec(), cspec, P(ax))))
                        compiled[cid] = run
                    sg, carry, counters = run(sg, carry, stacked)
                    self._last_miss = (sg.hmiss, sg.miss_buf, sg.overflow)
                    return sg, carry, counters[0]

                self._stream_cache[key] = fn
        return fn

    # -- halo exchange -----------------------------------------------------
    def _halo_forward(self, ls: LocalShard,
                      vals: Props) -> Props:
        """Owner → ghost refresh: for each dtype group, pack the boundary
        values each reader needs into one (P, Hs, C) buffer, one
        ``all_to_all``, scatter into the (H,)-halo strip.  Pad lanes
        carry garbage but land on ``recv_tgt`` pads (== H) and drop."""
        if not vals:
            return {}
        H = int(ls.ghosts.shape[0])
        blk = self.block
        idx = jnp.clip(ls.send_idx, 0, max(blk - 1, 0))     # (P, Hs)
        out = {}
        for dt, names in _dtype_groups(vals):
            sbuf = jnp.stack([vals[k][idx].astype(dt) for k in names],
                             axis=-1)                       # (P, Hs, C)
            rbuf = jax.lax.all_to_all(sbuf, self.axis, 0, 0, tiled=True)
            hbuf = jnp.zeros((H, len(names)), dt).at[ls.recv_tgt].set(
                rbuf, mode="drop")
            for c, k in enumerate(names):
                out[k] = hbuf[:, c].astype(vals[k].dtype)
        return out

    def _halo_reverse(self, ls: LocalShard, items: dict) -> dict:
        """Ghost partials → owner fold.  ``items`` maps name to
        ``(ghost (H,), base (blk,), fold, ident)``; returns the folded
        (blk,) owner values.  The same plan runs backwards: readers
        gather their ghost partials at ``recv_tgt``, owners fold the
        returning packets into their block at ``send_idx``."""
        if not items:
            return {}
        H = int(ls.ghosts.shape[0])
        safe = jnp.clip(ls.recv_tgt, 0, max(H - 1, 0))
        valid = ls.recv_tgt < H
        si = ls.send_idx
        out = {}
        groups: Dict[np.dtype, list] = {}
        for k in sorted(items):
            groups.setdefault(_pack_dtype(items[k][1].dtype), []).append(k)
        for dt, names in sorted(groups.items(), key=lambda kv: kv[0].str):
            cols = []
            for k in names:
                ghost, base, fold, ident = items[k]
                cols.append(jnp.where(valid, ghost[safe].astype(dt),
                                      jnp.asarray(ident, dt)))
            sbuf = jnp.stack(cols, axis=-1)                 # (P, Hs, C)
            rbuf = jax.lax.all_to_all(sbuf, self.axis, 0, 0, tiled=True)
            for c, k in enumerate(names):
                ghost, base, fold, ident = items[k]
                col = rbuf[..., c].astype(base.dtype)
                if fold == "min":
                    out[k] = base.at[si].min(col, mode="drop")
                elif fold == "max":
                    out[k] = base.at[si].max(col, mode="drop")
                else:
                    out[k] = base.at[si].add(col, mode="drop")
        return out

    # -- core sweep --------------------------------------------------------
    def _sweep_local(self, ls: LocalShard, sw: EdgeSweep, lp: Props,
                     read_set) -> Props:
        """One repair sweep on one shard.  Edge endpoints resolve to the
        (block + halo) concatenated property strip — owned ids map into
        the block, foreign ids binary-search the sorted ghost table.
        Reductions land in a (block + H + 1) dense buffer whose ghost
        strip folds back to owners through the reverse exchange.
        Unresolved endpoints (possible only for edges inserted after the
        tables were built) drop out of the sweep — their inserts already
        bumped the miss counter, so the driver is guaranteed to roll the
        segment back and replay it on rebuilt tables."""
        g = ls.g
        blk = self.block
        H = int(ls.ghosts.shape[0])
        drop = blk + H
        i = jax.lax.axis_index(self.axis)
        lo = i * blk
        esrc, edst, ew, ealive = g.edge_arrays()

        def resolve(v):
            owned = (v // blk) == i
            slot = jnp.clip(jnp.searchsorted(ls.ghosts, v), 0,
                            max(H - 1, 0))
            found = ls.ghosts[slot] == v
            ref = jnp.where(owned, v - lo,
                            jnp.where(found, blk + slot, drop))
            return ref, owned | found

        sref, s_ok = resolve(esrc)
        dref, d_ok = resolve(edst)
        ok = s_ok & d_ok & ealive
        gs = jnp.clip(sref, 0, drop - 1)
        gd = jnp.clip(dref, 0, drop - 1)

        halo = self._halo_forward(ls, {k: lp[k] for k in read_set})
        comb = {k: jnp.concatenate([lp[k], halo[k]]) for k in halo}
        out = sw.edge_fn(_DView(comb, gs), _DView(comb, gd), ew)

        tgt = jnp.where(ok, dref, drop)
        items, post_or = {}, set()
        for target, red in sw.reduces.items():
            if red.kind == "argmin":
                continue
            val, elig = out[target]
            elig = elig & ok
            ident = red.identity(val.dtype)
            v = jnp.where(elig, val, ident)
            dense = red.segment(v, tgt, drop + 1)
            if red.kind == "or":
                dense = dense.astype(INT)
                items["v:" + target] = (dense[blk:drop], dense[:blk],
                                        "max", jnp.zeros((), INT))
                post_or.add(target)
            elif red.kind == "sum":
                items["v:" + target] = (dense[blk:drop], dense[:blk],
                                        "add", jnp.zeros((), dense.dtype))
            else:
                fold = "min" if red.kind == "min" else "max"
                items["v:" + target] = (dense[blk:drop], dense[:blk],
                                        fold, ident)
            h = jax.ops.segment_max(elig.astype(INT), tgt,
                                    num_segments=drop + 1)
            items["h:" + target] = (h[blk:drop], h[:blk], "max",
                                    jnp.zeros((), INT))
        folded = self._halo_reverse(ls, items)
        reduced, hit = {}, {}
        for target, red in sw.reduces.items():
            if red.kind == "argmin":
                continue
            r = folded["v:" + target]
            reduced[target] = (r > 0) if target in post_or else r
            hit[target] = folded["h:" + target] > 0

        amins = {t: r for t, r in sw.reduces.items() if r.kind == "argmin"}
        if amins:
            # second pass: forward the folded minima so every shard can
            # test achievement, then min-fold the achieving GLOBAL
            # source ids — reproducing the deterministic smallest-source
            # tie-break of the single-device argmin bit-exactly.
            ofs = sorted({r.of for r in amins.values()})
            fwd = self._halo_forward(ls, {of: reduced[of] for of in ofs})
            cof = {of: jnp.concatenate([reduced[of], fwd[of]]) for of in ofs}
            aitems = {}
            for target, red in amins.items():
                val, elig = out[red.of]
                elig = elig & ok
                achieved = elig & (val == cof[red.of][gd])
                v = jnp.where(achieved, esrc, jnp.asarray(self.n_pad, INT))
                dense = jax.ops.segment_min(v, tgt, num_segments=drop + 1)
                aitems["a:" + target] = (dense[blk:drop], dense[:blk],
                                         "min", jnp.asarray(self.n_pad, INT))
            afold = self._halo_reverse(ls, aitems)
            for target, red in amins.items():
                reduced[target] = afold["a:" + target]
                hit[target] = hit[red.of]
        return sw.post_fn(lp, reduced, hit)

    # -- dynamic updates (row-ownership-masked, miss-recording) ------------
    def _row_owner(self, ls: LocalShard, v):
        return jnp.searchsorted(ls.row_starts, jnp.asarray(v, INT),
                                side="right") - 1

    def _covered(self, ls: LocalShard, v):
        i = jax.lax.axis_index(self.axis)
        H = int(ls.ghosts.shape[0])
        owned = (v // self.block) == i
        slot = jnp.clip(jnp.searchsorted(ls.ghosts, v), 0, max(H - 1, 0))
        return owned | (ls.ghosts[slot] == v)

    def _note_misses(self, ls: LocalShard, ids, mask) -> LocalShard:
        """Record endpoints the halo tables cannot resolve.  The counter
        is cumulative (rollback-safe: a replayed segment re-counts from
        the snapshot's value) and rides the overflow channel; the buffer
        keeps the earliest MISS_SLOTS distinct-ish ids as ghost hints
        for the rebuild."""
        K = int(ls.miss_buf.shape[0])
        miss = mask & ~self._covered(ls, ids)
        cnt = jnp.sum(miss.astype(INT))
        pos = ls.hmiss + jnp.cumsum(miss.astype(INT)) - 1
        pos = jnp.where(miss & (pos < K), pos, K)
        buf = ls.miss_buf.at[pos].set(jnp.asarray(ids, INT), mode="drop")
        return dataclasses.replace(ls, hmiss=ls.hmiss + cnt, miss_buf=buf)

    def _update_del_local(self, ls: LocalShard, b: UpdateBatch) -> LocalShard:
        i = jax.lax.axis_index(self.axis)
        own = self._row_owner(ls, b.del_src) == i
        g2 = diffcsr.update_csr_del(ls.g, b.del_src, b.del_dst,
                                    b.del_mask & own)
        # deletes tombstone rows already resident — no new endpoints,
        # no halo growth
        return dataclasses.replace(ls, g=g2)

    def _update_add_local(self, ls: LocalShard, b: UpdateBatch) -> LocalShard:
        i = jax.lax.axis_index(self.axis)
        own = self._row_owner(ls, b.add_src) == i
        m = b.add_mask & own
        g2 = diffcsr.update_csr_add(ls.g, b.add_src, b.add_dst, b.add_w, m)
        ls = dataclasses.replace(ls, g=g2)
        ids = jnp.concatenate([jnp.asarray(b.add_src, INT),
                               jnp.asarray(b.add_dst, INT)])
        return self._note_misses(ls, ids, jnp.concatenate([m, m]))
