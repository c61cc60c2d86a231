"""Inputs of a cell, made from ``--seed`` with the benchmark's own generators.

The graph is Graph500's undirected Kronecker (R-MAT) graph, each edge held
both ways, and the traffic a stream of ΔG batches.  The Kronecker draws copy
``repro.graph.csr.rmat_graph``; the stream draws as
``repro.graph.updates.random_updates`` does, vectorised and sized in whole
batches rather than as a percentage of |E|.  They are kept here so that
no change to the program can move the yardstick.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per input, from any whole-number seed."""
    return np.random.default_rng([seed % 2 ** 64, stream])


def kronecker(scale: int, edge_factor: int, rng: np.random.Generator,
              a: float, b: float, c: float) -> np.ndarray:
    """R-MAT pairs: ``edge_factor * 2**scale`` directed (src, dst) draws,
    one bit of each endpoint per level with quadrant probabilities
    (a, b, c, 1-a-b-c).  Same draws, in the same order, as
    ``repro.graph.csr.rmat_graph``."""
    n = 1 << scale
    m = n * edge_factor
    srcs = np.zeros(m, dtype=np.int64)
    dsts = np.zeros(m, dtype=np.int64)
    r = np.empty(m)
    r2 = np.empty(m)
    for _ in range(scale):
        rng.random(out=r)
        src_bit = r >= a + b
        rng.random(out=r2)
        dst_bit = np.where(src_bit, r2 >= c / (1 - a - b), r2 >= a / (a + b))
        srcs <<= 1
        srcs |= src_bit
        dsts <<= 1
        dsts |= dst_bit
    return np.stack([srcs, dsts], axis=1)


def draw_weights(rng: np.random.Generator, size: int, bits: int):
    """Graph500 kernel 3's edge weights, reals uniform in [0, 1), held as
    int32 fixed point: ``floor(r * 2**bits) + 1``, in [1, 2**bits]."""
    return (np.floor(rng.random(size) * 2.0 ** bits) + 1).astype(np.int32)


def both_ways(pairs: np.ndarray, values=None):
    """Each undirected pair (u, v) as the two adjacent lanes (u, v), (v, u);
    ``values`` (one per pair) repeated on both."""
    lanes = np.stack([pairs, pairs[:, ::-1]], axis=1).reshape(-1, 2)
    if values is None:
        return lanes
    return lanes, np.repeat(values, 2)


def make_graph(cfg: dict, seed: int):
    """(n, edges, weights) of the undirected graph the generator draws from
    ``seed``: every distinct non-loop pair of the Kronecker draws, held
    both ways with one weight, sorted by (src, dst)."""
    rng = rng_for(seed, 0)
    n = 1 << cfg["scale"]
    e = kronecker(cfg["scale"], cfg["edge_factor"], rng,
                  cfg["a"], cfg["b"], cfg["c"])
    e = np.sort(e[e[:, 0] != e[:, 1]], axis=1)
    key = np.unique(e @ np.array([n, 1], np.int64))
    pairs = np.stack([key // n, key % n], axis=1)
    lanes, w = both_ways(pairs, draw_weights(rng, len(pairs),
                                             cfg["weight_bits"]))
    order = np.argsort(lanes @ np.array([n, 1], np.int64))
    return n, lanes[order].astype(np.int32), w[order]


def search_key(cfg: dict) -> int:
    """Graph500's rule for a search key: a vertex drawn uniformly from those
    of degree at least 1, here from the fixed ``graph_seed``."""
    n, e, _ = make_graph(cfg, cfg["graph_seed"])
    return int(rng_for(cfg["graph_seed"], 3).choice(np.unique(e[:, 0])))


@dataclasses.dataclass(frozen=True)
class DeltaStream:
    """``batches`` forward ΔG batches whose keys are pairwise disjoint:
    deletes are distinct edges of the base graph, inserts are distinct
    fresh edges (no self-loop, not in the base graph).

    Batch 0 of the endless stream is forward batch 0, whose effect then
    stands for good.  Batch ``k >= 1`` cycles over the others: forward
    batch ``1 + (k - 1) % (batches - 1)`` on even passes and its inverse on
    odd ones (delete what it inserted, re-insert what it deleted with the
    original weight).  So a closed loop never runs out, the diff pool never
    holds more than one pass of inserts, and the graph is never the base
    graph again: a step that leaves its state unchanged cannot match the
    reference after any number of batches."""

    batch: int               # lanes per side of an UpdateBatch
    inserts: int             # insert lanes per forward batch
    deletes: int             # delete lanes per forward batch
    batches: int             # forward batches in one pass
    adds: np.ndarray         # (batches * inserts, 3) src, dst, w
    dels: np.ndarray         # (batches * deletes, 2) src, dst
    del_w: np.ndarray        # (batches * deletes,) base weight of each delete

    def _pass(self, k: int):
        """(passes done, batches into this one) of the cycle after batch 0."""
        return divmod(k - 1, self.batches - 1)

    def rows(self, k: int):
        """(adds (A, 3), dels (D, 2)) host rows of stream batch ``k``."""
        p, r = self._pass(k) if k else (0, -1)
        i, inverse = 1 + r, p % 2 == 1
        a = self.adds[i * self.inserts:(i + 1) * self.inserts]
        d = self.dels[i * self.deletes:(i + 1) * self.deletes]
        if not inverse:
            return a, d
        dw = self.del_w[i * self.deletes:(i + 1) * self.deletes]
        return (np.concatenate([d, dw[:, None]], axis=1).astype(np.int32),
                np.ascontiguousarray(a[:, :2]))

    def relabel(self, perm: np.ndarray) -> "DeltaStream":
        """The same stream with vertex ``v`` renamed ``perm[v]``."""
        adds = self.adds.copy()
        adds[:, :2] = perm[adds[:, :2]]
        return dataclasses.replace(self, adds=adds,
                                   dels=perm[self.dels].astype(np.int32))

    def in_effect(self, k: int) -> np.ndarray:
        """Forward batches whose effect stands after stream batches
        ``[0, k)``: batch 0 once ``k >= 1``, then of the others the first
        ``r`` on an even pass and the rest on an odd one."""
        if k == 0:
            return np.arange(0)
        p, r = self._pass(k)
        rest = np.arange(1, r + 1) if p % 2 == 0 \
            else np.arange(r + 1, self.batches)
        return np.concatenate([[0], rest]).astype(np.int64)

    def net(self, k: int):
        """(adds, dels) whose one-shot application to the base graph gives
        the graph after stream batches ``[0, k)``."""
        idx = self.in_effect(k)
        ar = (idx[:, None] * self.inserts + np.arange(self.inserts)).ravel()
        dr = (idx[:, None] * self.deletes + np.arange(self.deletes)).ravel()
        return self.adds[ar], self.dels[dr]


def make_stream(n: int, edges: np.ndarray, weights: np.ndarray,
                traffic: dict, seed: int, weight_bits: int) -> DeltaStream:
    """The traffic file's stream over the undirected base graph ``edges``
    (both ways, sorted by (src, dst), unique).  An update takes two lanes,
    one each way: deletes are pairs sampled without replacement from the
    graph, inserts fresh pairs with uniform endpoints as ``random_updates``
    draws them, weighted as the graph is."""
    rng = rng_for(seed, 1)
    nb, bi, bd = traffic["stream_batches"], traffic["inserts"], \
        traffic["deletes"]
    if max(bi, bd) > traffic["batch"] or bi % 2 or bd % 2:
        raise ValueError(f"a batch of {traffic['batch']} lanes cannot hold "
                         f"{bi} insert or {bd} delete lanes, two an update")
    if nb < 2:
        raise ValueError("a stream cycles over at least two batches")
    n_add, n_del = nb * bi // 2, nb * bd // 2
    up = np.flatnonzero(edges[:, 0] < edges[:, 1])
    if n_del > len(up):
        raise ValueError(f"{n_del} deletes from a graph of {len(up)} edges")
    pick = up[rng.choice(len(up), size=n_del, replace=False)]
    dels, del_w = both_ways(edges[pick], weights[pick])
    existing = edges[:, 0].astype(np.int64) * n + edges[:, 1]
    keys = np.zeros(0, np.int64)
    while len(keys) < n_add:
        cand = np.sort(rng.integers(0, n, size=(2 * (n_add - len(keys)) + 8,
                                                2)), axis=1)
        key = cand[:, 0] * np.int64(n) + cand[:, 1]
        pos = np.minimum(np.searchsorted(existing, key), len(existing) - 1)
        ok = (cand[:, 0] != cand[:, 1]) & (existing[pos] != key) \
            & ~np.isin(key, keys)
        first = np.zeros(len(key), bool)
        first[np.unique(key, return_index=True)[1]] = True
        keys = np.concatenate([keys, key[ok & first]])[:n_add]
    lanes, w = both_ways(np.stack([keys // n, keys % n], axis=1),
                         draw_weights(rng, n_add, weight_bits))
    adds = np.concatenate([lanes, w[:, None]], axis=1).astype(np.int32)
    return DeltaStream(batch=traffic["batch"], inserts=bi, deletes=bd,
                       batches=nb, adds=adds, dels=dels.astype(np.int32),
                       del_w=del_w.astype(np.int32))


def make_inputs(cfg: dict, traffic: dict, seed: int):
    """(n, edges, weights, stream, perm) of one run.

    The graph and the stream are drawn from the configuration's fixed
    ``graph_seed``; ``seed`` draws the permutation ``perm`` that renames
    vertex ``v`` to ``perm[v]`` everywhere, as Graph500 permutes vertex
    labels.  So every seed brings the same work, graphs and batches alike,
    laid out differently in memory: a graph drawn anew per seed changed
    the repair work of a batch by up to 14% between seeds."""
    n, e, w = make_graph(cfg, cfg["graph_seed"])
    stream = make_stream(n, e, w, traffic, cfg["graph_seed"],
                         cfg["weight_bits"])
    perm = rng_for(seed, 2).permutation(n).astype(np.int32)
    pe = perm[e]
    order = np.argsort(pe[:, 0].astype(np.int64) * n + pe[:, 1])
    return n, pe[order], w[order], stream.relabel(perm), perm
