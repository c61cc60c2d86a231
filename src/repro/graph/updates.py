"""Update batches: the ``updates<g>`` / ``Batch(allUpdates:batchSize)`` DSL
objects from the paper, as static-shape arrays.

An :class:`UpdateStream` is the full Δ (the paper generates these as a
percentage of |E|: half deletions sampled from existing edges, half
additions of fresh random edges, matching the paper's "percentage of
updates ... includes both incremental and decremental ones").

``batches()`` sweeps through it ``batch_size`` at a time — each
:class:`UpdateBatch` carries padded add/del arrays with validity masks so
every batch has the same static shape (XLA-friendly; the last partial
batch is padded).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np
import jax
import jax.numpy as jnp

from repro.graph.csr import CSR, INT
from repro.graph.diffcsr import BOOL


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class UpdateBatch:
    add_src: jax.Array   # (B,) int32
    add_dst: jax.Array
    add_w: jax.Array
    add_mask: jax.Array  # (B,) bool
    del_src: jax.Array   # (B,) int32
    del_dst: jax.Array
    del_mask: jax.Array

    @property
    def size(self) -> int:
        return int(self.add_src.shape[0])


@dataclasses.dataclass(frozen=True)
class UpdateStream:
    """Host-side container for the whole Δ; slices into UpdateBatches."""

    adds: np.ndarray      # (A, 3) src, dst, w
    dels: np.ndarray      # (Dl, 2) src, dst

    @property
    def num_adds(self) -> int:
        return int(self.adds.shape[0])

    @property
    def num_dels(self) -> int:
        return int(self.dels.shape[0])

    def num_batches(self, batch_size: int) -> int:
        longest = max(self.num_adds, self.num_dels, 1)
        return -(-longest // batch_size)

    def batch(self, i: int, batch_size: int) -> UpdateBatch:
        def pad_slice(arr: np.ndarray, width: int):
            lo = i * batch_size
            chunk = arr[lo:lo + batch_size]
            k = chunk.shape[0]
            out = np.zeros((batch_size, width), dtype=np.int32)
            # NaN/Inf rows int-cast silently here BY DESIGN: batch views
            # are device-bound int lanes, and admission validates the
            # raw host arrays before any batch view is trusted
            with np.errstate(invalid="ignore"):
                out[:k] = chunk
            mask = np.zeros((batch_size,), dtype=bool)
            mask[:k] = True
            return out, mask

        a, am = pad_slice(self.adds, 3)
        d, dm = pad_slice(self.dels, 2)
        return UpdateBatch(
            add_src=jnp.asarray(a[:, 0]), add_dst=jnp.asarray(a[:, 1]),
            add_w=jnp.asarray(np.maximum(a[:, 2], 1)),
            add_mask=jnp.asarray(am),
            del_src=jnp.asarray(d[:, 0]), del_dst=jnp.asarray(d[:, 1]),
            del_mask=jnp.asarray(dm),
        )

    def batches(self, batch_size: int) -> Iterator[UpdateBatch]:
        for i in range(self.num_batches(batch_size)):
            yield self.batch(i, batch_size)

    def window(self, batch_size: int, start: int,
               count: int) -> "UpdateStream":
        """A sub-stream covering batches ``[start, start+count)`` at this
        ``batch_size``.  Because ``batch()`` slices adds and dels with the
        same row arithmetic, ``window(bs, i, k).batch(j, bs)`` is
        lane-identical to ``self.batch(i + j, bs)`` — which is what lets
        the admission guard splice quarantined batches out of a stream
        and run the surviving contiguous ranges through the fused
        executor unchanged."""
        lo = start * batch_size
        hi = (start + count) * batch_size
        return UpdateStream(adds=self.adds[lo:hi], dels=self.dels[lo:hi])

    def stacked(self, batch_size: int, start: int = 0,
                count: int | None = None) -> UpdateBatch:
        """A (count, B)-leaved UpdateBatch pytree — the padded batch
        stream segment that ``Engine.run_stream`` lax.scans over."""
        nb = self.num_batches(batch_size)
        if count is None:
            count = nb - start
        bs = [self.batch(start + j, batch_size) for j in range(count)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *bs)


def random_updates(csr: CSR, percent: float, seed: int = 0,
                   max_w: int = 100, add_frac: float = 0.5) -> UpdateStream:
    """Sample Δ as the paper does: ``percent`` of |E| updates, split between
    deletions of existing edges and additions of fresh edges."""
    rng = np.random.default_rng(seed)
    n = csr.n
    e = csr.num_edges
    total = max(int(e * percent / 100.0), 1)
    n_add = int(total * add_frac)
    n_del = total - n_add

    src = np.asarray(csr.src)
    dst = np.asarray(csr.dst)
    del_idx = rng.choice(e, size=min(n_del, e), replace=False)
    dels = np.stack([src[del_idx], dst[del_idx]], axis=1).astype(np.int32)

    # Fresh edges: sample, then drop collisions with existing edges and
    # self-loops; within a draw the first copy of a key wins.  Membership
    # runs on sorted int64 pair keys, so this scales to large graphs.
    existing = np.sort(src.astype(np.int64) * n + dst)
    taken = np.zeros((0,), np.int64)
    adds = []
    while len(adds) < n_add:
        cand = rng.integers(0, n, size=(2 * (n_add - len(adds)) + 8, 2))
        key = cand[:, 0] * np.int64(n) + cand[:, 1]
        pos = np.minimum(np.searchsorted(existing, key), len(existing) - 1)
        ok = (cand[:, 0] != cand[:, 1]) & ~np.isin(key, taken)
        if len(existing):
            ok &= existing[pos] != key
        first = np.zeros(len(key), bool)
        first[np.unique(key, return_index=True)[1]] = True
        pick = np.flatnonzero(ok & first)[: n_add - len(adds)]
        for i in pick:     # one weight draw per accepted edge, in order
            adds.append((int(cand[i, 0]), int(cand[i, 1]),
                         int(rng.integers(1, max_w))))
        taken = np.concatenate([taken, key[pick]])
    adds = np.asarray(adds, dtype=np.int32).reshape(-1, 3)
    return UpdateStream(adds=adds, dels=dels)
