"""Transformer layer zoo: norms, RoPE, GQA attention (chunked-flash for
train/prefill, cache attention for decode), SwiGLU MLP, capacity-based MoE.

Conventions
-----------
* activations: (B, S, D); attention heads (B, S, H, dh)
* every sublayer is pre-norm residual
* ``shard`` is a callable(x, kind) applying with_sharding_constraint per
  the arch's attention sharding strategy ('head' vs 'dh', DESIGN.md §5);
  it is a no-op outside jit-with-mesh contexts.
* chunked flash attention: lax.scan over KV blocks with running
  (max, denom, acc) — O(S·Kb) memory instead of O(S²), which is what
  makes prefill_32k lowerable; the Pallas kernel in
  repro/kernels/flash_attention.py is the TPU-tiled version of the same
  schedule.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.archs import ArchConfig

F32 = jnp.float32
KV_BLOCK = 1024
NEG = -1e30


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + scale)


def rope(x, positions, theta):
    """x: (..., S, H, dh); positions: (..., S)"""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = positions[..., :, None].astype(F32) * freqs          # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softcap(logits, cap: Optional[float]):
    if cap is None:
        return logits
    return jnp.tanh(logits / cap) * cap


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def qkv_project(p, x, cfg: ArchConfig, shard):
    B, S, D = x.shape
    H, kv, dh = cfg.n_heads, cfg.n_kv, cfg.dh
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, H, dh)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, kv, dh)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(B, S, kv, dh)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(H, dh)
        k = k + p["bk"].reshape(kv, dh)
        v = v + p["bv"].reshape(kv, dh)
    return shard(q, "qkv"), shard(k, "qkv"), shard(v, "qkv")


def _flash_body(q, k, v, mask, cap):
    """One KV block: q (B,S,H,dh), k/v (B,Kb,H,dh), mask (S,Kb) or None."""
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(F32)
    logits = softcap(logits, cap)
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, NEG)
    m = jnp.max(logits, axis=-1)                              # (B,H,S)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)
    return m, l, acc


def flash_attention(q, k, v, cfg: ArchConfig, positions, causal: bool,
                    window: Optional[int], scale: float, shard=None):
    """Chunked-softmax attention; q,k,v: (B,S,H,dh) (kv already repeated).

    ``shard`` constrains the scan carries — without it the while loop
    pins them replicated and SPMD all-gathers the sharded logits every
    KV block (measured: 344 GB/layer at 32k for the 'dh' archs).
    """
    B, S, H, dh = q.shape
    q = q * scale
    Kb = min(KV_BLOCK, S)
    if S % Kb:               # non-power-of-two source lengths (e.g. 1500
        Kb = S               # whisper frames): single block
    nblk = S // Kb
    k = k.reshape(B, nblk, Kb, H, dh).swapaxes(0, 1)
    v = v.reshape(B, nblk, Kb, H, dh).swapaxes(0, 1)
    qpos = positions                                            # (S,)

    def step(carry, xs):
        m0, l0, acc0 = carry
        kb, vb, blk = xs
        kpos = blk * Kb + jnp.arange(Kb)
        mask = None
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
        m1, l1, a1 = _flash_body(q, kb, vb, mask, cfg.attn_softcap)
        m = jnp.maximum(m0, m1)
        c0 = jnp.exp(m0 - m)
        c1 = jnp.exp(m1 - m)
        l = l0 * c0 + l1 * c1
        acc = acc0 * c0.transpose(0, 2, 1)[..., None].astype(acc0.dtype) \
            + a1 * c1.transpose(0, 2, 1)[..., None].astype(a1.dtype)
        return (m, l, acc), None

    m0 = jnp.full((B, H, S), NEG, F32)
    l0 = jnp.zeros((B, H, S), F32)
    acc0 = jnp.zeros((B, S, H, dh), q.dtype)
    if shard is not None:
        m0 = shard(m0, "flash_ml")
        l0 = shard(l0, "flash_ml")
        acc0 = shard(acc0, "flash_acc")
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (k, v, jnp.arange(nblk)))
    denom = jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return (acc / denom.astype(acc.dtype)).astype(q.dtype)


def repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    B, S, kv, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None], (B, S, kv, n_rep, dh)) \
        .reshape(B, S, kv * n_rep, dh)


def attention_train(p, x, cfg: ArchConfig, positions, shard,
                    causal=True, window=None):
    B, S, D = x.shape
    H, kv, dh = cfg.n_heads, cfg.n_kv, cfg.dh
    q, k, v = qkv_project(p, x, cfg, shard)
    if causal:  # encoders (bidir) skip rope to mimic abs-pos (stub choice)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    k = repeat_kv(k, H // kv)
    v = repeat_kv(v, H // kv)
    o = flash_attention(q, k, v, cfg, positions, causal, window,
                        scale=dh ** -0.5)
    o = shard(o, "qkv")
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * dh), p["wo"])


def attention_decode(p, x, cfg: ArchConfig, cache_k, cache_v, pos, shard,
                     window=None):
    """x: (B,1,D); cache_k/v: (B,kv,S,dh); pos: scalar write index."""
    B, _, D = x.shape
    H, kv, dh = cfg.n_heads, cfg.n_kv, cfg.dh
    S = cache_k.shape[2]
    q, k, v = qkv_project(p, x, cfg, shard)
    posv = jnp.full((1,), pos)
    q = rope(q, posv, cfg.rope_theta)
    k = rope(k, posv, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype).transpose(0, 2, 1, 3),
        (0, 0, pos, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype).transpose(0, 2, 1, 3),
        (0, 0, pos, 0))
    q = shard(q, "q_decode")
    out = cache_attend(q, cache_k, cache_v, cfg, pos, window)
    return jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, H * dh), p["wo"]), \
        cache_k, cache_v


def cache_attend(q, cache_k, cache_v, cfg: ArchConfig, pos, window=None,
                 mask_to_pos=True):
    """q: (B,1,H,dh); cache: (B,kv,S,dh) -> (B,1,H,dh)."""
    from repro.models.tuning import get_tuning
    B, _, H, dh = q.shape
    kv = cache_k.shape[1]
    rep = H // kv
    qh = q.reshape(B, kv, rep, dh) * (dh ** -0.5)
    S = cache_k.shape[2]
    base = jnp.zeros((), jnp.int32)
    if window is not None and get_tuning().window_slice and S > window:
        # read only the window-sized slice of the cache (S is unsharded
        # under the 'dh' cache layout, so this is a local slice)
        base = jnp.clip(pos - window + 1, 0, S - window).astype(jnp.int32)
        cache_k = jax.lax.dynamic_slice_in_dim(cache_k, base, window,
                                               axis=2)
        cache_v = jax.lax.dynamic_slice_in_dim(cache_v, base, window,
                                               axis=2)
        S = window
    logits = jnp.einsum("bkrd,bksd->bkrs", qh, cache_k).astype(F32)
    logits = softcap(logits, cfg.attn_softcap)
    jpos = base + jnp.arange(S)
    if mask_to_pos:
        ok = jpos <= pos
        if window is not None:
            ok &= jpos > pos - window
        logits = jnp.where(ok[None, None, None], logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrs,bksd->bkrd", p.astype(cache_v.dtype), cache_v)
    return out.reshape(B, 1, H, dh)


def cross_attention(p, x, src_k, src_v, cfg: ArchConfig, shard):
    """x: (B,S,D) attending to precomputed source k/v (B,kv,T,dh)."""
    B, S, D = x.shape
    H, kv, dh = cfg.n_heads, cfg.n_kv, cfg.dh
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, H, dh)
    q = shard(q, "qkv")
    rep = H // kv
    qh = q.reshape(B, S, kv, rep, dh) * (dh ** -0.5)
    logits = jnp.einsum("bskrd,bktd->bskrt", qh, src_k).astype(F32)
    pr = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskrt,bktd->bskrd", pr.astype(src_v.dtype), src_v)
    out = out.reshape(B, S, H * dh)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def xattn_kv(p, src, cfg: ArchConfig, shard):
    """Precompute cross-attention K/V from source embeddings (B,T,D)."""
    B, T, D = src.shape
    kv, dh = cfg.n_kv, cfg.dh
    k = jnp.einsum("btd,dh->bth", src, p["wk"]).reshape(B, T, kv, dh)
    v = jnp.einsum("btd,dh->bth", src, p["wv"]).reshape(B, T, kv, dh)
    return shard(k.transpose(0, 2, 1, 3), "cache"), \
        shard(v.transpose(0, 2, 1, 3), "cache")


# ---------------------------------------------------------------------------
# FFNs
# ---------------------------------------------------------------------------

def mlp(p, x, shard):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard(h, "ffn")
    return h @ p["w_down"]


def moe(p, x, cfg: ArchConfig, shard):
    """Capacity-based top-k MoE with expert-parallel einsums.

    Tokens are sorted by expert, packed into a static (E, C, D) buffer
    (overflow drops — standard capacity-factor semantics), pushed through
    expert-sharded einsums, and combined back weighted by router scores.

    Dispatch has two lowerings (repro.models.tuning):
      'scatter' — rows scattered into the expert-sharded buffer (SPMD
        lowers this to an all-reduce of the FULL E·C·D buffer per layer);
      'gather'  — scatter int32 indices only, then row-gather (the wire
        cost drops to the token activations).  Default.
    """
    from repro.models.tuning import get_tuning
    mc = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = mc.n_experts, mc.top_k
    C = max(int(T * K / E * mc.capacity_factor), 8)
    dispatch = get_tuning().moe_dispatch
    if dispatch == "shard_map":
        policy = getattr(shard, "__self__", None)
        if policy is not None and getattr(policy, "mesh", None) is not None:
            return _moe_shard_map(p, x, cfg, policy)
        dispatch = "gather"            # no mesh → single-device fallback
    xt = x.reshape(T, D)
    logits = (xt @ p["router"]).astype(F32)                    # (T, E)
    scores = jax.nn.softmax(logits, axis=-1)
    top_s, top_e = jax.lax.top_k(scores, K)                    # (T, K)
    top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)

    eid = top_e.reshape(T * K)
    tok = jnp.repeat(jnp.arange(T), K)
    gate = top_s.reshape(T * K)
    order = jnp.argsort(eid)
    eid_s, tok_s, gate_s = eid[order], tok[order], gate[order]
    # rank within expert group (ELL trick: index − group start)
    start = jnp.searchsorted(eid_s, eid_s, side="left")
    rank = jnp.arange(T * K) - start
    keep = rank < C
    slot = jnp.where(keep, eid_s * C + rank, E * C)

    if dispatch == "gather":
        # indices-only scatter (E·C·4 bytes on the wire) + row gather
        tok_for_slot = jnp.full((E * C + 1,), T, jnp.int32) \
            .at[slot].set(tok_s.astype(jnp.int32), mode="drop")[:E * C]
        valid = tok_for_slot < T
        xe = jnp.where(valid[:, None],
                       xt[jnp.minimum(tok_for_slot, T - 1)],
                       jnp.zeros((), x.dtype)).reshape(E, C, D)
    else:
        xe = jnp.zeros((E * C, D), x.dtype).at[slot].set(
            xt[tok_s], mode="drop").reshape(E, C, D)
    xe = shard(xe, "moe")
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    ye = shard(ye, "moe").reshape(E * C, D)

    contrib = ye[jnp.minimum(slot, E * C - 1)] * \
        (gate_s * keep)[:, None].astype(ye.dtype)
    y = jax.ops.segment_sum(contrib, tok_s, num_segments=T)
    return y.reshape(B, S, D)


def _moe_route_local(xt, router, E, K, cap, E0, E_loc, C_loc):
    """Route local tokens to LOCAL experts [E0, E0+E_loc); returns the
    packed buffer index map + combine metadata.  Pure jnp — runs inside
    the shard_map body (no communication)."""
    T_loc = xt.shape[0]
    logits = (xt @ router).astype(F32)
    scores = jax.nn.softmax(logits, axis=-1)
    top_s, top_e = jax.lax.top_k(scores, K)
    top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    eid = top_e.reshape(T_loc * K)
    tok = jnp.repeat(jnp.arange(T_loc), K)
    gate = top_s.reshape(T_loc * K)
    mine = (eid >= E0) & (eid < E0 + E_loc)
    eid_l = jnp.where(mine, eid - E0, E_loc)
    order = jnp.argsort(eid_l)
    eid_s, tok_s, gate_s = eid_l[order], tok[order], gate[order]
    start = jnp.searchsorted(eid_s, eid_s, side="left")
    rank = jnp.arange(T_loc * K) - start
    keep = (rank < C_loc) & (eid_s < E_loc)
    slot = jnp.where(keep, eid_s * C_loc + rank, E_loc * C_loc)
    tok_for_slot = jnp.full((E_loc * C_loc + 1,), T_loc, jnp.int32) \
        .at[slot].set(tok_s.astype(jnp.int32), mode="drop")[:E_loc * C_loc]
    return tok_for_slot, slot, tok_s, gate_s, keep


def _moe_shard_map(p, x, cfg: ArchConfig, policy):
    """Expert-parallel MoE: local routing per (data, model) shard, local
    expert FFN on the model shard's experts, psum combine over 'model'.

    Wire cost per layer = the per-token partial outputs (T_loc·D) instead
    of the global (E,C,D) buffer, and the expert flops are computed once
    (the jit lowering replicates them across the data axis).
    """
    from jax.sharding import PartitionSpec as P

    mc = cfg.moe
    mesh = policy.mesh
    B, S, D = x.shape
    T = B * S
    E, K = mc.n_experts, mc.top_k
    dp = policy.dp_axes
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tp_size = mesh.shape[policy.tp] if policy.tp in mesh.axis_names else 0
    if not tp_size or E % tp_size or T % dp_size:
        # indivisible / no model axis — fall back to the jit lowering
        from repro.models.tuning import Tuning, use_tuning
        with use_tuning(Tuning(moe_dispatch="gather")):
            return moe(p, x, cfg, policy.shard)
    E_loc = E // tp_size
    T_loc = T // dp_size
    C_loc = max(int(T_loc * K / E * mc.capacity_factor), 8)

    def body(xt, router, wg, wu, wd):
        # xt: (T_loc, D) — this data shard's tokens (replicated over tp)
        m = jax.lax.axis_index(policy.tp)
        E0 = m * E_loc
        tok_for_slot, slot, tok_s, gate_s, keep = _moe_route_local(
            xt, router.astype(xt.dtype), E, K, mc.capacity_factor,
            E0, E_loc, C_loc)
        valid = tok_for_slot < T_loc
        xe = jnp.where(valid[:, None],
                       xt[jnp.minimum(tok_for_slot, T_loc - 1)],
                       jnp.zeros((), xt.dtype)).reshape(E_loc, C_loc, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) \
            * jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc * C_loc, D)
        contrib = ye[jnp.minimum(slot, E_loc * C_loc - 1)] * \
            (gate_s * keep)[:, None].astype(ye.dtype)
        y = jax.ops.segment_sum(contrib, tok_s, num_segments=T_loc)
        return jax.lax.psum(y, policy.tp)            # combine over experts

    tp = policy.tp
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp or None, None), P(None, None),
                  P(tp, None, None), P(tp, None, None), P(tp, None, None)),
        out_specs=P(dp or None, None), check_vma=False)
    xt = x.reshape(T, D)
    y = fn(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y.reshape(B, S, D)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _init(key, shape, scale=None, dtype=jnp.bfloat16):
    scale = scale if scale is not None else (1.0 / max(shape[0], 1)) ** 0.5
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_attn(key, cfg: ArchConfig, dtype, cross=False):
    H, kv, dh, D = cfg.n_heads, cfg.n_kv, cfg.dh, cfg.d_model
    ks = jax.random.split(key, 8)
    p = {
        "ln": jnp.zeros((D,), dtype),
        "wq": _init(ks[0], (D, H * dh), dtype=dtype),
        "wk": _init(ks[1], (D, kv * dh), dtype=dtype),
        "wv": _init(ks[2], (D, kv * dh), dtype=dtype),
        "wo": _init(ks[3], (H * dh, D), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((kv * dh,), dtype)
        p["bv"] = jnp.zeros((kv * dh,), dtype)
    if cfg.attn_softcap is not None:
        p["post_ln"] = jnp.zeros((D,), dtype)
    return p


def init_mlp(key, cfg: ArchConfig, dtype):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "ln": jnp.zeros((D,), dtype),
        "w_gate": _init(ks[0], (D, F), dtype=dtype),
        "w_up": _init(ks[1], (D, F), dtype=dtype),
        "w_down": _init(ks[2], (F, D), dtype=dtype),
    }


def init_moe(key, cfg: ArchConfig, dtype):
    D, E, F = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_ff
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.zeros((D,), dtype),
        "router": _init(ks[0], (D, E), dtype=jnp.float32),
        "w_gate": _init(ks[1], (E, D, F), dtype=dtype),
        "w_up": _init(ks[2], (E, D, F), dtype=dtype),
        "w_down": _init(ks[3], (E, F, D), dtype=dtype),
    }
