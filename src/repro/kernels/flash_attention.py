"""Pallas TPU flash-attention forward (the LM stack's hot kernel).

Canonical TPU tiling: grid = (B·H, S_q/BQ, S_k/BK), online-softmax
accumulation in VMEM scratch across the KV grid axis (innermost), output
written on the last KV step.  BQ/BK default to 128/512 — q tile rows sit
on the MXU's 128 sublanes; dh (128/256) fills lanes.

The jnp-chunked attention in ``repro.models.layers.flash_attention`` is
the oracle-equivalent schedule the models use on non-TPU backends; this
kernel is the TPU lowering, validated against ``ref.flash_ref`` in
interpret mode (tests/test_kernels.py sweeps shapes & dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

F32 = jnp.float32
NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  causal: bool, bq: int, bk: int, nk: int, scale: float,
                  softcap):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0] * scale                       # (BQ, dh)
    k = k_ref[0]                               # (BK, dh)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=F32)     # (BQ, BK)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    if causal:
        qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qi >= kj, s, NEG)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1)
    acc_sc[...] = acc_sc[...] * alpha[:, None] + \
        jnp.dot(p.astype(v.dtype), v, preferred_element_type=F32)
    m_sc[...] = m_new

    @pl.when(j == nk - 1)
    def _done():
        denom = jnp.maximum(l_sc[...], 1e-20)[:, None]
        o_ref[0] = (acc_sc[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "softcap", "interpret"))
def flash_attention(q, k, v, *, causal=True, bq=128, bk=512, softcap=None,
                    interpret=None):
    """q,k,v: (BH, S, dh) with kv heads already repeated; returns (BH,S,dh).

    Causal masking is block-exact (whole future blocks still execute but
    are fully masked; the skip-block optimization is recorded as a perf
    lever in EXPERIMENTS.md §Perf).
    """
    BH, S, dh = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    nq, nk = S // bq, S // bk
    grid = (BH, nq, nk)
    kern = functools.partial(
        _flash_kernel, causal=causal, bq=bq, bk=bk, nk=nk,
        scale=dh ** -0.5, softcap=softcap)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), F32),
            pltpu.VMEM((bq,), F32),
            pltpu.VMEM((bq, dh), F32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
