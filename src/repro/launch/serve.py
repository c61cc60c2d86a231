"""Batched decode driver (the serving-side end-to-end path).

PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m --reduced \
    --batch 4 --prompt-len 16 --gen 32

Serving here is the LM-side analogue of the paper's dynamic-vs-static
trade: ``prefill`` is the static full recomputation, each ``serve_step``
is an *incremental* update that touches only the new token's row of the
attention "graph" (DESIGN.md §4) — dynamic processing wins exactly when
the update fraction (1 token vs the 32k context) is small, which is the
paper's headline observation transplanted to inference.

``--graph`` lifts the same driver shape onto graph sessions: a
:class:`repro.serve.SessionPool` of N tenants, each ingesting one ΔG
batch per service tick through the pool's batched mega-call, with
per-tick p50/p99 latency reported the way the decode path reports
tok/s.

PYTHONPATH=src python -m repro.launch.serve --graph --tenants 16 \
    --ticks 12 --batch-size 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.archs import REGISTRY, get_arch
from repro.configs.reduced import reduced
from repro.models import transformer as T
from repro.models.model import Model
from repro.runtime.compile_cache import enable_compile_cache


def serve(args) -> dict:
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = Model(cfg=cfg, dtype=jnp.float32 if args.f32 else jnp.bfloat16)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)

    B, P, G = args.batch, args.prompt_len, args.gen
    S = P + G
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab)
    src = None
    if cfg.family == "vlm":
        src = jnp.ones((B, cfg.n_img_tokens, cfg.d_model), jnp.float32)
    elif cfg.family == "audio":
        src = jnp.ones((B, cfg.enc_seq, cfg.d_model), jnp.float32)

    # ---- prefill (static recomputation over the prompt) -------------------
    t0 = time.time()
    batch = {"tokens": prompts}
    if src is not None:
        batch["src"] = src
    logits, caches = model.prefill_step(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    # pad the prefill cache out to the full decode length
    def pad(x):
        if x.ndim == 5 and x.shape[3] == P:        # (R,B,kv,P,dh)
            return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, S - P), (0, 0)))
        return x
    caches = jax.tree_util.tree_map(pad, caches)

    step_fn = jax.jit(
        lambda p, c, t, pos: model.serve_step(p, c, t, pos, src=src))

    # ---- incremental decode ------------------------------------------------
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out = [np.asarray(tok)]
    t0 = time.time()
    for i in range(G - 1):
        logits, caches = step_fn(params, caches, tok, jnp.asarray(P + i))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok))
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    toks = np.concatenate(out, axis=1)
    tps = B * (G - 1) / max(t_decode, 1e-9)
    print(f"[serve] arch={cfg.name} B={B} prompt={P} gen={G}")
    print(f"[serve] prefill {t_prefill*1e3:.1f} ms "
          f"({B * P / max(t_prefill, 1e-9):.0f} tok/s)")
    print(f"[serve] decode  {t_decode*1e3:.1f} ms "
          f"({tps:.0f} tok/s, {t_decode / max(G - 1, 1) * 1e3:.1f} ms/step)")
    assert np.isfinite(toks).all()
    return {"tokens": toks, "prefill_s": t_prefill, "decode_s": t_decode}


def serve_graphs(args) -> dict:
    """The graph-session serving loop: one pool, ``--tenants`` live
    graphs, one ΔG batch per tenant per tick, drained through the
    batched mega-call.  Prints per-tick p50/p99 and the pool's health
    counters; returns the stats snapshot for callers/tests."""
    from repro.core import registry
    from repro.graph.csr import build_csr, rmat_graph
    from repro.graph.updates import random_updates
    from repro.serve import SessionPool

    n, edges, w = rmat_graph(args.scale, 8, seed=args.seed)
    keep = edges[:, 0] != edges[:, 1]
    csr = build_csr(n, edges[keep], w[keep])
    pool = SessionPool(backend=args.backend, batch_mode=args.batch_mode,
                       max_pending=4 * args.tenants)
    streams = [random_updates(csr, 30, seed=args.seed + 1 + t)
               for t in range(args.tenants)]
    for t in range(args.tenants):
        pool.bind(f"t{t}", csr)
    print(f"[serve] graph pool: backend={args.backend} "
          f"mode={args.batch_mode} tenants={args.tenants} "
          f"n={csr.n} edges={csr.num_edges}")

    ticks = []
    for i in range(args.ticks):
        reqs = [(f"t{t}",
                 streams[t].batch(i % streams[t].num_batches(args.batch_size),
                                  args.batch_size))
                for t in range(args.tenants)]
        t0 = time.time()
        pool.apply_many(reqs)
        jax.block_until_ready([pool.session(f"t{t}")._handle
                               for t in range(args.tenants)])
        ticks.append(time.time() - t0)
    warm = np.asarray(ticks[1:]) if len(ticks) > 1 else np.asarray(ticks)
    p50, p99 = np.percentile(warm, [50, 99])
    stats = pool.stats()
    print(f"[serve] tick p50 {p50 * 1e3:.2f} ms  p99 {p99 * 1e3:.2f} ms  "
          f"({p50 / args.tenants * 1e6:.0f} us/session)")
    print(f"[serve] mega_calls={stats['mega_calls']} "
          f"mega_sessions={stats['mega_sessions']} "
          f"sequential_fallbacks={stats['sequential_fallbacks']} "
          f"applied={stats['applied']}")
    registry.clear_shared_engines()
    return {"p50_s": float(p50), "p99_s": float(p99), "stats": stats}


def parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m", choices=list(REGISTRY))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--f32", action="store_true")
    # --graph mode: multi-tenant graph-session pool instead of LM decode
    ap.add_argument("--graph", action="store_true",
                    help="serve a pool of graph sessions instead of decode")
    ap.add_argument("--backend", default="jnp")
    ap.add_argument("--batch-mode", default="vmap",
                    choices=("vmap", "scan", "off"))
    ap.add_argument("--tenants", type=int, default=16)
    ap.add_argument("--ticks", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--scale", type=int, default=9,
                    help="rmat graph scale (log2 nodes)")
    return ap


def main():
    args = parser().parse_args()
    enable_compile_cache()
    if args.graph:
        serve_graphs(args)
    else:
        serve(args)


if __name__ == "__main__":
    main()
