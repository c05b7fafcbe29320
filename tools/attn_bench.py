"""Attention micro-benchmark: naive XLA vs flash kernel vs ring variants.

Standalone evidence tool for the PERF.md flash-attention table (run on the
chip).  Times fwd+bwd of each attention form at several sequence lengths
inside one jit (fori_loop with a carried data dependence), so dispatch
cost stays out of the per-iteration time.

    python tools/attn_bench.py [--seqs 512,2048,8192] [--iters 8]
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")


def _chain(fn, args, iters):
    """Time fn(*args) iterated with a carried data dependence, two chain
    lengths, differenced so the fixed launch cost cancels."""
    def run(n):
        def body(i, a):
            q, k, v = a
            g = fn(q, k, v)
            return (q + 0.0 * g[0], k, v)

        out = jax.lax.fori_loop(0, n, body, args)
        return out[0].sum()

    r1 = jax.jit(run, static_argnums=0)
    float(r1(1))                       # compile + warm
    t0 = time.time(); float(r1(1)); t1 = time.time() - t0
    t0 = time.time(); float(r1(1 + iters)); t2 = time.time() - t0
    return (t2 - t1) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="512,2048,4096")
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=8192,
                    help="batch*seq kept ~constant across rows")
    args = ap.parse_args()

    from apex_example_tpu.ops.attention import (attention_reference,
                                                flash_attention)

    def grad_of(f):
        g = jax.grad(lambda q, k, v: jnp.sum(
            jnp.square(f(q, k, v).astype(jnp.float32))), argnums=(0, 1, 2))
        return lambda q, k, v: g(q, k, v)[0]

    for s in (int(x) for x in args.seqs.split(",")):
        b = max(1, args.tokens // s)
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (b, s, args.heads, args.head_dim),
                                     jnp.bfloat16) for kk in ks)
        for name, f in (("naive", attention_reference),
                        ("flash", flash_attention)):
            fwd = _chain(lambda q, k, v, f=f: f(q, k, v), (q, k, v),
                         args.iters)
            bwd = _chain(grad_of(f), (q, k, v), args.iters)
            print(f"S={s:6d} b={b:3d} {name:6s} "
                  f"fwd {fwd * 1e3:8.2f} ms  fwd+bwd {bwd * 1e3:8.2f} ms",
                  flush=True)


if __name__ == "__main__":
    main()
