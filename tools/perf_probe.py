#!/usr/bin/env python
"""Perf probe: apportion ResNet-50 O2 step time across phases on the real chip.

Times, with the same two-point chain method bench.py uses (each chain ends
in a value fetch; differencing two lengths cancels the fetch):
  - fwd:       forward loss only
  - fwdbwd:    loss + grad
  - full:      the real train step (grad + allreduce-less + optimizer + scaler)
  - opt:       optimizer apply alone on a fixed grad tree

Usage: python tools/perf_probe.py [--batch-size 256] [--steps 20]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from apex_example_tpu import amp
from apex_example_tpu.data import image_batch
from apex_example_tpu.engine import (create_train_state, make_train_step,
                                     cross_entropy_loss, _apply_model)
from apex_example_tpu.models import resnet50
from apex_example_tpu.optim import FusedSGD


def chain_time(fn, state, n_warm, n1, n2, fetch):
    for _ in range(n_warm):
        state = fn(state)
    fetch(state)
    t0 = time.perf_counter()
    for _ in range(n1):
        state = fn(state)
    fetch(state)
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n2):
        state = fn(state)
    fetch(state)
    t2 = time.perf_counter() - t0
    return (t2 - t1) / (n2 - n1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--image-size", type=int, default=224)
    args = ap.parse_args()

    policy, scaler = amp.initialize("O2")
    model = resnet50(num_classes=1000, dtype=policy.compute_dtype,
                     param_dtype=policy.param_dtype, bn_dtype=policy.bn_dtype)
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)

    batch = image_batch(jnp.asarray(0), batch_size=args.batch_size,
                        image_size=args.image_size, channels=3,
                        num_classes=1000, seed=0)
    batch = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, jax.devices()[0]), batch)
    x, y = batch

    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               x[:1], policy, scaler)
    n1, n2 = max(args.steps // 5, 1), args.steps
    bs = args.batch_size

    # --- full step ---
    step = jax.jit(make_train_step(model, opt, policy),
                   donate_argnums=(0,))
    full = chain_time(lambda s: step(s, batch)[0], state, 3, n1, n2,
                      lambda s: float(s.step))
    print(f"full step:   {full*1e3:8.2f} ms  ({bs/full:7.1f} img/s)")

    # --- fwd only (train-mode apply + loss; carry loss to chain deps) ---
    def fwd(carry):
        p, s, acc = carry
        logits, new_stats = _apply_model(model, p, s, x, train=True)
        return p, new_stats, acc + cross_entropy_loss(logits, y)
    fwd_j = jax.jit(fwd, donate_argnums=(0,))
    state2 = create_train_state(jax.random.PRNGKey(0), model, opt, x[:1],
                                policy, scaler)
    c0 = (state2.params, state2.batch_stats, jnp.zeros((), jnp.float32))
    tf = chain_time(fwd_j, c0, 3, n1, n2, lambda c: float(c[2]))
    print(f"fwd only:    {tf*1e3:8.2f} ms  ({bs/tf:7.1f} img/s)")

    # --- fwd+bwd (grad, no optimizer) ---
    def fb(carry):
        p, s, acc = carry
        def loss_fn(params):
            logits, new_stats = _apply_model(model, params, s, x, train=True)
            return cross_entropy_loss(logits, y), new_stats
        g, new_stats = jax.grad(loss_fn, has_aux=True)(p)
        # fold grads back so the chain has a data dependence
        p2 = jax.tree_util.tree_map(lambda a, b: a - 0.0 * b, p, g)
        return p2, new_stats, acc + g["fc"]["bias"][0]
    fb_j = jax.jit(fb, donate_argnums=(0,))
    state3 = create_train_state(jax.random.PRNGKey(0), model, opt, x[:1],
                                policy, scaler)
    c0 = (state3.params, state3.batch_stats, jnp.zeros((), jnp.float32))
    tfb = chain_time(fb_j, c0, 3, n1, n2, lambda c: float(c[2]))
    print(f"fwd+bwd:     {tfb*1e3:8.2f} ms  ({bs/tfb:7.1f} img/s)")

    # --- optimizer alone ---
    state4 = create_train_state(jax.random.PRNGKey(0), model, opt, x[:1],
                                policy, scaler)
    grads = jax.tree_util.tree_map(lambda p: jnp.ones_like(p, jnp.float32),
                                   state4.params)

    def opt_only(carry):
        params, opt_state = carry
        return opt.apply(grads, opt_state, params)
    opt_j = jax.jit(opt_only, donate_argnums=(0,))
    c0 = (state4.params, state4.opt_state)
    topt = chain_time(opt_j, c0, 3, n1, n2,
                      lambda c: float(jax.tree_util.tree_leaves(c[0])[0].ravel()[0]))
    print(f"opt only:    {topt*1e3:8.2f} ms")

    print(f"derived bwd: {(tfb-tf)*1e3:8.2f} ms")
    print(f"step - fwdbwd - opt = {(full-tfb-topt)*1e3:8.2f} ms (scaler/misc)")


if __name__ == "__main__":
    main()
