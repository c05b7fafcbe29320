"""Fused softmax cross-entropy with label smoothing.

Reference: apex.contrib.xentropy (``SoftmaxCrossEntropyLoss``, backed by
apex/contrib/csrc/xentropy — SURVEY.md §2.1 contrib row): one CUDA kernel
computes the loss without materializing log-softmax, and the backward
rebuilds ``softmax − target`` on the fly.

TPU-native design: a ``custom_vjp`` over the logsumexp form.  The forward
saves only ``(logits, labels, lse)`` — logits are an input the caller
already holds, and lse is O(tokens) — and the backward REMATERIALIZES the
(tokens, V) probability tensor as ``exp(logits − lse)`` instead of storing
it.  Under plain autodiff the residual set includes an O(tokens·V) tensor
(log-softmax or probs); dropping that residual is the entire point of the
contrib kernel, and XLA fuses the rematerialized exp into the backward's
subtract.  (How many tokens reach it is the caller's: BERT's train step
hands it blocks of the labelled rows, ``workloads.mlm_loss.over_rows``, so
the B·S·V logits themselves — ~4 GB in fp32 at BERT's shape — are never
formed; evaluation and the LM losses hand it every row.)  No Pallas kernel is
needed: both passes are single fused elementwise+reduce sweeps, which XLA
already emits optimally (the same rely-on-XLA stance as fused_dense,
SURVEY.md §2.1).

Smoothing semantics match torch/apex: the target distribution is
``(1−ε)·δ_y + ε/V`` uniformly over the V classes, i.e.
``loss = lse − (1−ε)·z_y − (ε/V)·Σ_j z_j``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["softmax_cross_entropy", "softmax_cross_entropy_reference"]


def softmax_cross_entropy_reference(logits, labels, smoothing: float = 0.0):
    """Plain-autodiff form (test golden): per-example loss, fp32."""
    lf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(lf, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if smoothing == 0.0:
        return nll
    return (1.0 - smoothing) * nll - smoothing * jnp.mean(logp, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_cross_entropy(logits, labels, smoothing: float = 0.0):
    """Per-example softmax CE: logits (..., V) any float dtype, labels
    (...,) int; returns fp32 losses of shape (...).  The backward never
    stores the (..., V) probability tensor (see module docstring)."""
    loss, _ = _xent_fwd(logits, labels, smoothing)
    return loss


def _xent_fwd(logits, labels, smoothing):
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    z_y = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    loss = lse - z_y
    if smoothing:
        v = logits.shape[-1]
        # lse − (1−ε)z_y − (ε/V)Σz  ==  (1−ε)(lse − z_y) + ε(lse − mean z)
        loss = loss + smoothing * (z_y - jnp.mean(lf, axis=-1))
    return loss, lse


def _xent_fwd_vjp(logits, labels, smoothing):
    loss, lse = _xent_fwd(logits, labels, smoothing)
    return loss, (logits, labels, lse)


def _xent_bwd_vjp(smoothing, res, dloss):
    logits, labels, lse = res
    lf = logits.astype(jnp.float32)
    p = jnp.exp(lf - lse[..., None])          # rematerialized, fused by XLA
    v = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, v, dtype=jnp.float32)
    target = (1.0 - smoothing) * onehot + smoothing / v
    dlogits = (p - target) * dloss[..., None].astype(jnp.float32)
    return dlogits.astype(logits.dtype), None


softmax_cross_entropy.defvjp(_xent_fwd_vjp, _xent_bwd_vjp)
