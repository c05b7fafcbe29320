"""SyncBatchNorm: cross-replica batch normalization.

Reference (apex/parallel/{sync_batchnorm,optimized_sync_batchnorm}.py +
csrc/syncbn.cpp/welford.cu; SURVEY.md §4.4): local Welford statistics, an
NCCL allreduce of (count, mean, M2) across the process group, normalization
with the global stats, and a matching backward that allreduces the two
gradient sums.

TPU-native design: a Flax module whose statistics cross the ``data`` mesh axis
via ``lax.psum`` *inside* the jitted step — the backward reductions come from
differentiating psum (transpose of psum is psum), so no hand-written backward
is needed.  The Welford merge across shards is exact:

    global_mean = Σ_d sum_d / Σ_d n_d
    global_M2   = Σ_d [ M2_d + n_d (mean_d − global_mean)² ]

Numerics match torch.nn.BatchNorm2d semantics (the golden in our tests):
normalization uses biased variance, running_var stores the unbiased estimate,
``momentum`` is the *new-stat weight* (torch convention, default 0.1 — note
flax's BatchNorm uses the opposite convention).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax


class SyncBatchNorm(nn.Module):
    """Drop-in BatchNorm with optional cross-replica stat reduction.

    With ``axis_name=None`` this is plain BatchNorm (torch semantics).  With
    ``axis_name="data"`` inside shard_map/pmap, batch statistics are the exact
    global-batch statistics — the invariant the reference's two-GPU unit test
    checks (N-shard SyncBN == full-batch BN; SURVEY.md §5).
    """

    use_running_average: Optional[bool] = None
    axis_name: Optional[str] = None
    momentum: float = 0.1          # torch convention: weight of the new stat
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None       # I/O dtype; None → follow input
    stats_dtype: Optional[jnp.dtype] = None  # math/stats dtype; None → fp32
    param_dtype: jnp.dtype = jnp.float32
    use_bias: bool = True
    use_scale: bool = True
    # Route training-mode BN through the custom-VJP Pallas kernel pair
    # (ops/batch_norm.py — the reference's welford.cu analog).  Measured on
    # the v5e-1 rig this LOSES ~44% C2 throughput (2579→1447 img/s): XLA
    # already fuses the stat/backward reduces into the surrounding conv
    # epilogues and elementwise chains, and the opaque kernel boundary
    # forces relayout copies (~40 ms/step of %copy in the trace) — so the
    # XLA composite form below stays the default.  The kernel path remains
    # for parity evidence and for shapes/backends where XLA fuses worse.
    fused_kernel: bool = False

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        # Dtype contract (the reference's keep_batchnorm_fp32 realized the
        # way cuDNN does: half I/O, fp32 math/params/stats — NOT fp32 I/O).
        # ``stats_dtype`` (policy.bn_dtype) is where moments/normalization
        # run; the output follows the *input* dtype so BN fuses into the
        # surrounding bf16 conv/relu chain instead of materializing fp32
        # activations in HBM (profiled: fp32 BN I/O cost ~25% of the O2
        # ResNet-50 step in convert_element_type fusions alone).
        use_ra = nn.merge_param(
            "use_running_average", self.use_running_average,
            use_running_average)
        feat = x.shape[-1]
        reduce_axes = tuple(range(x.ndim - 1))

        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros(feat, jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones(feat, jnp.float32))

        md = jnp.dtype(self.stats_dtype or jnp.float32)
        scale = (self.param("scale", nn.initializers.ones, (feat,),
                            self.param_dtype).astype(jnp.float32)
                 if self.use_scale else jnp.ones(feat, jnp.float32))
        bias = (self.param("bias", nn.initializers.zeros, (feat,),
                           self.param_dtype).astype(jnp.float32)
                if self.use_bias else jnp.zeros(feat, jnp.float32))
        out_dtype = self.dtype or x.dtype

        if use_ra:
            mean, var = ra_mean.value, ra_var.value
            inv = lax.rsqrt(var + self.epsilon).astype(md)
            y = (x.astype(md) - mean.astype(md)) * (inv * scale.astype(md))
            y = y + bias.astype(md)
            return y.astype(out_dtype)

        # Training mode.  Moment ACCUMULATION is always fp32 — Σx/Σx² over
        # ~10⁶ bf16 values cancels catastrophically in bf16 (the reference's
        # cuDNN path likewise never lowers BN stat precision).  The pass is
        # centered on the running mean (a per-channel constant, identical on
        # every replica): shifted moments are exact for any constant shift,
        # and with c tracking the batch mean the Σ(x−c)² accumulation no
        # longer cancels catastrophically when |mean| ≫ std.
        c = ra_mean.value.astype(jnp.float32)
        axis = None if self.is_initializing() else self.axis_name

        if self.fused_kernel:
            # Custom-VJP kernel pair (one Pallas pass fwd, one bwd); the two
            # cross-replica psums live inside batch_norm_train.
            from apex_example_tpu.ops.batch_norm import batch_norm_train
            y, mean, var = batch_norm_train(x, scale, bias, c, axis,
                                            self.epsilon, md, out_dtype)
            n = 1
            for a in reduce_axes:
                n *= x.shape[a]
            if axis is not None:
                n *= lax.axis_size(axis)
        else:
            # XLA composite form: one fused (Σ(x-c), Σ(x-c)²) read, psum
            # Welford merge, elementwise apply.  XLA fuses the stat reduces
            # into the producing conv's epilogue and the apply into the
            # consuming chain — measured faster than the opaque kernel
            # boundary on v5e (see ``fused_kernel``).
            n_local = 1
            for a in reduce_axes:
                n_local *= x.shape[a]
            xc = x.astype(jnp.float32) - c
            local_sum = jnp.sum(xc, axis=reduce_axes)
            local_sumsq = jnp.sum(jnp.square(xc), axis=reduce_axes)
            local_mean_c = local_sum / n_local          # E[x] − c, locally
            local_m2 = local_sumsq - jnp.square(local_mean_c) * n_local

            if axis is not None:
                # Cross-replica Welford merge (reference: syncbn allreduce of
                # (count, mean, M2); here two psums over the mesh axis).
                world = lax.axis_size(axis)
                n = n_local * world
                mean_c = lax.psum(local_sum, axis) / n
                m2 = lax.psum(
                    local_m2 + n_local * jnp.square(local_mean_c - mean_c),
                    axis)
            else:
                n = n_local
                mean_c, m2 = local_mean_c, local_m2
            mean = c + mean_c
            # E[x²]−E[x]² can go fractionally negative under cancellation.
            var = jnp.maximum(m2 / n, 0.0)

            inv = lax.rsqrt(var + self.epsilon).astype(md)
            y = (x.astype(md) - mean.astype(md)) * (inv * scale.astype(md))
            y = y + bias.astype(md)

        if not self.is_initializing():
            m = self.momentum
            unbiased = var * (jnp.float32(n) / max(n - 1, 1))
            ra_mean.value = (1 - m) * ra_mean.value + m * mean
            ra_var.value = (1 - m) * ra_var.value + m * unbiased

        return y.astype(out_dtype)


def convert_syncbn_model(module: nn.Module,
                         axis_name: str = "data") -> nn.Module:
    """Reference parity: apex.parallel.convert_syncbn_model recursively swaps
    nn.BatchNorm for SyncBatchNorm.  Flax modules are immutable dataclasses,
    so models in this framework expose a ``bn_axis_name`` field and conversion
    is a clone with the mesh axis bound.
    """
    if not hasattr(module, "bn_axis_name"):
        raise TypeError(
            f"{type(module).__name__} does not expose bn_axis_name; "
            "only models built with framework norm layers can be converted")
    return module.clone(bn_axis_name=axis_name)
