"""Fused training-mode BatchNorm: one-pass Pallas reduce kernels + custom VJP.

Reference: the syncbn native unit (SURVEY.md §2.1 ledger row "syncbn welford +
psum"; the reference's welford.cu computes local stats in one kernel and the
backward's two gradient sums in another).  Round-1 shipped the XLA composite
form; profiling the C2 step on v5e (tools/trace_top.py) showed the XLA
multi-output reduce fusions that implement BN stats/backward-sums running at
~130-250 GB/s — well under the chip's ~300 GB/s streaming rate — with BN
accounting for ~52% of step time.  This module takes control of exactly those
two passes:

  fwd:  (Σ(x-c), Σ(x-c)²) per channel — one Pallas pass over x
  bwd:  (Σdy, Σdy·x̂)      per channel — one Pallas pass over (x, dy)

while the elementwise normalize/apply (fwd) and dx (bwd) stay in XLA, where
they fuse with the surrounding relu/residual chains.  The custom VJP also
pins the saved residuals to {x (input dtype), mean, inv} so no fp32 copy of
the activation is ever materialized for backward.

Cross-replica (SyncBatchNorm) semantics: the caller passes ``axis_name``;
the per-shard kernel sums are psum-merged *inside* the custom VJP — forward
stats and backward sums each cross the mesh exactly once, matching the
reference's two syncbn allreduces (SURVEY.md §4.4).

Gradient contract: outputs are (y, mean, var).  mean/var exist for running-
stat tracking (a flax variable update, which is not differentiated); their
cotangents are ignored in the backward.  Differentiating through mean/var as
data is NOT supported.  The centering constant ``c`` is a buffer whose true
gradient is identically zero (mean = c + Σ(x-c)/n and var are algebraically
invariant in c), so its returned cotangent is exact.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.ops import _config as _cfg
from apex_example_tpu.ops._vma import sds


def _interpret() -> bool:
    return _cfg.interpret()


def _pick_block(rows: int, channels: int, nbufs: int = 1) -> Optional[int]:
    """Largest row-block that divides ``rows``, is a multiple of 8, and keeps
    each of the kernel's ``nbufs`` streamed (blk, C) buffers ≤ ~1 MiB so the
    double-buffered working set stays well inside the 16 MiB VMEM budget.

    Zero-padding would corrupt the *centered* sums (a padded zero contributes
    (0-c) ≠ 0), so the grid must tile rows exactly; batch×spatial row counts
    (N·H·W with N a multiple of 8) always admit a divisor.
    """
    if rows % 8 != 0:
        return None
    limit = max(8, (1 << 19) // (channels * nbufs))   # 512K elems / bufs
    g = max(-(-rows // limit), 1)                     # ceil: block ≤ limit
    while g <= rows // 8:
        if rows % g == 0 and (rows // g) % 8 == 0:
            return rows // g
        g += 1
    return None


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------

def _stats_kernel(x_ref, c_ref, s_ref, ss_ref):
    """One-pass centered moments: accumulate (Σ(x-c), Σ(x-c)²) in fp32."""
    import jax.experimental.pallas as pl

    xc = x_ref[...].astype(jnp.float32) - c_ref[...]

    @pl.when(pl.program_id(0) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)
    s_ref[...] += jnp.sum(xc, axis=0)
    ss_ref[...] += jnp.sum(xc * xc, axis=0)


def _bwd_sums_kernel(x_ref, dy_ref, m_ref, i_ref, s_ref, sx_ref):
    """One-pass backward sums: (Σdy, Σdy·x̂) with x̂ recomputed in-flight."""
    import jax.experimental.pallas as pl

    xhat = (x_ref[...].astype(jnp.float32) - m_ref[...]) * i_ref[...]
    dyf = dy_ref[...].astype(jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        sx_ref[...] = jnp.zeros_like(sx_ref)
    s_ref[...] += jnp.sum(dyf, axis=0)
    sx_ref[...] += jnp.sum(dyf * xhat, axis=0)


def bn_stats(x2: jnp.ndarray, c: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-channel centered sums of a (rows, C) view: (Σ(x-c), Σ(x-c)²)."""
    rows, C = x2.shape
    blk = _pick_block(rows, C, nbufs=1)
    if blk is None or not _cfg.use_pallas_for(x2, c):
        xc = x2.astype(jnp.float32) - c
        return jnp.sum(xc, axis=0), jnp.sum(xc * xc, axis=0)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vec = lambda: pl.BlockSpec((C,), lambda i: (0,),
                               memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _stats_kernel,
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((blk, C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM), vec()],
        out_specs=[vec(), vec()],
        out_shape=[sds((C,), jnp.float32, x2, c)] * 2,
        name="bn_stats",
        interpret=_interpret(),
    )(x2, c)


def bn_bwd_sums(x2: jnp.ndarray, dy2: jnp.ndarray, mean: jnp.ndarray,
                inv: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-channel backward sums over (rows, C) views: (Σdy, Σdy·x̂)."""
    rows, C = x2.shape
    blk = _pick_block(rows, C, nbufs=2)
    if blk is None or not _cfg.use_pallas_for(x2, dy2):
        xhat = (x2.astype(jnp.float32) - mean) * inv
        dyf = dy2.astype(jnp.float32)
        return jnp.sum(dyf, axis=0), jnp.sum(dyf * xhat, axis=0)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vec = lambda: pl.BlockSpec((C,), lambda i: (0,),
                               memory_space=pltpu.VMEM)
    mat = lambda: pl.BlockSpec((blk, C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _bwd_sums_kernel,
        grid=(rows // blk,),
        in_specs=[mat(), mat(), vec(), vec()],
        out_specs=[vec(), vec()],
        out_shape=[sds((C,), jnp.float32, x2, dy2)] * 2,
        name="bn_bwd_sums",
        interpret=_interpret(),
    )(x2, dy2, mean, inv)


# --------------------------------------------------------------------------
# custom-VJP training-mode batch norm
# --------------------------------------------------------------------------

def _rows(x) -> int:
    n = 1
    for d in x.shape[:-1]:
        n *= d
    return n


def _bn_train_impl(x, scale, bias, c, axis_name, eps, apply_dtype,
                   out_dtype):
    C = x.shape[-1]
    rows = _rows(x)
    s, ss = bn_stats(x.reshape(rows, C), c)
    n = jnp.float32(rows)
    if axis_name is not None:
        s = lax.psum(s, axis_name)
        ss = lax.psum(ss, axis_name)
        n = n * lax.axis_size(axis_name)
    mean_c = s / n
    # Var[x] = E[(x-c)²] − (E[x-c])²; exact for any constant shift c.
    var = jnp.maximum(ss / n - mean_c * mean_c, 0.0)
    mean = c + mean_c
    inv = lax.rsqrt(var + eps)

    md = jnp.dtype(apply_dtype)
    y = ((x.astype(md) - mean.astype(md)) * (inv * scale).astype(md)
         + bias.astype(md)).astype(out_dtype)
    return y, mean, var, inv, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def batch_norm_train(x, scale, bias, c, axis_name: Optional[str],
                     eps: float, apply_dtype, out_dtype):
    """Training-mode (Sync)BatchNorm over the last axis of ``x``.

    Args:
      x: (..., C) activations (any float dtype; stats accumulate fp32).
      scale, bias: fp32 (C,) affine parameters.
      c: fp32 (C,) centering constant for the one-pass moments (the running
         mean; any constant is mathematically exact, and tracking the batch
         mean keeps the Σ(x-c)² accumulation cancellation-free).
      axis_name: mesh axis for cross-replica stats, or None.
      eps: variance epsilon.
      apply_dtype: dtype of the normalize-apply arithmetic
         (policy.bn_dtype; fp32 realizes keep_batchnorm_fp32).
      out_dtype: dtype of y (the module's I/O dtype — cast once here so the
         O1 fp32-I/O contract doesn't round-trip through half precision).

    Returns:
      (y, mean, biased_var) — y in out_dtype; mean/var fp32, for running-stat
      updates only (see module docstring for the gradient contract).
    """
    y, mean, var, _, _ = _bn_train_impl(x, scale, bias, c, axis_name, eps,
                                        apply_dtype, out_dtype)
    return y, mean, var


def _bn_train_fwd(x, scale, bias, c, axis_name, eps, apply_dtype, out_dtype):
    y, mean, var, inv, n = _bn_train_impl(x, scale, bias, c, axis_name, eps,
                                          apply_dtype, out_dtype)
    return (y, mean, var), (x, scale, mean, inv, n)


def _bn_train_bwd(axis_name, eps, apply_dtype, out_dtype, saved, cts):
    x, scale, mean, inv, n = saved
    dy, _dmean, _dvar = cts   # mean/var feed undifferentiated buffer updates

    C = x.shape[-1]
    rows = _rows(x)
    sdy, sdyx = bn_bwd_sums(x.reshape(rows, C), dy.reshape(rows, C),
                            mean, inv)
    if axis_name is not None:
        sdy = lax.psum(sdy, axis_name)
        sdyx = lax.psum(sdyx, axis_name)

    dscale = sdyx                       # Σ dy·x̂
    dbias = sdy                         # Σ dy
    # dx = γ·inv·(dy − Σdy/n − x̂·(Σdy·x̂)/n); elementwise — XLA fuses it
    # with the adjacent relu-backward / residual-add chains.
    md = jnp.dtype(apply_dtype)
    g = (scale * inv).astype(md)
    mdy = (sdy / n).astype(md)
    mdyx = (sdyx / n).astype(md)
    xhat = (x.astype(md) - mean.astype(md)) * inv.astype(md)
    dx = (g * (dy.astype(md) - mdy - xhat * mdyx)).astype(x.dtype)
    return dx, dscale, dbias, jnp.zeros_like(mean)


batch_norm_train.defvjp(_bn_train_fwd, _bn_train_bwd)
