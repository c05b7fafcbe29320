"""Fused LayerNorm: Pallas TPU kernel with custom VJP + XLA reference.

Reference (csrc/layer_norm_cuda.cpp + layer_norm_cuda_kernel.cu, exposed as
apex.normalization.FusedLayerNorm; SURVEY.md §2.1): a CUDA kernel computes
Welford mean/var per row and normalizes in one pass; the backward kernel
produces dx and the dgamma/dbeta reductions.

TPU-native design: one Pallas kernel per pass, gridded over row blocks.  Rows
live in VMEM; mean/var are row reductions on the VPU; the affine transform is
fused into the same kernel (one HBM round-trip, which is the entire point —
LayerNorm is bandwidth-bound).  Stats are computed in fp32 regardless of the
input dtype (the reference's MixedFusedLayerNorm behavior: bf16 in/out, fp32
params and stats).  The backward recomputes x̂ from the saved fp32 (mean,
rstd) instead of saving it — rematerialization trades a cheap VPU op for HBM.

``layer_norm`` is the public entry: custom_vjp, Pallas on TPU, pure-XLA
elsewhere (tests compare both against torch.nn.LayerNorm goldens).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.ops._vma import align_param_grad, sds

from apex_example_tpu.ops import _config as _cfg


def _use_pallas(x, *more) -> bool:
    if not _cfg.use_pallas_for(x, *more):
        return False
    # Lane-dim constraint on the chip: hidden must tile to 128 for a clean
    # kernel (the interpreter takes any width).
    return _cfg.INTERPRET or (x.shape[-1] >= 128
                              and x.shape[-1] % 128 == 0)


# --------------------------------------------------------------------------
# XLA reference path (also the golden for kernel tests).
# --------------------------------------------------------------------------

def layer_norm_reference(x, gamma, beta, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_reference(x, gamma, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    rstd = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rstd * gamma.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Pallas kernels.
# --------------------------------------------------------------------------

def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    xf = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    xhat = xc * rstd
    y = xhat * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    # Stats are (block, 1) 2-D: rank-1 outputs would pin the row block to
    # Mosaic's 1024-element 1-D tiling (hit on real TPU by hidden=768);
    # rank-2 blocks only need the usual (8, 128) tiling.
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                dx_ref, dg_ref, db_ref):
    xf = x_ref[:].astype(jnp.float32)
    dyf = dy_ref[:].astype(jnp.float32)
    mean = mean_ref[:]          # (block, 1)
    rstd = rstd_ref[:]
    xhat = (xf - mean) * rstd
    gamma = g_ref[:].astype(jnp.float32)

    # dgamma/dbeta: partial sums per row-block, accumulated across the grid.
    dg_ref[:] += jnp.sum(dyf * xhat, axis=0)
    db_ref[:] += jnp.sum(dyf, axis=0)

    # dx = rstd * (dy*g - mean(dy*g) - xhat * mean(dy*g*xhat))
    wdy = dyf * gamma
    c1 = jnp.mean(wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (wdy - c1 - xhat * c2)).astype(dx_ref.dtype)


def _pick_block_rows(n_rows: int, hidden: int, dtype,
                     budget: int = 1024 * 1024) -> int:
    # Row blocks are multiples of 128 (sublane-friendly, and the (block, 1)
    # stat outputs only face the standard 2-D tiling).  ``budget`` bounds the
    # x-block bytes; the kernel's fp32 temporaries multiply it ~4-6x on the
    # VMEM stack (Mosaic's 16 MiB limit — the backward kernel holds x, dy,
    # dx plus four fp32 intermediates, so it passes a halved budget).
    bytes_per = jnp.dtype(dtype).itemsize
    target = budget // max(1, hidden * bytes_per)
    block = max(128, (target // 128) * 128)
    return min(block, max(128, ((n_rows + 127) // 128) * 128))


def _specs(pl, pltpu, block, h):
    """(mat, vec, stat) BlockSpec constructors shared by fwd/bwd plumbing."""
    mat = lambda: pl.BlockSpec((block, h), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)
    vec = lambda: pl.BlockSpec((h,), lambda i: (0,),
                               memory_space=pltpu.VMEM)
    stat = lambda: pl.BlockSpec((block, 1), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)
    return mat, vec, stat


def _norm_fwd_pallas(x2d, gamma, beta, eps):
    """Shared fwd plumbing for LayerNorm (beta given) and RMSNorm (beta
    None): block picking, row padding, specs, and the (block, 1) stat rule.

    Returns (y, mean|None, rstd)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with_mean = beta is not None
    n, h = x2d.shape
    block = _pick_block_rows(n, h, x2d.dtype)
    pad = (-n) % block
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    np_ = x2d.shape[0]

    mat, vec, stat = _specs(pl, pltpu, block, h)
    n_stats = 2 if with_mean else 1
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel if with_mean else _rms_fwd_kernel,
                          eps=eps),
        grid=(np_ // block,),
        in_specs=[mat()] + [vec()] * (2 if with_mean else 1),
        out_specs=[mat()] + [stat()] * n_stats,
        out_shape=([sds((np_, h), x2d.dtype, x2d)]
                   + [sds((np_, 1), jnp.float32, x2d)] * n_stats),
        name=("layer_norm_fwd" if with_mean else "rms_norm_fwd"),
        interpret=_cfg.INTERPRET,
    )(*([x2d, gamma, beta] if with_mean else [x2d, gamma]))
    if with_mean:
        y, mean, rstd = outs
        return y[:n], mean[:n, 0], rstd[:n, 0]
    y, rstd = outs
    return y[:n], None, rstd[:n, 0]


def _norm_bwd_pallas(x2d, gamma, mean, rstd, dy2d):
    """Shared bwd plumbing: LayerNorm when ``mean`` is given (emits dx, dg,
    db), RMSNorm when ``mean`` is None (emits dx, dg)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with_mean = mean is not None
    n, h = x2d.shape
    block = _pick_block_rows(n, h, x2d.dtype, budget=512 * 1024)
    pad = (-n) % block
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
        dy2d = jnp.pad(dy2d, ((0, pad), (0, 0)))
        if with_mean:
            mean = jnp.pad(mean, (0, pad))
        rstd = jnp.pad(rstd, (0, pad))  # padded rows: rstd 0 => contribute 0
    stats2 = ([mean[:, None]] if with_mean else []) + [rstd[:, None]]
    np_ = x2d.shape[0]
    n_grads = 2 if with_mean else 1     # dg (+ db)

    def bwd_with_init(*refs):
        from jax.experimental import pallas as pl2

        @pl2.when(pl2.program_id(0) == 0)
        def _():
            # the trailing refs are the across-grid accumulators (dg [, db])
            for r in refs[-n_grads:]:
                r[:] = jnp.zeros_like(r)
        (_bwd_kernel if with_mean else _rms_bwd_kernel)(*refs)

    mat, vec, stat = _specs(pl, pltpu, block, h)
    outs = pl.pallas_call(
        bwd_with_init,
        grid=(np_ // block,),
        in_specs=([mat(), vec()] + [stat()] * len(stats2) + [mat()]),
        # dgamma/dbeta accumulate across sequential grid steps: every step
        # maps to the same block (TPU grids are sequential).
        out_specs=[mat()] + [vec()] * n_grads,
        out_shape=([sds((np_, h), x2d.dtype, x2d, dy2d)]
                   + [sds((h,), jnp.float32, x2d, dy2d, gamma)] * n_grads),
        name=("layer_norm_bwd" if with_mean else "rms_norm_bwd"),
        interpret=_cfg.INTERPRET,
    )(x2d, gamma, *stats2, dy2d)
    dx = outs[0][:n] if pad else outs[0]
    return (dx, *outs[1:])


# --------------------------------------------------------------------------
# Public op with custom VJP.
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, gamma, beta, eps: float = 1e-5):
    """Fused LayerNorm over the last axis.  x: (..., H); gamma/beta: (H,)."""
    y, _, _ = _layer_norm_fwd(x, gamma, beta, eps)
    return y


def _layer_norm_fwd(x, gamma, beta, eps):
    shape = x.shape
    h = shape[-1]
    x2d = x.reshape(-1, h)
    if _use_pallas(x2d):
        y, mean, rstd = _norm_fwd_pallas(x2d, gamma, beta, eps)
    else:
        xf = x2d.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1)
        var = jnp.mean(jnp.square(xf - mean[:, None]), axis=-1)
        rstd = lax.rsqrt(var + eps)
        y = ((xf - mean[:, None]) * rstd[:, None]
             * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
             ).astype(x.dtype)
    return y.reshape(shape), mean, rstd


def _layer_norm_fwd_vjp(x, gamma, beta, eps):
    y, mean, rstd = _layer_norm_fwd(x, gamma, beta, eps)
    return y, (x, gamma, mean, rstd)


def _layer_norm_bwd_vjp(eps, res, dy):
    del eps
    x, gamma, mean, rstd = res
    shape = x.shape
    h = shape[-1]
    x2d = x.reshape(-1, h)
    dy2d = dy.reshape(-1, h)
    if _use_pallas(x2d, dy2d):
        dx, dg, db = _norm_bwd_pallas(x2d, gamma, mean, rstd, dy2d)
    else:
        xf = x2d.astype(jnp.float32)
        dyf = dy2d.astype(jnp.float32)
        xhat = (xf - mean[:, None]) * rstd[:, None]
        gf = gamma.astype(jnp.float32)
        dg = jnp.sum(dyf * xhat, axis=0)
        db = jnp.sum(dyf, axis=0)
        wdy = dyf * gf
        c1 = jnp.mean(wdy, axis=-1, keepdims=True)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = (rstd[:, None] * (wdy - c1 - xhat * c2)).astype(x.dtype)
    # Mesh-invariant gamma/beta get mesh-invariant (psum-ed) grads — the
    # reduction regular primitives receive from vma-aware AD (see
    # _vma.align_param_grad).
    dg = align_param_grad(dg, gamma)
    db = align_param_grad(db, gamma)
    return (dx.reshape(shape), dg.astype(gamma.dtype), db.astype(gamma.dtype))


layer_norm.defvjp(_layer_norm_fwd_vjp, _layer_norm_bwd_vjp)


# --------------------------------------------------------------------------
# FusedRMSNorm (reference: the later apex FusedRMSNorm in
# apex/normalization/fused_layer_norm.py, SURVEY.md §3.4): LayerNorm minus
# the mean subtraction — rstd over E[x²], no beta.  Same blocking and the
# same rank-2 (rows, 1) stat-output rule as layer_norm above.
# --------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, g_ref, y_ref, rstd_ref, *, eps):
    xf = x_ref[:].astype(jnp.float32)
    rstd = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y_ref[:] = (xf * rstd * g_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _rms_bwd_kernel(x_ref, g_ref, rstd_ref, dy_ref, dx_ref, dg_ref):
    xf = x_ref[:].astype(jnp.float32)
    dyf = dy_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]                   # (block, 1)
    xhat = xf * rstd
    wdy = dyf * g_ref[:].astype(jnp.float32)

    dg_ref[:] += jnp.sum(dyf * xhat, axis=0)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (wdy - xhat * c2)).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, gamma, eps: float = 1e-5):
    """Fused RMSNorm over the last axis.  x: (..., H); gamma: (H,)."""
    y, _ = _rms_norm_fwd(x, gamma, eps)
    return y


def _rms_norm_fwd(x, gamma, eps):
    shape = x.shape
    h = shape[-1]
    x2d = x.reshape(-1, h)
    if _use_pallas(x2d):
        y, _, rstd = _norm_fwd_pallas(x2d, gamma, None, eps)
    else:
        xf = x2d.astype(jnp.float32)
        rstd = lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)
        y = (xf * rstd[:, None] * gamma.astype(jnp.float32)).astype(x.dtype)
    return y.reshape(shape), rstd


def _rms_norm_fwd_vjp(x, gamma, eps):
    y, rstd = _rms_norm_fwd(x, gamma, eps)
    return y, (x, gamma, rstd)


def _rms_norm_bwd_vjp(eps, res, dy):
    del eps
    x, gamma, rstd = res
    shape = x.shape
    h = shape[-1]
    x2d = x.reshape(-1, h)
    dy2d = dy.reshape(-1, h)
    if _use_pallas(x2d, dy2d):
        dx, dg = _norm_bwd_pallas(x2d, gamma, None, rstd, dy2d)
    else:
        xf = x2d.astype(jnp.float32)
        dyf = dy2d.astype(jnp.float32)
        xhat = xf * rstd[:, None]
        wdy = dyf * gamma.astype(jnp.float32)
        dg = jnp.sum(dyf * xhat, axis=0)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = (rstd[:, None] * (wdy - xhat * c2)).astype(x.dtype)
    dg = align_param_grad(dg, gamma)
    return dx.reshape(shape), dg.astype(gamma.dtype)


rms_norm.defvjp(_rms_norm_fwd_vjp, _rms_norm_bwd_vjp)
