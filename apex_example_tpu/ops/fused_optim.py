"""Fused optimizer update kernels (Adam/AdamW, LAMB stages, SGD+momentum,
NovoGrad).

Reference (csrc/multi_tensor_adam.cu, multi_tensor_lamb.cu with
lamb_stage_1/lamb_stage_2, multi_tensor_sgd_kernel.cu,
multi_tensor_novograd.cu; SURVEY.md §2.1): one CUDA launch updates chunks of
(p, g, m, v) in place for the whole param list.

TPU-native design: the payoff of fusion here is reading p/g/m/v from HBM once
and writing p'/m'/v' once — a Pallas kernel per leaf does exactly that, with
``input_output_aliases`` donating p/m/v so XLA updates in place.  Hyper-
parameters and bias corrections arrive as an SMEM scalar vector, so one
compiled kernel serves every step (step count enters only through the scalar
values, keeping the trace static).

LAMB keeps the reference's two-stage shape: stage 1 produces the Adam-style
update plus per-tensor squared norms of param and update (the per-block
partial-norms trick collapses into the same kernel); the per-tensor trust
ratios are O(#tensors) scalar work done in XLA; stage 2 is a scaled apply.

XLA reference implementations live alongside (``*_reference``) and serve as
CPU fallback and as the golden in kernel tests (which additionally compare
against torch.optim on identical data, SURVEY.md §5).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from apex_example_tpu.ops import _config as _cfg
from apex_example_tpu.ops._vma import sds
from apex_example_tpu.ops.multi_tensor import (_LANES, _grid_rows,
                                               _pad_rows, _to_lanes,
                                               _unpad)


def _interpret() -> bool:
    return _cfg.interpret()


def _use_pallas(*operands) -> bool:
    return _cfg.use_pallas_for(*operands)


# --------------------------------------------------------------------------
# Adam / AdamW
# --------------------------------------------------------------------------

def _adam_kernel(p_ref, g_ref, m_ref, v_ref, s_ref,
                 po_ref, mo_ref, vo_ref, *, adam_w):
    lr, b1, b2, eps, wd, c1, c2 = (s_ref[i] for i in range(7))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    if not adam_w:            # classic Adam: L2 folded into the gradient
        g = g + wd * p
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    update = (m * c1) / (jnp.sqrt(v * c2) + eps)
    if adam_w:                # AdamW: decoupled decay on the param
        update = update + wd * p
    p = p - lr * update

    po_ref[:] = p.astype(po_ref.dtype)
    mo_ref[:] = m.astype(mo_ref.dtype)
    vo_ref[:] = v.astype(vo_ref.dtype)


def adam_update_leaf(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay,
                     bias_c1, bias_c2, adam_w_mode: bool = True):
    """One fused Adam step for one leaf.  Scalars may be traced values."""
    if not _use_pallas(p, g, m, v):
        return adam_update_leaf_reference(
            p, g, m, v, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
            weight_decay=weight_decay, bias_c1=bias_c1, bias_c2=bias_c2,
            adam_w_mode=adam_w_mode)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p2, n = _to_lanes(p)
    g2, _ = _to_lanes(g)
    m2, _ = _to_lanes(m)
    v2, _ = _to_lanes(v)
    rows = p2.shape[0]
    block, pad = _grid_rows(rows)
    p2, g2, m2, v2 = (_pad_rows(t, pad) for t in (p2, g2, m2, v2))
    grid = p2.shape[0] // block
    scal = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                      (lr, beta1, beta2, eps, weight_decay,
                       bias_c1, bias_c2)])

    bspec = lambda: pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
    po, mo, vo = pl.pallas_call(
        functools.partial(_adam_kernel, adam_w=adam_w_mode),
        grid=(grid,),
        in_specs=[bspec(), bspec(), bspec(), bspec(),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bspec(), bspec(), bspec()],
        out_shape=[sds(p2.shape, p.dtype, p2, g2, m2, v2),
                   sds(p2.shape, m.dtype, p2, g2, m2, v2),
                   sds(p2.shape, v.dtype, p2, g2, m2, v2)],
        input_output_aliases={0: 0, 2: 1, 3: 2},
        name="adam",
        interpret=_interpret(),
    )(p2, g2, m2, v2, scal)

    return _unpad(po, n, p), _unpad(mo, n, m), _unpad(vo, n, v)


def adam_update_leaf_reference(p, g, m, v, *, lr, beta1, beta2, eps,
                               weight_decay, bias_c1, bias_c2,
                               adam_w_mode: bool = True):
    pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
    mf, vf = m.astype(jnp.float32), v.astype(jnp.float32)
    if not adam_w_mode:
        gf = gf + weight_decay * pf
    mf = beta1 * mf + (1.0 - beta1) * gf
    vf = beta2 * vf + (1.0 - beta2) * gf * gf
    upd = (mf * bias_c1) / (jnp.sqrt(vf * bias_c2) + eps)
    if adam_w_mode:
        upd = upd + weight_decay * pf
    pf = pf - lr * upd
    return pf.astype(p.dtype), mf.astype(m.dtype), vf.astype(v.dtype)


# --------------------------------------------------------------------------
# LAMB stage 1: Adam-style update + per-tensor sq-norms of param and update
# --------------------------------------------------------------------------

def _lamb1_kernel(p_ref, g_ref, m_ref, v_ref, s_ref,
                  u_ref, mo_ref, vo_ref, norms_ref, *, nrows):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        norms_ref[0] = jnp.zeros((), jnp.float32)
        norms_ref[1] = jnp.zeros((), jnp.float32)

    b1, b2, eps, wd, c1, c2, gscale = (s_ref[i] for i in range(7))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale   # global grad-norm clip factor
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    u = (m * c1) / (jnp.sqrt(v * c2) + eps) + wd * p

    # Padded tail rows hold zeros, so they add nothing to the norms.  Rows
    # beyond the true element count n were zero-padded in _to_lanes.
    del nrows
    norms_ref[0] += jnp.sum(p * p)
    norms_ref[1] += jnp.sum(u * u)

    u_ref[:] = u
    mo_ref[:] = m.astype(mo_ref.dtype)
    vo_ref[:] = v.astype(vo_ref.dtype)


def lamb_stage1_leaf(p, g, m, v, *, beta1, beta2, eps, weight_decay,
                     bias_c1, bias_c2, grad_scale=1.0):
    """Returns (update, m', v', ||p||², ||update||²) for one leaf."""
    if not _use_pallas(p, g, m, v):
        pf, gf = p.astype(jnp.float32), g.astype(jnp.float32) * grad_scale
        mf, vf = m.astype(jnp.float32), v.astype(jnp.float32)
        mf = beta1 * mf + (1.0 - beta1) * gf
        vf = beta2 * vf + (1.0 - beta2) * gf * gf
        u = (mf * bias_c1) / (jnp.sqrt(vf * bias_c2) + eps) + weight_decay * pf
        return (u, mf.astype(m.dtype), vf.astype(v.dtype),
                jnp.sum(pf * pf), jnp.sum(u * u))

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p2, n = _to_lanes(p)
    g2, _ = _to_lanes(g)
    m2, _ = _to_lanes(m)
    v2, _ = _to_lanes(v)
    rows = p2.shape[0]
    block, pad = _grid_rows(rows)
    p2, g2, m2, v2 = (_pad_rows(t, pad) for t in (p2, g2, m2, v2))
    grid = p2.shape[0] // block
    scal = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                      (beta1, beta2, eps, weight_decay, bias_c1, bias_c2,
                       grad_scale)])

    bspec = lambda: pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
    u, mo, vo, norms = pl.pallas_call(
        functools.partial(_lamb1_kernel, nrows=rows),
        grid=(grid,),
        in_specs=[bspec(), bspec(), bspec(), bspec(),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bspec(), bspec(), bspec(),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[sds(p2.shape, jnp.float32, p2, g2, m2, v2),
                   sds(p2.shape, m.dtype, p2, g2, m2, v2),
                   sds(p2.shape, v.dtype, p2, g2, m2, v2),
                   sds((2,), jnp.float32, p2, g2, m2, v2)],
        input_output_aliases={2: 1, 3: 2},
        name="lamb_stage1",
        interpret=_interpret(),
    )(p2, g2, m2, v2, scal)

    return (_unpad(u, n, p), _unpad(mo, n, m), _unpad(vo, n, v),
            norms[0], norms[1])


# --------------------------------------------------------------------------
# LAMB stage 2: p -= lr * trust_ratio * update  (an axpby specialization)
# --------------------------------------------------------------------------

def _lamb2_kernel(p_ref, u_ref, s_ref, po_ref):
    po_ref[:] = (p_ref[:].astype(jnp.float32)
                 - s_ref[0] * u_ref[:].astype(jnp.float32)
                 ).astype(po_ref.dtype)


def lamb_stage2_leaf(p, update, scaled_lr):
    """p' = p - scaled_lr * update (scaled_lr = lr * trust_ratio, traced)."""
    if not _use_pallas(p, update):
        return (p.astype(jnp.float32)
                - scaled_lr * update.astype(jnp.float32)).astype(p.dtype)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p2, n = _to_lanes(p)
    u2, _ = _to_lanes(update)
    rows = p2.shape[0]
    block, pad = _grid_rows(rows)
    p2, u2 = _pad_rows(p2, pad), _pad_rows(u2, pad)
    grid = p2.shape[0] // block
    bspec = lambda: pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
    po = pl.pallas_call(
        _lamb2_kernel,
        grid=(grid,),
        in_specs=[bspec(), bspec(),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=bspec(),
        out_shape=sds(p2.shape, p.dtype, p2, u2),
        input_output_aliases={0: 0},
        name="lamb_stage2",
        interpret=_interpret(),
    )(p2, u2, jnp.asarray(scaled_lr, jnp.float32).reshape(1))
    return _unpad(po, n, p)


# --------------------------------------------------------------------------
# SGD (+ momentum, nesterov)
# --------------------------------------------------------------------------

def _sgd_kernel(p_ref, g_ref, b_ref, s_ref, po_ref, bo_ref, *, nesterov,
                first_step):
    lr, mom, wd, damp = (s_ref[i] for i in range(4))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    g = g + wd * p
    if first_step:
        buf = g          # torch: first momentum buffer is the (decayed) grad
    else:
        buf = mom * b_ref[:].astype(jnp.float32) + (1.0 - damp) * g
    step_dir = (g + mom * buf) if nesterov else buf
    po_ref[:] = (p - lr * step_dir).astype(po_ref.dtype)
    bo_ref[:] = buf.astype(bo_ref.dtype)


def _novograd_kernel(p_ref, g_ref, m_ref, s_ref, po_ref, mo_ref):
    inv_denom, wd, b1, ga, lr_c1 = (s_ref[i] for i in range(5))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:].astype(jnp.float32)
    g_hat = g * inv_denom + wd * p       # normalized grad + L2 (reg outside)
    m = b1 * m + ga * g_hat
    po_ref[:] = (p - lr_c1 * m).astype(po_ref.dtype)
    mo_ref[:] = m.astype(mo_ref.dtype)


def novograd_update_leaf(p, g, m, *, inv_denom, lr_c1, beta1, weight_decay,
                         grad_avg_coeff):
    """Fused NovoGrad apply for one leaf, given the per-tensor normalization
    scalar ``inv_denom`` = 1/(sqrt(v̂)+eps) (reference:
    multi_tensor_novograd.cu — the per-tensor second moment is the squared
    grad L2-norm, so it is scalar work outside the elementwise kernel).

    g_hat = g*inv_denom + wd*p;  m' = b1*m + ga*g_hat;  p' = p − lr_c1*m'
    (lr_c1 folds the bias correction 1/(1−b1^t) into the learning rate).
    """
    if not _use_pallas(p, g, m):
        pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
        mf = m.astype(jnp.float32)
        g_hat = gf * inv_denom + weight_decay * pf
        mf = beta1 * mf + grad_avg_coeff * g_hat
        return (pf - lr_c1 * mf).astype(p.dtype), mf.astype(m.dtype)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p2, n = _to_lanes(p)
    g2, _ = _to_lanes(g)
    m2, _ = _to_lanes(m)
    rows = p2.shape[0]
    block, pad = _grid_rows(rows)
    p2, g2, m2 = (_pad_rows(t, pad) for t in (p2, g2, m2))
    grid = p2.shape[0] // block
    scal = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                      (inv_denom, weight_decay, beta1, grad_avg_coeff,
                       lr_c1)])
    bspec = lambda: pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
    po, mo = pl.pallas_call(
        _novograd_kernel,
        grid=(grid,),
        in_specs=[bspec(), bspec(), bspec(),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bspec(), bspec()],
        out_shape=[sds(p2.shape, p.dtype, p2, g2, m2),
                   sds(p2.shape, m.dtype, p2, g2, m2)],
        input_output_aliases={0: 0, 2: 1},
        name="novograd",
        interpret=_interpret(),
    )(p2, g2, m2, scal)
    return _unpad(po, n, p), _unpad(mo, n, m)


def sgd_update_leaf(p, g, buf, *, lr, momentum, weight_decay, dampening=0.0,
                    nesterov=False, first_step=False):
    """Fused momentum-SGD step (reference: multi_tensor_sgd_kernel.cu)."""
    if not _use_pallas(p, g, buf):
        pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
        gf = gf + weight_decay * pf
        if first_step:
            nb = gf          # torch: first buffer is the (decayed) grad
        else:
            nb = momentum * buf.astype(jnp.float32) + (1.0 - dampening) * gf
        step_dir = (gf + momentum * nb) if nesterov else nb
        return (pf - lr * step_dir).astype(p.dtype), nb.astype(buf.dtype)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p2, n = _to_lanes(p)
    g2, _ = _to_lanes(g)
    b2, _ = _to_lanes(buf)
    rows = p2.shape[0]
    block, pad = _grid_rows(rows)
    p2, g2, b2 = (_pad_rows(t, pad) for t in (p2, g2, b2))
    grid = p2.shape[0] // block
    scal = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                      (lr, momentum, weight_decay, dampening)])
    bspec = lambda: pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
    po, bo = pl.pallas_call(
        functools.partial(_sgd_kernel, nesterov=nesterov,
                          first_step=first_step),
        grid=(grid,),
        in_specs=[bspec(), bspec(), bspec(),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bspec(), bspec()],
        out_shape=[sds(p2.shape, p.dtype, p2, g2, b2),
                   sds(p2.shape, buf.dtype, p2, g2, b2)],
        input_output_aliases={0: 0, 2: 1},
        name="sgd",
        interpret=_interpret(),
    )(p2, g2, b2, scal)
    return _unpad(po, n, p), _unpad(bo, n, buf)


# --------------------------------------------------------------------------
# Adagrad (reference: apex/optimizers/fused_adagrad.py backed by
# multi_tensor_adagrad.cu): h += g²; p -= lr·g/(√h + eps).  Weight decay is
# L2-into-the-gradient by default, decoupled under adagrad_w_mode — the same
# switch FusedAdam exposes.
# --------------------------------------------------------------------------

def _adagrad_kernel(p_ref, g_ref, h_ref, s_ref, po_ref, ho_ref, *,
                    adagrad_w):
    lr, eps, wd = (s_ref[i] for i in range(3))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    h = h_ref[:].astype(jnp.float32)
    if not adagrad_w:
        g = g + wd * p
    h = h + g * g
    upd = g / (jnp.sqrt(h) + eps)
    if adagrad_w:
        upd = upd + wd * p
    po_ref[:] = (p - lr * upd).astype(po_ref.dtype)
    ho_ref[:] = h.astype(ho_ref.dtype)


def adagrad_update_leaf(p, g, h, *, lr, eps, weight_decay,
                        adagrad_w_mode: bool = False):
    """One fused Adagrad step for one leaf.  Scalars may be traced."""
    if not _use_pallas(p, g, h):
        return adagrad_update_leaf_reference(
            p, g, h, lr=lr, eps=eps, weight_decay=weight_decay,
            adagrad_w_mode=adagrad_w_mode)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p2, n = _to_lanes(p)
    g2, _ = _to_lanes(g)
    h2, _ = _to_lanes(h)
    rows = p2.shape[0]
    block, pad = _grid_rows(rows)
    p2, g2, h2 = (_pad_rows(t, pad) for t in (p2, g2, h2))
    grid = p2.shape[0] // block
    scal = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                      (lr, eps, weight_decay)])
    bspec = lambda: pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)
    po, ho = pl.pallas_call(
        functools.partial(_adagrad_kernel, adagrad_w=adagrad_w_mode),
        grid=(grid,),
        in_specs=[bspec(), bspec(), bspec(),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bspec(), bspec()],
        out_shape=[sds(p2.shape, p.dtype, p2, g2, h2),
                   sds(p2.shape, h.dtype, p2, g2, h2)],
        input_output_aliases={0: 0, 2: 1},
        name="adagrad",
        interpret=_interpret(),
    )(p2, g2, h2, scal)
    return _unpad(po, n, p), _unpad(ho, n, h)


def adagrad_update_leaf_reference(p, g, h, *, lr, eps, weight_decay,
                                  adagrad_w_mode: bool = False):
    pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
    hf = h.astype(jnp.float32)
    if not adagrad_w_mode:
        gf = gf + weight_decay * pf
    hf = hf + gf * gf
    upd = gf / (jnp.sqrt(hf) + eps)
    if adagrad_w_mode:
        upd = upd + weight_decay * pf
    return (pf - lr * upd).astype(p.dtype), hf.astype(h.dtype)
