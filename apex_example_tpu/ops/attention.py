"""Fused multi-head attention (flash attention): Pallas TPU kernels + XLA
reference.

Reference: apex ships fused attention as a contrib CUDA extension
(apex/contrib/csrc/fmha — SURVEY.md §2.1 contrib row) used by its BERT
recipes; the in-tree models otherwise materialize the full (Sq, Sk) score
matrix.  This module is the TPU-native equivalent and the long-context
workhorse the task brief asks for: blockwise attention whose score matrix
never leaves VMEM, so HBM traffic is O(S·D) instead of O(S²).

TPU-native design
-----------------
One forward Pallas kernel gridded ``(batch*heads, q_blocks, kv_blocks)``
with the kv dimension innermost (TPU grids run sequentially, so the running
online-softmax state lives in VMEM scratch across kv steps):

    m    running row max            (block_q, 1)  fp32
    l    running row sum of exp     (block_q, 1)  fp32
    acc  running unnormalized P·V   (block_q, D)  fp32

Each step computes ``S = QK^T·scale (+bias) (+causal mask)`` on the MXU with
fp32 accumulation, rescales (m, l, acc) by ``exp(m_old - m_new)``, and at the
last kv step writes ``O = acc / l`` plus the row logsumexp (saved for the
backward).  The backward follows the standard two-kernel flash decomposition:
a dK/dV kernel gridded over kv blocks (q innermost, accumulating in scratch)
and a dQ kernel gridded over q blocks (kv innermost), both recomputing
``P = exp(S - lse)`` from the saved logsumexp instead of storing it —
rematerialization trades MXU FLOPs for the O(S²) HBM tensor, the same trade
the LayerNorm kernel makes for x̂.

Numerics: logits and softmax are always fp32 (the amp "blacklist" contract —
SURVEY.md §3.1; model code keeps a naive path for O3's half-softmax).  The
probability matrix is cast back to the input dtype for the P·V / P^T·dO
matmuls so the MXU runs bf16 with fp32 accumulation, matching the XLA
reference path below, which is also the CPU fallback and the test golden.

Supported bias: an additive per-key bias of shape (B, Sk) — the key-padding
mask form BERT uses (already clamped to a finite "minus infinity" by the
model).  The bias is a constant mask, not a learned tensor: its VJP is zero.
Rows whose every key is masked produce an arbitrary convex combination of
values (the reference's softmax over all -1e9 logits does the same).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.ops import _config as _cfg
from apex_example_tpu.ops import paged_cache
from apex_example_tpu.ops._vma import sds

# Finite stand-in for -inf: exp(_MASK - anything_reasonable) == 0 in fp32,
# while (_MASK - _MASK) == 0 keeps fully-masked prefixes NaN-free (they are
# then exactly cancelled by the exp(m_old - m_new) rescale once a live block
# arrives).
_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)


def _dot_f32(a, b, *, trans_a=False, trans_b=False):
    """MXU matmul with fp32 accumulation regardless of operand dtype."""
    ca = ((0,) if trans_a else (1,), (1,) if trans_b else (0,))
    return lax.dot_general(a, b, (ca, ((), ())),
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# XLA reference path (CPU fallback + kernel-test golden).
# --------------------------------------------------------------------------

def _scores_reference(q, k, bias, causal, scale):
    """fp32 (B, H, Sq, Sk) scores: scaled QK^T, bias, causal mask — the one
    place the reference-path score semantics live (the Pallas counterpart is
    _scores)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        # Bottom-right aligned (the prefix-cache convention): when Sq < Sk
        # the queries are the LAST Sq positions, so query i sees keys
        # 0..(Sk-Sq)+i.  For Sq == Sk this is the ordinary triangular mask.
        sq, sk = q.shape[1], k.shape[1]
        mask = (lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
                >= lax.broadcasted_iota(jnp.int32, (sq, sk), 1))
        s = jnp.where(mask, s, _MASK)
    return s


def attention_reference(q, k, v, bias=None, causal=False,
                        scale: Optional[float] = None):
    """Naive attention.  q: (B, Sq, H, D); k/v: (B, Sk, H, D);
    bias: (B, Sk) additive, already finite; returns (B, Sq, H, D)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = _scores_reference(q, k, bias, causal, scale)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _reference_pair(q, k, v, bias, causal, scale):
    """attention_reference's output plus its (B, H, Sq) row logsumexp, both
    derived from ONE score tensor (keeps out and lse mutually consistent on
    the fallback path — the ring combine weights depend on that)."""
    s = _scores_reference(q, k, bias, causal, scale)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None]).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out, lse


# --------------------------------------------------------------------------
# Pallas kernels.  All operate on (BH, S, D) with B*H folded into the grid.
# --------------------------------------------------------------------------

def _when_live(i, j, *, causal, bq, bk, off):
    """Decorator: run the kernel body only when causal masking leaves the
    (q block i, kv block j) pair any live entries — i.e. the kv block starts
    at or before the q block's last visible key.  Skipping dead pairs saves
    ~half the causal grid's MXU work (init/write steps stay unguarded).
    Non-causal attention has no dead pairs; the body runs unconditionally."""
    if not causal:
        return lambda body: body()
    return pl.when(j * bk <= i * bq + off + bq - 1)


def _scores(q, k, bias_ref, i, j, *, scale, causal, bq, bk, off):
    """fp32 (bq, bk) logits for q block i vs kv block j: scale, bias, mask.

    ``off`` = Sk - Sq implements the bottom-right-aligned causal convention
    (see attention_reference)."""
    s = _dot_f32(q, k, trans_b=True) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0][None, :].astype(jnp.float32)
    if causal:
        row = i * bq + off + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(row >= col, s, _MASK)
    return s


def _fwd_kernel(*refs, scale, causal, bq, bk, nk, has_bias, off):
    if has_bias:
        q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, acc, m, l = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l = refs
        b_ref = None
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m[:] = jnp.full_like(m, _MASK)
        l[:] = jnp.zeros_like(l)
        acc[:] = jnp.zeros_like(acc)

    @_when_live(i, j, causal=causal, bq=bq, bk=bk, off=off)
    def _():
        s = _scores(q_ref[0], k_ref[0], b_ref, i, j,
                    scale=scale, causal=causal, bq=bq, bk=bk,
                    off=off)
        m_new = jnp.maximum(m[:], jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m[:] - m_new)
        p = jnp.exp(s - m_new)
        l[:] = l[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + _dot_f32(p.astype(v_ref.dtype), v_ref[0])
        m[:] = m_new

    @pl.when(j == nk - 1)
    def _():
        lsafe = jnp.where(l[:] == 0.0, 1.0, l[:])
        o_ref[0] = (acc[:] / lsafe).astype(o_ref.dtype)
        # lse rides as (BH, 1, Sq): a (1, 1, bq) block satisfies Mosaic's
        # second-minor-divisible-by-8-or-full rule, which a (1, bq) block of
        # a (BH, Sq) array does not.
        lse_ref[0, 0] = (m[:] + jnp.log(lsafe))[:, 0]


def _dkdv_kernel(*refs, scale, causal, bq, bk, nq, has_bias, off):
    if has_bias:
        (q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        b_ref = None
    j, i = pl.program_id(1), pl.program_id(2)   # grid: (bh, kv, q)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @_when_live(i, j, causal=causal, bq=bq, bk=bk, off=off)
    def _():
        s = _scores(q_ref[0], k_ref[0], b_ref, i, j,
                    scale=scale, causal=causal, bq=bq, bk=bk,
                    off=off)
        p = jnp.exp(s - lse_ref[0, 0][:, None])               # (bq, bk) fp32
        dof = do_ref[0]
        dv_acc[:] += _dot_f32(p.astype(dof.dtype), dof, trans_a=True)
        dp = _dot_f32(dof, v_ref[0], trans_b=True)            # (bq, bk)
        ds = p * (dp - dl_ref[0, 0][:, None]) * scale
        dk_acc[:] += _dot_f32(ds.astype(q_ref.dtype), q_ref[0], trans_a=True)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(*refs, scale, causal, bq, bk, nk, has_bias, off):
    if has_bias:
        (q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
         dq_ref, dq_acc) = refs
        b_ref = None
    i, j = pl.program_id(1), pl.program_id(2)   # grid: (bh, q, kv)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @_when_live(i, j, causal=causal, bq=bq, bk=bk, off=off)
    def _():
        s = _scores(q_ref[0], k_ref[0], b_ref, i, j,
                    scale=scale, causal=causal, bq=bq, bk=bk,
                    off=off)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = _dot_f32(do_ref[0], v_ref[0], trans_b=True)
        ds = p * (dp - dl_ref[0, 0][:, None]) * scale
        dq_acc[:] += _dot_f32(ds.astype(k_ref.dtype), k_ref[0])

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


# Deferred pallas import (the module must import on hosts without pallas
# deps); bound at first kernel use, mirroring layer_norm.py's local imports.
pl = None
pltpu = None


def _bind_pallas():
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu
        pl, pltpu = _pl, _pltpu


def _pick_blocks(sq: int, sk: int):
    bq = 256 if sq % 256 == 0 else 128
    bk = 256 if sk % 256 == 0 else 128
    return bq, bk


def _kernel_ok(q, k, *more) -> bool:
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1]
    if sq % 128 or sk % 128 or d % 8:
        return False
    if not _cfg.use_pallas_for(q, k, *more):
        return False
    return True


def _pad_head(x):
    """Pad the head dim up to a lane multiple when it isn't one.

    Kernel blocks always span the full head dim, and Mosaic accepts a last
    block dim equal to the overall array dim — so half-lane multiples
    (64, 128, 192, ...) run unpadded; ragged head dims (80, 96, ...) pay a
    pad to the next lane multiple.  Zeros change neither QK^T nor the value
    columns sliced back off."""
    d = x.shape[-1]
    if d % 64:
        pad = (-d) % 128
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
    return x


def _fold(x):
    """(B, S, H, D) -> (B*H, S, D)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _bias_spec(bk, h, kv_axis=2):
    # bias rides as (B, 1, Sk) (same Mosaic tiling rule as lse); grid dim 0
    # runs over B*H, so the index map folds the head back out with a static
    # integer division.  ``kv_axis`` names which grid position (1 or 2)
    # walks kv blocks — it differs per kernel.
    return pl.BlockSpec(
        (1, 1, bk), lambda *g, h=h, a=kv_axis: (g[0] // h, 0, g[a]))


def _attn_fwd_pallas(q, k, v, bias, causal, scale, h):
    _bind_pallas()
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _pick_blocks(sq, sk)
    nq, nk = sq // bq, sk // bk

    mat = lambda bs, im: pl.BlockSpec((1, bs, d), im)
    in_specs = [mat(bq, lambda b, i, j: (b, i, 0)),
                mat(bk, lambda b, i, j: (b, j, 0)),
                mat(bk, lambda b, i, j: (b, j, 0))]
    operands = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bk, h))
        operands.append(bias[:, None, :])

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, has_bias=bias is not None,
                          off=sk - sq),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[mat(bq, lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))],
        out_shape=[sds((bh, sq, d), q.dtype, q, k, v),
                   sds((bh, 1, sq), jnp.float32, q, k, v)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        name="flash_attention_fwd",
        interpret=_cfg.INTERPRET,
    )(*operands)
    return o, lse


def _attn_bwd_pallas(q, k, v, bias, causal, scale, h, o, lse, do,
                     dlse=None):
    _bind_pallas()
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _pick_blocks(sq, sk)
    nq, nk = sq // bq, sk // bk

    # delta_i = sum_d dO_i O_i — the d(logsumexp) correction; a cheap fused
    # elementwise+reduce, left to XLA rather than a third kernel.  Carried
    # (BH, 1, Sq) like lse (see the fwd kernel's tiling note).  When the lse
    # output itself carries a cotangent (flash_attention_with_lse — the ring
    # combine differentiates through it), it folds in here: dS = P∘(dP − Δ)
    # gains the term dlse_i·P_ij because ∂lse_i/∂S_ij = P_ij, i.e.
    # Δ_i := Δ_i − dlse_i.
    dl = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, None, :]
    if dlse is not None:
        dl = dl - dlse

    mat = lambda bs, im: pl.BlockSpec((1, bs, d), im)
    row = lambda bs, im: pl.BlockSpec((1, 1, bs), im)

    common = dict(scale=scale, causal=causal, bq=bq, bk=bk,
                  has_bias=bias is not None, off=sk - sq)
    qkv_specs = lambda qi, ki, kva: (
        [mat(bq, qi), mat(bk, ki), mat(bk, ki)]
        + ([_bias_spec(bk, h, kv_axis=kva)] if bias is not None else []))
    operands = [q, k, v] + ([bias[:, None, :]] if bias is not None else [])

    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, nq=nq, **common),
        grid=(bh, nk, nq),   # kv outer, q inner (accumulate over q)
        in_specs=qkv_specs(lambda b, j, i: (b, i, 0),
                           lambda b, j, i: (b, j, 0), 1)
        + [mat(bq, lambda b, j, i: (b, i, 0)),     # do
           row(bq, lambda b, j, i: (b, 0, i)),     # lse
           row(bq, lambda b, j, i: (b, 0, i))],    # delta
        out_specs=[mat(bk, lambda b, j, i: (b, j, 0)),
                   mat(bk, lambda b, j, i: (b, j, 0))],
        out_shape=[sds((bh, sk, d), k.dtype, q, k, v, do),
                   sds((bh, sk, d), v.dtype, q, k, v, do)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        name="flash_attention_bwd_dkv",
        interpret=_cfg.INTERPRET,
    )(*operands, do, lse, dl)

    (dq,) = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(bh, nq, nk),   # q outer, kv inner (accumulate over kv)
        in_specs=qkv_specs(lambda b, i, j: (b, i, 0),
                           lambda b, i, j: (b, j, 0), 2)
        + [mat(bq, lambda b, i, j: (b, i, 0)),
           row(bq, lambda b, i, j: (b, 0, i)),
           row(bq, lambda b, i, j: (b, 0, i))],
        out_specs=[mat(bq, lambda b, i, j: (b, i, 0))],
        out_shape=[sds((bh, sq, d), q.dtype, q, k, v, do)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=_cfg.INTERPRET,
    )(*operands, do, lse, dl)
    return dq, dk, dv


# --------------------------------------------------------------------------
# Public ops with custom VJP.  flash_attention and flash_attention_with_lse
# share one dispatch pipeline (_lse_fwd / _bwd_dispatch); the only
# difference is whether the row logsumexp is exposed to the caller (and may
# therefore carry a cotangent).
# --------------------------------------------------------------------------

def _lse_fwd(q, k, v, bias, causal, scale):
    """Shared forward: (o, lse_public (B,H,Sq), lse_folded (BH,1,Sq)|None).

    lse_folded is None exactly when the XLA reference path ran (the backward
    then differentiates the reference instead of running the kernels)."""
    if causal and q.shape[1] > k.shape[1]:
        # Bottom-right alignment would leave the first Sq-Sk query rows with
        # no visible keys at all — there is no meaningful gradient for such
        # rows (and the kernel's recomputed-softmax backward would disagree
        # with autodiff on them), so the configuration is rejected outright.
        raise ValueError(
            f"causal attention needs Sq <= Sk (bottom-right alignment), got "
            f"Sq={q.shape[1]} > Sk={k.shape[1]}")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    args = (q, k, v) + (() if bias is None else (bias,))
    b, sq, h, d = q.shape
    if not _kernel_ok(*args):
        o, lse = _reference_pair(q, k, v, bias, causal, scale)
        return o, lse, None
    qf, kf, vf = (_pad_head(_fold(x)) for x in (q, k, v))
    o, lse = _attn_fwd_pallas(qf, kf, vf, bias, causal, scale, h)
    return (_unfold(o[..., :d], b, h), lse[:, 0, :].reshape(b, h, sq), lse)


def _bwd_dispatch(causal, scale, res, do, dlse):
    """Shared backward.  dlse is the lse cotangent (None for the plain op)."""
    q, k, v, bias, o, lse_folded = res
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if lse_folded is None:
        if dlse is None:
            f = lambda q, k, v: attention_reference(q, k, v, bias, causal,
                                                    scale)
            _, vjp = jax.vjp(f, q, k, v)
            dq, dk, dv = vjp(do)
        else:
            f = lambda q, k, v: _reference_pair(q, k, v, bias, causal, scale)
            _, vjp = jax.vjp(f, q, k, v)
            dq, dk, dv = vjp((do, dlse))
    else:
        b, sq, h, d = q.shape
        qf, kf, vf, of, dof = (_pad_head(_fold(x)) for x in (q, k, v, o, do))
        dlse_f = None if dlse is None else \
            dlse.astype(jnp.float32).reshape(b * h, 1, sq)
        dq, dk, dv = _attn_bwd_pallas(qf, kf, vf, bias, causal, scale, h,
                                      of, lse_folded, dof, dlse=dlse_f)
        dq, dk, dv = (_unfold(g[..., :d], b, h) for g in (dq, dk, dv))
    dbias = None if bias is None else jnp.zeros_like(bias)  # constant mask
    return dq, dk, dv, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_attention_op(q, k, v, bias, causal, scale):
    o, _, _ = _lse_fwd(q, k, v, bias, causal, scale)
    return o


def _flash_fwd_vjp(q, k, v, bias, causal, scale):
    o, _, lse_folded = _lse_fwd(q, k, v, bias, causal, scale)
    return o, (q, k, v, bias, o, lse_folded)


def _flash_bwd_vjp(causal, scale, res, do):
    return _bwd_dispatch(causal, scale, res, do, None)


_flash_attention_op.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


def flash_attention(q, k, v, bias=None, causal: bool = False,
                    scale: Optional[float] = None):
    """Memory-efficient multi-head attention.

    q: (B, Sq, H, D); k, v: (B, Sk, H, D); bias: optional (B, Sk) additive
    key bias (finite values; use ~-1e9 for masked keys); returns
    (B, Sq, H, D) in q's dtype.  Softmax is fp32.  Falls back to the XLA
    reference off-TPU or when shapes don't tile (S % 128, tiny sequences).

    ``bias`` is treated as a constant MASK: ``lax.stop_gradient`` is
    applied to it at this boundary, so differentiating w.r.t. a bias input
    yields structurally zero gradients on every path (kernel and fallback
    alike).  Do not route a *learned* bias (ALiBi-style scores etc.)
    through it — the parameter would not train; use explicit scores for
    that.
    """
    if bias is not None:
        bias = lax.stop_gradient(bias)
    return _flash_attention_op(q, k, v, bias, causal, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_attention_with_lse_op(q, k, v, bias, causal, scale):
    o, lse, _ = _lse_fwd(q, k, v, bias, causal, scale)
    return o, lse


def _flash_lse_fwd_vjp(q, k, v, bias, causal, scale):
    o, lse_pub, lse_folded = _lse_fwd(q, k, v, bias, causal, scale)
    return (o, lse_pub), (q, k, v, bias, o, lse_folded)


def _flash_lse_bwd_vjp(causal, scale, res, cts):
    do, dlse = cts
    return _bwd_dispatch(causal, scale, res, do, dlse)


_flash_attention_with_lse_op.defvjp(_flash_lse_fwd_vjp, _flash_lse_bwd_vjp)


def flash_attention_with_lse(q, k, v, bias=None, causal: bool = False,
                             scale: Optional[float] = None):
    """:func:`flash_attention` that also returns the row logsumexp.

    Returns ``(out, lse)`` with ``out``: (B, Sq, H, D) in q's dtype and
    ``lse``: (B, H, Sq) fp32.  The composable form: ring/blockwise context
    parallelism (parallel/context_parallel.py) merges per-chunk results with
    the logsumexp-weighted combine.  Unlike the bias argument (constant
    mask, stop_gradient'ed at this boundary exactly like
    :func:`flash_attention`), ``lse`` is fully differentiable — the combine
    weights backpropagate through it (the kernel backward absorbs the
    cotangent into its Δ correction: ∂lse_i/∂S_ij = P_ij).
    """
    if bias is not None:
        bias = lax.stop_gradient(bias)
    return _flash_attention_with_lse_op(q, k, v, bias, causal, scale)


# --------------------------------------------------------------------------
# Paged latent attention (the serve tick of models/xing4.py): the absorbed
# MLA form over a head-less [NB, BS, W] arena whose rows are key and value
# at once.  ``paged_latent_attention`` is the op; the XLA form below it is
# the CPU fallback, FORCE_XLA's path and the kernel tests' golden.
# --------------------------------------------------------------------------

def paged_latent_attention_reference(qf, arena, block_table, fill, n_new,
                                     scale, kr):
    """The XLA form: every slot's whole row of the block table gathered
    into a ``[S, L, W]`` view, scores against all ``L`` positions, masked,
    softmaxed and multiplied back.  Same contract as
    :func:`paged_latent_attention`; ``walked`` is ``L`` for every slot."""
    S, C = qf.shape[:2]
    view = paged_cache.gather(arena, block_table)
    with jax.named_scope("latent_attention"):
        L = view.shape[1]
        lane = jnp.arange(C)[None, :]
        scores = jnp.einsum("schw,slw->shcl", qf, view,
                            preferred_element_type=jnp.float32) * scale
        live = jnp.arange(L)[None, None, :] <= (fill[:, None] + lane)[..., None]
        probs = jax.nn.softmax(jnp.where(live[:, None], scores, -1e30), -1)
        ol = jnp.einsum("shcl,slr->schr", probs.astype(qf.dtype),
                        view[..., :kr], preferred_element_type=jnp.float32)
        ol = jnp.where((lane < n_new[:, None])[..., None, None], ol, 0.0)
        return ol.astype(qf.dtype), jnp.full((S,), L, jnp.int32)


def _paged_latent_kernel(table_ref, fill_ref, n_new_ref, q_ref, arena_ref,
                         o_ref, walked_ref, kbuf, sem, first_buf, acc, m, l,
                         *, scale, kr, heads, bs, pages, max_blocks,
                         row_tile):
    """One grid step = one slot.  Its ``rows = C * heads`` query rows lie
    lane-major (row // heads is the lane), so the live ones come first and
    row tiles past ``n_new * heads`` are never computed.  The slot's live
    blocks are walked ``pages`` a compute tile: each page one DMA from
    where it lies in the arena, the next tile in flight while this one is
    scored (two buffers; during a slot's last tile the next slot's first is
    in flight), online softmax state in float32 scratch."""
    s, slots = pl.program_id(0), pl.num_programs(0)
    rows, nb = q_ref.shape[1], arena_ref.shape[0]
    tile = pages * bs

    def blocks_tiles(slot):
        """A slot's live blocks (never more than its row of the table
        holds) and the compute tiles they make."""
        n_new = n_new_ref[slot]
        blocks = jnp.where(
            n_new > 0,
            jnp.minimum((fill_ref[slot] + n_new + bs - 1) // bs, max_blocks),
            0)
        return blocks, (blocks + pages - 1) // pages

    fill, n_new = fill_ref[s], n_new_ref[s]
    total = fill + n_new
    live_rows = n_new * heads
    n_blocks, n_tiles = blocks_tiles(s)
    walked_ref[s] = n_blocks * bs

    def page_dmas(slot, t, buf, act):
        """``act`` on the DMA of every live page of ``slot``'s tile ``t``."""
        first = t * pages

        def one(p, carry):
            page = table_ref[slot * max_blocks + first + p]
            act(pltpu.make_async_copy(
                arena_ref.at[jnp.clip(page, 0, nb - 1)],
                kbuf.at[buf, pl.ds(pl.multiple_of(p * bs, bs), bs)],
                sem.at[buf]))
            return carry

        lax.fori_loop(0, jnp.clip(blocks_tiles(slot)[0] - first, 0, pages),
                      one, 0)

    start = lambda dma: dma.start()
    wait = lambda dma: dma.wait()

    def row_tiles(lo, hi, body):
        """``body(rs, r)`` for the row tiles ``lo <= i < hi``: ``rs`` the
        tile's rows as a slice, ``r`` its first row."""
        def one(i, carry):
            r = pl.multiple_of(i * row_tile, row_tile)
            body(pl.ds(r, row_tile), r)
            return carry
        lax.fori_loop(lo, hi, one, 0)

    live_tiles = (live_rows + row_tile - 1) // row_tile

    def init(rs, r):
        m[rs] = jnp.full((row_tile, 1), _MASK, jnp.float32)
        l[rs] = jnp.zeros((row_tile, 1), jnp.float32)
        acc[rs] = jnp.zeros((row_tile, kr), jnp.float32)

    row_tiles(0, live_tiles, init)

    # tile t of this slot lies in buffer (first + t) % 2.  The slot before,
    # if it walked anything, left ``first`` behind and our tile 0 in flight
    before, after = jnp.maximum(s - 1, 0), jnp.minimum(s + 1, slots - 1)
    in_flight = jnp.logical_and(s > 0, blocks_tiles(before)[1] > 0)
    hand_on = jnp.logical_and(s + 1 < slots, blocks_tiles(after)[1] > 0)
    first = jnp.where(s > 0, first_buf[0], 0)

    @pl.when(jnp.logical_and(n_tiles > 0, jnp.logical_not(in_flight)))
    def _():
        page_dmas(s, 0, first, start)

    def walk(t, carry):
        buf = lax.rem(first + t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            page_dmas(s, t + 1, 1 - buf, start)

        @pl.when(jnp.logical_and(t + 1 == n_tiles, hand_on))
        def _():
            page_dmas(after, 0, 1 - buf, start)

        page_dmas(s, t, buf, wait)
        t0 = t * tile

        @pl.when(t0 + tile > total)
        def _():
            # the slot's last tile: rows past its last token (the tail of a
            # page, pages not fetched) hold whatever was there; as values
            # they would meet a zero probability, and 0 * NaN is NaN
            at = lax.broadcasted_iota(jnp.int32, kbuf.shape[1:], 0)
            kbuf[buf] = jnp.where(at < total - t0, kbuf[buf], 0)

        def score(rs, r):
            k = kbuf[buf]
            sc = _dot_f32(q_ref[0, rs, :], k, trans_b=True) * scale
            row = r + lax.broadcasted_iota(jnp.int32, sc.shape, 0)
            col = t0 + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            # lane j = row // heads sees positions <= fill + j
            sc = jnp.where((col - fill) * heads <= row, sc, _MASK)
            m_new = jnp.maximum(m[rs], jnp.max(sc, -1, keepdims=True))
            alpha = jnp.exp(m[rs] - m_new)
            p = jnp.exp(sc - m_new)
            l[rs] = l[rs] * alpha + jnp.sum(p, -1, keepdims=True)
            acc[rs] = acc[rs] * alpha + _dot_f32(p.astype(k.dtype),
                                                 k[:, :kr])
            m[rs] = m_new

        row_tiles(0, live_tiles, score)
        return carry

    lax.fori_loop(0, n_tiles, walk, 0)
    first_buf[0] = lax.rem(first + n_tiles, 2)

    def write(rs, r):
        row = r + lax.broadcasted_iota(jnp.int32, (row_tile, kr), 0)
        o_ref[0, rs, :] = jnp.where(row < live_rows, acc[rs] / l[rs],
                                    0.0).astype(o_ref.dtype)

    def blank(rs, r):
        o_ref[0, rs, :] = jnp.zeros((row_tile, kr), o_ref.dtype)

    row_tiles(0, live_tiles, write)
    row_tiles(live_tiles, rows // row_tile, blank)


# The paged latent kernel's tiles: cache positions a compute tile (several
# pages) and query rows a row tile.  On the v5e at 32 heads x 16 lanes and
# a 640-wide arena in pages of 16, under a load like the serving cell's
# (45 of 64 slots live, 62k positions): 256 / 512 / 1024 positions read
# 0.55 / 0.45 / 0.43 ms a call, 128 rows beat 256 (0.45 against 0.53) and
# 512 (0.72): a decode slot has one live lane of 16 (PERF.md section 6).
_PAGED_TILE = 512
_PAGED_ROW_TILE = 128
# Mosaic's default scoped-VMEM limit on the v5e (of the chip's 128 MiB).
_MOSAIC_SCOPED_VMEM_BYTES = 16 << 20


def _paged_vmem_limit(rows: int, W: int, kr: int, tile: int, itemsize: int):
    """The scoped-VMEM limit the kernel asks for: twice what one grid step
    holds — a slot's whole ``[C x heads, W]`` query block and its output
    block (both double-buffered), the float32 softmax state of every row
    (``m`` and ``l`` a whole lane tile wide each) and two page buffers —
    where that passes Mosaic's default, else None (the default).  At 16
    lanes x 32 heads a step holds 4.3 MB and the call is what it always
    was; at 16 x 128 heads (2048 rows: models/pangu_moe.py) it holds 16.25
    MiB, which is also what the v5e's compiler counts, a quarter of a MiB
    over the default, and the kernel is refused without a limit."""
    held = (2 * rows * (W + kr) + 2 * tile * W) * itemsize \
        + rows * (kr + 2 * 128) * 4
    return 2 * held if 2 * held > _MOSAIC_SCOPED_VMEM_BYTES else None


# jitted so that a model's layers share one trace and one lowering of the
# kernel (tracing it costs the set-up 0.6 s a layer otherwise); what is
# read when it is traced is therefore an argument
@functools.partial(jax.jit, static_argnames=("scale", "kr", "interpret",
                                             "pages", "row_tile"))
def _paged_latent_pallas(qf, arena, block_table, fill, n_new, scale, kr,
                         interpret, pages=None, row_tile=None):
    _bind_pallas()
    S, C, H, W = qf.shape
    NB, BS, _ = arena.shape
    max_blocks = block_table.shape[1]
    rows = C * H
    if pages is None:
        pages = max(1, min(max_blocks, _PAGED_TILE // BS))
    if row_tile is None:
        row_tile = _PAGED_ROW_TILE if rows % _PAGED_ROW_TILE == 0 else rows
    i32 = lambda x: x.astype(jnp.int32)
    ol, walked = pl.pallas_call(
        functools.partial(_paged_latent_kernel, scale=scale, kr=kr, heads=H,
                          bs=BS, pages=pages, max_blocks=max_blocks,
                          row_tile=row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, rows, W), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, rows, kr), lambda s, *_: (s, 0, 0)),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((2, pages * BS, W), arena.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((rows, kr), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32)]),
        out_shape=[sds((S, rows, kr), qf.dtype, qf, arena),
                   sds((S,), jnp.int32, qf, arena)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_paged_vmem_limit(
                rows, W, kr, pages * BS, arena.dtype.itemsize)),
        name="paged_latent_attention",
        interpret=interpret,
    )(i32(block_table).reshape(-1), i32(fill), i32(n_new),
      qf.reshape(S, rows, W), arena)
    return ol.reshape(S, C, H, kr), walked


def _paged_latent_ok(qf, arena, kr) -> bool:
    if not _cfg.use_pallas():
        return False
    if _cfg.INTERPRET:
        return True
    # Mosaic: whole 128-lane tiles across, whole sublane tiles a page
    sublanes = 8 * 4 // arena.dtype.itemsize
    return (arena.shape[2] % 128 == 0 and kr % 128 == 0
            and arena.shape[1] % sublanes == 0
            and (qf.shape[1] * qf.shape[2]) % sublanes == 0)


def paged_latent_attention(qf, arena, block_table, fill, n_new, *, scale, kr):
    """Absorbed latent attention of one serve tick against the paged arena.

    qf: (S, C, H, W) queries already in the latent space (``q_nope W_UK^T``
    beside the rotated ``q_rope``, pad lanes zero); arena: (NB, BS, W), one
    row a cached token, key in all ``W`` columns and value in the first
    ``kr``; block_table: (S, max_blocks) block ids, entries past a slot's
    live blocks anything (−1 included); fill, n_new: (S,) tokens cached
    before this tick and lanes that are real.  Lane ``j`` of slot ``s``
    attends positions ``<= fill[s] + j``.  Returns ``(ol, walked)``: ol
    (S, C, H, kr) in qf's dtype, zeros for lanes ``>= n_new`` (so every row
    is finite), and walked (S,) int32, the cache positions the
    implementation read for each slot.

    Scores, softmax and accumulation are float32; probabilities are cast
    to the arena's dtype for the second product.  On TPU (and under the
    interpreter) a Pallas kernel walks each slot's live blocks where they
    lie, ``ceil((fill + n_new) / BS)`` of them and none for ``n_new == 0``;
    elsewhere the XLA form gathers and scores all ``max_blocks * BS``
    positions of every slot."""
    if _paged_latent_ok(qf, arena, kr):
        with jax.named_scope("latent_attention"):
            return _paged_latent_pallas(qf, arena, block_table, fill, n_new,
                                        scale, kr, _cfg.INTERPRET)
    return paged_latent_attention_reference(qf, arena, block_table, fill,
                                            n_new, scale, kr)


# --------------------------------------------------------------------------
# Paged per-head attention (the serve tick of models/trinity.py, of
# models/granite_hybrid.py and of models/bert.py's float arenas): K and V in
# two [NB, BS, Hk * hd] arenas, full or window, the window's through a ring.
# ``paged_gqa_attention`` is the op; the XLA form below it is the CPU
# fallback, FORCE_XLA's path and the kernel tests' golden.
# --------------------------------------------------------------------------

def _gqa_rows(q, kv_heads: int):
    """``[S, C, Hq, hd]`` queries as ``[S, Hk, C * g, hd]``: a key/value
    head's ``g`` query heads of every lane, lane-major (row // g is the
    lane), so that a slot's live rows come first."""
    S, C, Hq, hd = q.shape
    g = Hq // kv_heads
    return q.reshape(S, C, kv_heads, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        S, kv_heads, C * g, hd)


def _gqa_lanes(o, lanes: int):
    """:func:`_gqa_rows` undone: ``[S, Hk, C * g, hd]`` as ``[S, C, Hq,
    hd]``."""
    S, Hk, rows, hd = o.shape
    g = rows // lanes
    return o.reshape(S, Hk, lanes, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        S, lanes, Hk * g, hd)


def _heads_a_tile(hd: int, kv_heads: int) -> int:
    """Key/value heads the kernel takes as one: a head of 64 is half a
    128-lane tile of the arena's merged dimension, so two that lie side by
    side are taken as one of 128 (:func:`_paired_rows`)."""
    return 2 if hd == 64 and kv_heads % 2 == 0 else 1


def _paired_rows(q, kv_heads: int):
    """Queries for key/value heads taken two a lane tile: ``[S, C, Hq,
    hd]`` as ``[S, C, Hq, 2 * hd]``, each row in its own head's half of the
    pair's lanes and zeros in the other, so that its scores against the
    pair's keys are its own head's exactly (``0 * k`` in the other half).
    Also ``upper [Hq]``: whose half is the second.  A pair's ``2 g`` query
    heads stay adjacent, so :func:`_gqa_rows` over ``Hk / 2`` heads orders
    the rows lane-major as before (row // (2 g) is the lane)."""
    Hq, hd = q.shape[2:]
    upper = (jnp.arange(Hq) // (Hq // kv_heads)) % 2 == 1
    own = upper[:, None] == (jnp.arange(2 * hd) >= hd)[None, :]
    return jnp.where(own, jnp.concatenate([q, q], -1), 0), upper


def paged_gqa_attention_reference(q, k_arena, v_arena, table, fill, n_new,
                                  scale, window=None, ring=None):
    """The XLA form: every slot's whole row of the table gathered into
    ``[S, L, Hk, hd]`` views, scores against all ``L`` rows, masked by each
    lane's own position (and window), softmaxed and multiplied back.  A
    ring's column ``c`` is taken for the newest logical block ``j <=`` the
    slot's last with ``j mod ring == c``: an older one the window hides, a
    column not yet written reads as before position 0 and is masked.  Same
    contract as :func:`paged_gqa_attention`; ``walked`` is ``L`` for every
    slot."""
    S, C, Hq, hd = q.shape
    BS, Hk = k_arena.shape[1], k_arena.shape[2] // hd
    keys, vals = paged_cache.gather((k_arena, v_arena), table, heads=Hk)
    with jax.named_scope("paged_gqa_attention"):
        L = keys.shape[1]
        lane = jnp.arange(C)[None, :]
        pos = fill[:, None] + lane                               # [S, C]
        col, at = jnp.arange(L)[None, :] // BS, jnp.arange(L)[None, :] % BS
        if ring:
            last = (jnp.maximum(fill + n_new, 1)[:, None] - 1) // BS
            col = last - (last - col) % table.shape[1]
        kpos = col * BS + at                                     # [S, L]
        seen = (kpos[:, None, :] <= pos[:, :, None]) & (kpos[:, None, :] >= 0)
        if window is not None:
            seen &= kpos[:, None, :] > pos[:, :, None] - window
        qg = q.reshape(S, C, Hk, Hq // Hk, hd)
        scores = jnp.einsum("sckgd,slkd->skgcl", qg, keys,
                            preferred_element_type=jnp.float32) * scale
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None], scores, -1e30), -1)
        o = jnp.einsum("skgcl,slkd->sckgd", probs.astype(q.dtype), vals,
                       preferred_element_type=jnp.float32)
        o = jnp.where((lane < n_new[:, None])[..., None, None, None], o, 0.0)
        return (o.reshape(S, C, Hq, hd).astype(q.dtype),
                jnp.full((S,), L, jnp.int32))


def _paged_gqa_kernel(table_ref, fill_ref, n_new_ref, q_ref, k_ref, v_ref,
                      o_ref, walked_ref, kbuf, vbuf, sem, first_buf, acc, m,
                      l, *, scale, group, hd, bs, pages, columns, window,
                      ring, row_tile, small):
    """One grid step = one slot, all its key/value heads.  A head's ``rows =
    C * group`` query rows lie lane-major, so the live ones come first and
    row tiles past ``n_new * group`` are never computed.  The walk goes over
    the slot's logical blocks from the first any live lane may see (block 0,
    or in a window layer the block of ``fill - window + 1``) to the last it
    wrote, ``pages`` a compute tile: each page one DMA of K and one of V
    from where they lie in their arenas (through the ring's column ``block
    mod ring`` where the table is one), the next tile in flight while this
    one is scored, online softmax state in float32 scratch a head."""
    s, slots = pl.program_id(0), pl.num_programs(0)
    heads, rows = q_ref.shape[1], q_ref.shape[2]
    nb = k_ref.shape[0]
    tile = pages * bs

    def span(slot):
        """A slot's first logical block, its blocks walked and the compute
        tiles they make (none for a slot that feeds nothing)."""
        fill, n_new = fill_ref[slot], n_new_ref[slot]
        first = 0 if window is None \
            else jnp.maximum(fill - window + 1, 0) // bs
        blocks = jnp.where(
            n_new > 0,
            jnp.minimum((fill + n_new + bs - 1) // bs - first, columns), 0)
        return first, blocks, (blocks + pages - 1) // pages

    fill, n_new = fill_ref[s], n_new_ref[s]
    total = fill + n_new
    live_rows = n_new * group
    first_block, n_blocks, n_tiles = span(s)
    walked_ref[s] = n_blocks * bs

    def start_pages(slot, t, buf):
        """Start both DMAs of every live page of ``slot``'s tile ``t``."""
        first, blocks, _ = span(slot)
        at = t * pages

        def one(p, carry):
            block = first + at + p
            column = lax.rem(block, columns) if ring else block
            page = jnp.clip(table_ref[slot * columns + column], 0, nb - 1)
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            pltpu.make_async_copy(k_ref.at[page], kbuf.at[buf, dst],
                                  sem.at[buf, 0]).start()
            pltpu.make_async_copy(v_ref.at[page], vbuf.at[buf, dst],
                                  sem.at[buf, 1]).start()
            return carry

        lax.fori_loop(0, jnp.clip(blocks - at, 0, pages), one, 0)

    def wait_pages(t, buf):
        """Wait for this slot's tile ``t`` to have landed in buffer
        ``buf``.  A wait needs the semaphore and the bytes, not the page:
        a full tile is one wait an arena over the whole buffer, a slot's
        last, partial tile one a page."""
        n = jnp.clip(n_blocks - t * pages, 0, pages)

        def landed(ref, sem_ref):
            pltpu.make_async_copy(ref, ref, sem_ref).wait()

        @pl.when(n == pages)
        def _():
            landed(kbuf.at[buf], sem.at[buf, 0])
            landed(vbuf.at[buf], sem.at[buf, 1])

        @pl.when(n < pages)
        def _():
            def one(p, carry):
                dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
                landed(kbuf.at[buf, dst], sem.at[buf, 0])
                landed(vbuf.at[buf, dst], sem.at[buf, 1])
                return carry
            lax.fori_loop(0, n, one, 0)

    def row_tiles(lo, hi, body):
        def one(i, carry):
            body(pl.ds(pl.multiple_of(i * row_tile, row_tile), row_tile),
                 i * row_tile)
            return carry
        lax.fori_loop(lo, hi, one, 0)

    live_tiles = (live_rows + row_tile - 1) // row_tile

    def init(rs, r):
        for h in range(heads):
            m[h, rs] = jnp.full((row_tile, 1), _MASK, jnp.float32)
            l[h, rs] = jnp.zeros((row_tile, 1), jnp.float32)
            acc[h, rs] = jnp.zeros((row_tile, hd), jnp.float32)

    row_tiles(0, live_tiles, init)

    # tile t of this slot lies in buffer (first + t) % 2.  The slot before,
    # if it walked anything, left ``first`` behind and our tile 0 in flight
    before, after = jnp.maximum(s - 1, 0), jnp.minimum(s + 1, slots - 1)
    in_flight = jnp.logical_and(s > 0, span(before)[2] > 0)
    hand_on = jnp.logical_and(s + 1 < slots, span(after)[2] > 0)
    first = jnp.where(s > 0, first_buf[0], 0)

    @pl.when(jnp.logical_and(n_tiles > 0, jnp.logical_not(in_flight)))
    def _():
        start_pages(s, 0, first)

    def walk(t, carry):
        buf = lax.rem(first + t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            start_pages(s, t + 1, 1 - buf)

        @pl.when(jnp.logical_and(t + 1 == n_tiles, hand_on))
        def _():
            start_pages(after, 0, 1 - buf)

        wait_pages(t, buf)
        t0 = (first_block + t * pages) * bs      # the tile's first position

        @pl.when(t0 + tile > total)
        def _():
            # the slot's last tile: rows past its last token (the tail of a
            # page, pages not fetched) hold whatever was there; as values
            # they would meet a zero probability, and 0 * NaN is NaN (as
            # keys their scores are replaced by the mask below)
            at = lax.broadcasted_iota(jnp.int32, vbuf.shape[1:], 0)
            vbuf[buf] = jnp.where(at < total - t0, vbuf[buf], 0)

        def score(rs, r):
            # the heads' first products, then their softmax steps, then
            # their second products: independent products side by side keep
            # the MXUs fed (0.77 -> 0.60 ms a full-layer call on the v5e)
            ks = [kbuf[buf, :, pl.ds(h * hd, hd)] for h in range(heads)]
            scs = [_dot_f32(q_ref[0, h, rs, :], ks[h], trans_b=True) * scale
                   for h in range(heads)]
            row = r + lax.broadcasted_iota(jnp.int32, scs[0].shape, 0)
            col = t0 + lax.broadcasted_iota(jnp.int32, scs[0].shape, 1)
            seen = (col - fill) * group <= row
            if window is not None:
                seen = jnp.logical_and(
                    seen, (col - fill + window) * group > row)
            ps, alphas = [], []
            for h in range(heads):
                sc = jnp.where(seen, scs[h], _MASK)
                m_new = jnp.maximum(m[h, rs], jnp.max(sc, -1, keepdims=True))
                alpha = jnp.exp(m[h, rs] - m_new)
                p = jnp.exp(sc - m_new)
                l[h, rs] = l[h, rs] * alpha + jnp.sum(p, -1, keepdims=True)
                m[h, rs] = m_new
                ps.append(p.astype(ks[h].dtype))
                alphas.append(alpha)
            for h in range(heads):
                acc[h, rs] = acc[h, rs] * alphas[h] + _dot_f32(
                    ps[h], vbuf[buf, :, pl.ds(h * hd, hd)])

        # a decoding slot's ``group`` live rows a head in one small tile
        # (a product's cost on the MXU is its key tile's load plus the rows
        # streamed past it), a prefilling slot's in row tiles
        if small is None:
            row_tiles(0, live_tiles, score)
        else:
            @pl.when(live_rows <= small)
            def _():
                score(pl.ds(0, small), 0)

            @pl.when(live_rows > small)
            def _():
                row_tiles(0, live_tiles, score)
        return carry

    lax.fori_loop(0, n_tiles, walk, 0)
    first_buf[0] = lax.rem(first + n_tiles, 2)

    def write(rs, r):
        row = r + lax.broadcasted_iota(jnp.int32, (row_tile, hd), 0)
        for h in range(heads):
            o_ref[0, h, rs, :] = jnp.where(
                row < live_rows, acc[h, rs] / l[h, rs], 0.0).astype(
                    o_ref.dtype)

    def blank(rs, r):
        for h in range(heads):
            o_ref[0, h, rs, :] = jnp.zeros((row_tile, hd), o_ref.dtype)

    row_tiles(0, live_tiles, write)
    row_tiles(live_tiles, rows // row_tile, blank)


# The paged GQA kernel's tiles: cache positions a compute tile (pages of
# both arenas, 16 KB each at 16 x 512 bfloat16) and query rows a row tile (a
# prefilling slot's; a decoding slot's ``group`` live rows a head go through
# one sublane tile).  On the v5e at 32 over 4 heads of 128, 16 lanes, pages
# of 16, under a load like the serving cell's (48 of 64 slots live, 108k
# positions walked in a full layer's call, 71k in a window layer's), full /
# window, ms a call (PERF.md section 6, PR 40): 512 positions a tile, 32
# rows, head by head, a wait a page 0.94 / 0.73; 1,024 positions 0.76 /
# 0.61 (256: 1.40 / 0.97; 2,048 no better); the heads' products side by
# side 0.60 / 0.50; one wait an arena for a full tile and 64 rows 0.55 /
# 0.47; a decoding slot's rows in one tile of 16 and a prefilling slot's
# in one of 128 0.52 / 0.48.  The pages' DMAs alone take 0.43 / 0.43 (514
# GB/s: 16 KB a descriptor), the products alone 0.46-0.56.
_GQA_TILE = 1024
_GQA_ROW_TILE = 128


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "ring", "interpret", "pages", "row_tile"))
def _paged_gqa_pallas(q, k_arena, v_arena, table, fill, n_new, scale, window,
                      ring, interpret, pages=None, row_tile=None):
    _bind_pallas()
    S, C, Hq, hd = q.shape
    NB, BS, W = k_arena.shape
    Hk = W // hd
    paired = _heads_a_tile(hd, Hk) == 2
    if paired:
        # the second product fills both halves of a pair's lanes; each row
        # keeps its own below
        q, upper = _paired_rows(q, Hk)
        Hk, hd = Hk // 2, 2 * hd
    columns = table.shape[1]
    rows = C * (Hq // Hk)
    if pages is None:
        pages = max(1, min(columns, _GQA_TILE // BS))
    if row_tile is None:
        row_tile = _GQA_ROW_TILE if rows % _GQA_ROW_TILE == 0 else rows
    # the least rows a product takes: one sublane tile of the queries' dtype
    small = 8 * 4 // q.dtype.itemsize
    small = small if small < row_tile and row_tile % small == 0 else None
    i32 = lambda x: x.astype(jnp.int32)
    block = lambda: pl.BlockSpec((1, Hk, rows, hd), lambda s, *_: (s, 0, 0, 0))
    o, walked = pl.pallas_call(
        functools.partial(_paged_gqa_kernel, scale=scale, group=Hq // Hk,
                          hd=hd, bs=BS, pages=pages, columns=columns,
                          window=window, ring=ring is not None,
                          row_tile=row_tile, small=small),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[block(), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[block(), pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((2, pages * BS, W), k_arena.dtype),
                            pltpu.VMEM((2, pages * BS, W), v_arena.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((Hk, rows, hd), jnp.float32),
                            pltpu.VMEM((Hk, rows, 1), jnp.float32),
                            pltpu.VMEM((Hk, rows, 1), jnp.float32)]),
        out_shape=[sds((S, Hk, rows, hd), q.dtype, q, k_arena),
                   sds((S,), jnp.int32, q, k_arena)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_gqa_attention",
        interpret=interpret,
    )(i32(table).reshape(-1), i32(fill), i32(n_new), _gqa_rows(q, Hk),
      k_arena, v_arena)
    o = _gqa_lanes(o, C)
    if paired:
        o = jnp.where(upper[:, None], o[..., hd // 2:], o[..., :hd // 2])
    return o, walked


def _paged_gqa_ok(q, k_arena) -> bool:
    if not _cfg.use_pallas():
        return False
    if _cfg.INTERPRET:
        return True
    # Mosaic: a head, or a pair of heads, a whole 128-lane tile; whole
    # sublane tiles a page
    sublanes = 8 * 4 // k_arena.dtype.itemsize
    _, C, Hq, hd = q.shape
    Hk = k_arena.shape[2] // hd
    heads = _heads_a_tile(hd, Hk)
    rows = C * Hq // (Hk // heads)
    return ((heads * hd) % 128 == 0 and k_arena.shape[1] % sublanes == 0
            and rows % sublanes == 0)


def paged_gqa_attention(q, k_arena, v_arena, table, fill, n_new, *, scale,
                        window=None, ring=None):
    """Grouped-query attention of one serve tick against paged K and V.

    q: (S, C, Hq, hd) queries (normed and rotated by the caller);
    k_arena, v_arena: (NB, BS, Hk * hd), a row a cached token, heads the
    outer factor of the merged dimension; query head ``i`` reads key/value
    head ``i // (Hq // Hk)``.  table: (S, columns) block ids: a slot's row
    of the block table, or with ``ring`` (the ring's blocks a slot, ``==
    columns``) of a window leaf's ring table, where logical block ``j``
    stands in column ``j mod ring``; entries that hold nothing may be
    anything.  fill, n_new: (S,) tokens cached before this tick and lanes
    that are real (this tick's rows are written already).  Lane ``j`` of
    slot ``s``, at position ``p = fill[s] + j``, attends the positions ``p'
    <= p`` and, with ``window``, ``p' > p - window``.  Returns ``(o,
    walked)``: o (S, C, Hq, hd) in q's dtype, zeros for lanes ``>= n_new``,
    and walked (S,) int32, the cache positions read for each slot.

    Scores, softmax and accumulation are float32; probabilities are cast to
    the arena's dtype for the second product (bfloat16 arenas stay
    bfloat16, float32 arenas float32).  On TPU (and under the interpreter)
    a Pallas kernel walks, for each slot, the blocks from the first any
    live lane may see to the last it wrote, where they lie, and nothing for
    ``n_new == 0``; elsewhere the XLA form gathers and scores every row of
    the table.  Mosaic wants a head, or a pair of heads, a whole 128-lane
    tile of the arena: ``hd % 128 == 0`` (models/trinity.py), or ``hd ==
    64`` with an even ``Hk``, two adjacent heads taken as one of 128 by the
    launcher (models/granite_hybrid.py, models/bert.py's float arenas);
    one kernel body either way."""
    if ring and ring != table.shape[1]:
        raise ValueError(f"a ring of {ring} blocks wants a table {ring} "
                         f"columns wide, got {table.shape[1]}")
    if _paged_gqa_ok(q, k_arena):
        with jax.named_scope("paged_gqa_attention"):
            return _paged_gqa_pallas(q, k_arena, v_arena, table, fill,
                                     n_new, scale, window, ring,
                                     _cfg.INTERPRET)
    return paged_gqa_attention_reference(q, k_arena, v_arena, table, fill,
                                         n_new, scale, window, ring)
