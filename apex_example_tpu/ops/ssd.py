"""The selective state-space recurrence of a Mamba-2 layer in its chunked
(SSD) form, and the short causal convolution in front of it.

Per head ``h`` (``P`` channels, ``N`` state columns, one scalar decay):

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        a_t = exp(dt_t A_h),  A_h < 0
    y_t = S_t C_t + D_h x_t

A lane-by-lane scan carries ``S`` (``[H, P, N]`` float32 a sequence)
through memory once a lane.  The chunked form reads it once and writes it
once a call: with ``cum_t = sum_{j<=t} dt_j A`` (so ``exp(cum_t)`` is the
decay from the call's start to lane ``t``),

    y_t = exp(cum_t) (S_0 C_t)
          + sum_{j<=t} (C_t . B_j) exp(cum_t - cum_j) dt_j x_j  +  D x_t
    S_L = exp(cum_L) S_0 + sum_j exp(cum_L - cum_j) dt_j x_j B_j^T

every ratio of decays formed as ``exp`` of a difference of ``cum``
(float32; never a quotient of two underflowed products), and nothing of
size ``[.., L, H, P, N]`` exists.  Sequences longer than ``chunk`` go
chunk by chunk with the state carried between (``lax.scan``): the model's
plain forward at the published ``mamba_chunk_size``, a serve tick's
``[SLOTS, C]`` lanes in one chunk.

A dead lane has ``dt = 0``: it decays nothing and adds nothing, so the
state returned is the one after the last *live* lane, and a sequence with
no live lane keeps its state bit for bit.  Everything here is float32
with the products at ``Precision.HIGHEST`` (the state is the one quantity
of the model that must not round through bfloat16: it is carried for the
whole request).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=F32)


def _chunk(state, x, dt, A, B, C, reset=None):
    """One chunk: state [S,H,P,N], x [S,L,H,P], dt [S,L,H] (zero on dead
    lanes), A [H], B and C [S,L,N] -> (y [S,L,H,P] without the D term, the
    state after the chunk).  ``reset`` [S]: read the state as zero.  It is
    applied to what is computed from the state, not to the state (whatever
    it holds, NaN included, is selected away): a zeroed copy would be one
    more pass over ``[S,H,P,N]``, which each of the two products below
    reads where it lies."""
    L = x.shape[1]
    zero = (lambda t: t) if reset is None else (lambda t: jnp.where(
        reset.reshape((-1,) + (1,) * (t.ndim - 1)), 0.0, t))
    cum = jnp.cumsum(dt * A, axis=1)                          # [S,L,H]
    # what the carried state gives every lane
    y = zero(_ein("sln,shpn->slhp", C, state)) * jnp.exp(cum)[..., None]
    # what the chunk's own lanes give each other: lane t reads j <= t
    seg = cum[:, :, None, :] - cum[:, None, :, :]             # [S,t,j,H]
    seen = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    w = jnp.exp(jnp.where(seen, seg, -jnp.inf)) \
        * _ein("sln,sjn->slj", C, B)[..., None] * dt[:, None, :, :]
    y = y + _ein("stjh,sjhp->sthp", w, x)
    # the state handed on
    w_end = jnp.exp(cum[:, -1:, :] - cum) * dt                # [S,L,H]
    new = zero(state * jnp.exp(cum[:, -1])[:, :, None, None]) \
        + _ein("sjhp,sjn->shpn", x * w_end[..., None], B)
    return y, new


def ssd_scan(state, x, dt, a_log, B, C, D, live, *, chunk: int,
             reset=None) -> Tuple[jax.Array, jax.Array]:
    """``state`` [S,H,P,N] float32 at the start; ``x`` [S,L,H,P], ``dt``
    [S,L,H] (the step after its softplus), ``B`` and ``C`` [S,L,N], ``live``
    [S,L] bool; ``a_log`` and ``D`` [H].  ``reset`` [S] bool: sequences that
    start from a zero state whatever ``state`` holds (a request slot at its
    first token).  Returns ``(y [S,L,H,P] float32, state after the last
    live lane)``."""
    S, L = x.shape[:2]
    x, B, C = x.astype(F32), B.astype(F32), C.astype(F32)
    dt = jnp.where(live[..., None], dt.astype(F32), 0.0)
    A = -jnp.exp(a_log.astype(F32))
    if L <= chunk:
        y, new = _chunk(state, x, dt, A, B, C, reset)
    else:
        pad = -L % chunk
        parts = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 .reshape((S, -1, chunk) + t.shape[2:]).swapaxes(0, 1)
                 for t in (x, dt, B, C)]

        def step(carry, part):
            y, carry = _chunk(carry, *part[:2], A, *part[2:])
            return carry, y

        first = state if reset is None else jnp.where(
            reset[:, None, None, None], 0.0, state)
        new, ys = jax.lax.scan(step, first, tuple(parts))
        y = ys.swapaxes(0, 1).reshape((S, -1) + x.shape[2:])[:, :L]
    y = y + D.astype(F32)[None, None, :, None] * x
    # bit for bit where nothing was live (x * 1 + 0 turns a -0.0 to +0.0)
    moved = jnp.any(live, axis=1)
    return y, jnp.where(moved[:, None, None, None], new, state)


def causal_conv(rows, x, w, b, n_new, reset=None):
    """Depthwise causal convolution of width ``K`` over ``x`` [S,L,ch]
    behind the ``K - 1`` rows kept from before (``rows`` [S,K-1,ch], zeros
    at a sequence's start): ``out_t = b + sum_k w[k] x_{t-K+1+k}``, float32.
    ``w`` [K,ch], ``b`` [ch].  The live lanes of a sequence are its first
    ``n_new[s]``; returns ``(out [S,L,ch], the last K - 1 live rows)``, the
    rows unchanged, bit for bit, where ``n_new`` is 0."""
    K, L = w.shape[0], x.shape[1]
    if reset is not None:
        rows = jnp.where(reset[:, None, None], jnp.zeros((), rows.dtype),
                         rows)
    window = jnp.concatenate([rows, x.astype(rows.dtype)], axis=1)
    wf = w.astype(F32)
    out = b.astype(F32) + sum(wf[k] * window[:, k:k + L].astype(F32)
                              for k in range(K))
    idx = n_new[:, None] + jnp.arange(K - 1)[None, :]
    kept = jnp.take_along_axis(window, idx[..., None], axis=1)
    return out, kept

