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

Two forms of one chunk
----------------------
``_chunk`` is the XLA form: three einsums, each reading every sequence's
state where it lies, and a select that keeps the sequences nothing moved.
It is the CPU's form, ``FORCE_XLA``'s, the plain forward's (``lax.scan``
over chunks), the one odd shapes get, and the tests' golden.

``ssd_chunk`` is the Pallas form a serve tick takes on the TPU (one chunk,
float32 state, ``(P, N)`` in whole ``(8, 128)`` tiles; ``_kernel_ok``).  The
sequences with a live lane are compacted into a list that is scalar-
prefetched; the grid walks that list and no further (its length is the
grid's own bound), one sequence a step.  A step *fetches* the sequence's
``[H, P, N]`` state once (2 MB at the served widths, double-buffered by the
pipeline) and, on the tile where it lies in VMEM, computes the read-out
``C S^T`` and the update ``(w x)^T B`` on the MXU at ``HIGHEST`` and the
intra-chunk product on the VPU in exact float32, one pass a lane up to the
last live one (``C_t . B_j`` included); it *writes* the state back once.
The state input is *aliased* to the state output, so a sequence off the
list is neither read nor written and keeps its bits; ``reset`` selects the
fetched tile away inside the kernel, NaN or not.  ``y`` (with its ``D x``)
goes into the call as zeros and comes out the same buffer, so a sequence
off the list reads zero; both sides of the call keep the lanes' rows as
``[S, L, H * P]``.  On the v5e a step takes ≈ 9-10 us, hardly more than the
DMA of its 4.7 MB; the products all but hide behind it (PERF.md section 5).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.ops import _config as _cfg
from apex_example_tpu.ops._vma import sds

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST

# What one grid step may hold of VMEM: a sequence's state in and out, two
# buffers each (8.4 MB at the served [64, 64, 128]), the MXU's three
# bfloat16 parts of it and the update before it is added (5 MB), the lanes'
# rows.  Over the 16 MiB Mosaic grants by default, well under the v5e's 128.
_VMEM_LIMIT_BYTES = 64 << 20


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


def _kernel_ok(state, x, chunk: int) -> bool:
    """The Pallas form takes one chunk over a float32 state whose heads
    lie in whole tiles: ``(P, N)`` in ``(8, 128)`` tiles, and ``P`` dividing
    the 128 lanes or a multiple of them (the lanes' rows are ``[L, H * P]``,
    and a head's factor is spread over its ``P`` lanes tile by tile).  The
    interpreter is held to the same shapes: a tiny model's scan is the XLA
    form under the tests too."""
    (_, L, H, P), N = x.shape, state.shape[-1]
    return (_cfg.use_pallas() and state.dtype == F32 and L <= chunk
            and P % 8 == 0 and N % 128 == 0 and (H * P) % 128 == 0
            and (128 % P == 0 or P % 128 == 0))


def _kernel(seq_ref, fresh_ref, lanes_ref, decay_ref, s_ref, x_ref, cum_ref,
            dt_ref, b_ref, c_ref, d_ref, _, o_ref, y_ref, rows_x, new, *, H, P):
    """One sequence of the list.  ``s_ref``/``o_ref`` [H, P, N] (one
    buffer in HBM), ``x_ref``/``y_ref`` [L, H * P], ``cum_ref``/``dt_ref``
    [L, H], ``b_ref``/``c_ref`` [L, N], ``d_ref`` [1, H * P] (``D`` on each
    head's lanes); scratch ``rows_x`` [2 L, H * P] (``cum`` over ``dt``,
    each head's on its ``P`` lanes) and ``new`` [H, P, N] (the update
    before the decayed state is added).  In SMEM: the list, ``reset``, one
    past the last live lane, ``exp(cum_L)`` [S, H].  Loops are rolled where
    an index may be dynamic: what is traced and lowered at every process
    start is some two hundred operations, not nine hundred."""
    s = seq_ref[pl.program_id(0)]
    L, M = x_ref.shape
    N = s_ref.shape[-1]
    # a head's cum and dt on each of its P lanes, a 128-lane tile at a time
    # (a lane offset has to be static)
    both = jnp.concatenate([cum_ref[...], dt_ref[...]], axis=0)     # [2L, H]
    width = max(P, 128)
    lane = lax.broadcasted_iota(jnp.int32, (2 * L, width), 1)
    for q in range(M // width):
        tile = None
        for k in range(width // P):
            h = q * (width // P) + k
            mine = jnp.broadcast_to(both[:, h:h + 1], (2 * L, width))
            tile = mine if tile is None else jnp.where(lane >= k * P, mine,
                                                       tile)
        rows_x[:, q * width:(q + 1) * width] = tile
    fresh = fresh_ref[s] != 0
    cum, dt, x, c = rows_x[:L], rows_x[L:], x_ref[...], c_ref[...]
    # what the carried state gives every lane
    y = lax.dot_general(c, s_ref[...].reshape(M, N),
                        (((1,), (1,)), ((), ())), precision=HI,
                        preferred_element_type=F32)               # [L, M]
    y_ref[...] = jnp.where(fresh, 0.0, y) * jnp.exp(cum) + d_ref[...] * x
    # what the chunk's own lanes give each other: lane t reads j <= t
    row = lax.broadcasted_iota(jnp.int32, (L, 1), 0)

    def lane_j(j, carry):
        at = lambda ref, r: ref[pl.ds(r, 1), :]
        g = jnp.sum(c * at(b_ref, j), axis=1, keepdims=True)     # C_t . B_j
        seg = jnp.where(row >= j, cum - at(rows_x, j), -jnp.inf)
        y_ref[...] += jnp.exp(seg) * g * (at(rows_x, L + j) * at(x_ref, j))
        return carry
    lax.fori_loop(0, lanes_ref[s], lane_j, 0)
    # the state handed on.  Every read of the fetched state comes before the
    # write to the same place of the output: where XLA holds the caller's
    # buffer in VMEM already (a state of a few slots), the two are one memory
    new[...] = lax.dot_general(
        x * (jnp.exp(cum[L - 1:L] - cum) * dt), b_ref[...],
        (((0,), (0,)), ((), ())), precision=HI,
        preferred_element_type=F32).reshape(H, P, N)

    def head(h, carry):
        kept = s_ref[h] * decay_ref[s, h]
        o_ref[h] = jnp.where(fresh, 0.0, kept) + new[h]
        return carry
    lax.fori_loop(0, H, head, 0)


# Deferred pallas import, as ops/attention.py's.
pl = None
pltpu = None


def _bind_pallas():
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu
        pl, pltpu = _pl, _pltpu


# jitted so that a model's layers share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_pallas(state, x, dt, A, B, C, D, live, reset, interpret):
    """``_chunk`` with the ``D`` term and the select that follow it, as one
    kernel over the sequences that have a live lane: ``(y, state, moved [S]
    int32)``.  ``y`` goes in as zeros and comes out the same buffer, so a
    sequence the kernel does not visit reads zero; the lanes' rows are
    ``[S, L, H * P]`` on both sides of the call (a head's 64 channels are
    half a lane tile: ``[.., H, P]`` is another layout on the TPU, a copy
    of ``x`` and of ``y`` a layer away)."""
    _bind_pallas()
    (S, L, H, P), N = x.shape, B.shape[-1]
    M = H * P
    x = x.reshape(S, L, M)
    cum = jnp.cumsum(dt * A, axis=1)                              # [S,L,H]
    moved = jnp.any(live, axis=1)
    # the sequences that moved, in order; the grid stops where they end
    seqs = jnp.argsort(~moved, stable=True).astype(jnp.int32)
    lanes = jnp.max(jnp.where(live, jnp.arange(1, L + 1), 0), axis=1)

    def of_seq(*block):          # the walked sequence's block of an operand
        return pl.BlockSpec((None,) + block, lambda i, seqs, *_:
                            (seqs[i],) + (0,) * len(block))
    whole, rows = of_seq(H, P, N), functools.partial(of_seq, L)
    new, y = pl.pallas_call(
        functools.partial(_kernel, H=H, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.sum(moved),),
            in_specs=[whole, rows(M), rows(H), rows(H), rows(N), rows(N),
                      pl.BlockSpec((1, M), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[whole, rows(M)],
            scratch_shapes=[pltpu.VMEM((2 * L, M), F32),
                            pltpu.VMEM((H, P, N), F32)]),
        out_shape=[sds(state.shape, F32, state, x),
                   sds((S, L, M), F32, state, x)],
        input_output_aliases={4: 0, 11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="ssd_chunk",
        interpret=interpret,
    )(seqs, reset.astype(jnp.int32), lanes.astype(jnp.int32),
      jnp.exp(cum[:, -1]), state, x, cum, dt, B, C, jnp.repeat(D, P)[None],
      jnp.zeros((S, L, M), F32))
    return y.reshape(S, L, H, P), new, moved.astype(jnp.int32)


def ssd_scan_counted(state, x, dt, a_log, B, C, D, live, *, chunk: int,
                     reset=None
                     ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """:func:`ssd_scan` and what the kernel visited: ``visits [S]`` int32,
    1 where it fetched and wrote a sequence's state, None from the XLA form
    (which reads every sequence's)."""
    S, L = x.shape[:2]
    x, B, C = x.astype(F32), B.astype(F32), C.astype(F32)
    dt = jnp.where(live[..., None], dt.astype(F32), 0.0)
    A = -jnp.exp(a_log.astype(F32))
    if _kernel_ok(state, x, chunk):
        return _chunk_pallas(
            state, x, dt, A, B, C, D.astype(F32), live,
            jnp.zeros((S,), bool) if reset is None else reset,
            _cfg.INTERPRET)
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
    return y, jnp.where(moved[:, None, None, None], new, state), None


def ssd_scan(state, x, dt, a_log, B, C, D, live, *, chunk: int,
             reset=None) -> Tuple[jax.Array, jax.Array]:
    """``state`` [S,H,P,N] float32 at the start; ``x`` [S,L,H,P], ``dt``
    [S,L,H] (the step after its softplus), ``B`` and ``C`` [S,L,N], ``live``
    [S,L] bool; ``a_log`` and ``D`` [H].  ``reset`` [S] bool: sequences that
    start from a zero state whatever ``state`` holds (a request slot at its
    first token).  Returns ``(y [S,L,H,P] float32, state after the last
    live lane)``."""
    return ssd_scan_counted(state, x, dt, a_log, B, C, D, live, chunk=chunk,
                            reset=reset)[:2]


def causal_conv(rows, x, w, b, n_new, reset=None):
    """Depthwise causal convolution of width ``K`` over ``x`` [S,L,ch]
    behind the ``K - 1`` rows kept from before (``rows`` [S,K-1,ch], zeros
    at a sequence's start): ``out_t = b + sum_k w[k] x_{t-K+1+k}``, float32.
    ``w`` [K,ch], ``b`` [ch] or None (no bias).  The live lanes of a
    sequence are its first
    ``n_new[s]``; returns ``(out [S,L,ch], the last K - 1 live rows)``, the
    rows unchanged, bit for bit, where ``n_new`` is 0."""
    K, L = w.shape[0], x.shape[1]
    if reset is not None:
        rows = jnp.where(reset[:, None, None], jnp.zeros((), rows.dtype),
                         rows)
    window = jnp.concatenate([rows, x.astype(rows.dtype)], axis=1)
    wf = w.astype(F32)
    bf = None if b is None else b.astype(F32)
    out = sum(wf[k] * window[:, k:k + L].astype(F32) for k in range(K))
    if bf is not None:
        out = bf + out
    idx = n_new[:, None] + jnp.arange(K - 1)[None, :]
    kept = jnp.take_along_axis(window, idx[..., None], axis=1)
    return out, kept

