"""Grouped matmul for dropless experts: Pallas TPU kernel + XLA reference.

``a [M, K]`` holds the rows of ``G`` groups one after another, ``sizes[g]``
rows each; group ``g`` is multiplied by its own matrix ``w[g] [K, N]``.  A
server's expert layer is this product three times a layer (gate, up, down)
over a few hundred live rows that touch nearly every expert, so the cost
is the weights: each touched expert's matrix has to cross HBM once, and the
kernel's job is to keep the DMA engine on that stream.

TPU-native design
-----------------
One ``pallas_call`` gridded ``(n tiles, visits)``.  A *visit* is one
(group, row tile) pair that holds a live row: scalar-prefetched metadata
(group offsets, the group and the row tile of every visit) drives the
index maps, and the number of visits is the grid's own dynamic bound, so
row tiles past ``sum(sizes)`` cost nothing.  A visit's weight block is
``[K, tn]``: the whole contraction, megabytes a block, double-buffered by
the pipeline, so that the next expert's block is in flight while this one
is multiplied and no accumulator is carried between steps.  A group that
straddles two row tiles is two consecutive visits with one block index:
the pipeline does not fetch it again.  Every visit multiplies its whole
row tile and stores the rows of its own group alone (the output block
stays resident while consecutive visits share a row tile).

Rows past ``sum(sizes)`` are never written and hold whatever the buffer
held: the caller selects them away.

Numerics: operands as they come (bfloat16 on the serving path), float32
accumulation, float32 out; the fused gate/up form applies ``silu(g) * u``
in float32 and casts once, as the XLA form does.  ``lax.ragged_dot`` is the
CPU's form, ``FORCE_XLA``'s and the tests' golden.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.ops import _config as _cfg
from apex_example_tpu.ops._vma import sds
from apex_example_tpu.ops.attention import _dot_f32

# Row tile: the v5e's MXU is 128 x 128, and a straddling group costs a
# second pass over its weight block on the MXU (never a second fetch).
_ROW_TILE = 128
# The most one weight block may take of VMEM; two buffers a weight.  At the
# served widths (3584 x 1024, bfloat16) a whole expert matrix is 7 MiB, and
# the fused form holds four of them, so the scoped limit is raised.  On the
# v5e with 400 pairs over all 64 experts a product reads 0.68-0.69 ms (690
# GB/s of 819) whatever the tiles: rows 128 / 64 / 32, columns the whole
# width, a half or a quarter all lie within 3% (PERF.md section 6): the
# DMA engine sets the pace, so the simplest tiling stays.
_WEIGHT_BLOCK_BYTES = 8 << 20
_VMEM_LIMIT_BYTES = 100 << 20


def ragged_dot_f32(a, w, sizes):
    """The XLA form: ``a[rows of group g] @ w[g]``, float32 out."""
    return lax.ragged_dot(a, w.astype(a.dtype), sizes,
                          preferred_element_type=jnp.float32)


def _tiles(M: int, K: int, N: int, itemsize: int) -> Tuple[int, int]:
    """(row tile, column tile): all of M where one ``_ROW_TILE`` holds it
    (a whole dimension is always a legal block: 24 rows are one visit a
    group, not three tiles of 8), else the largest row tile up to
    ``_ROW_TILE`` that divides M; and the widest whole-lane-tile divisor of
    N whose ``[K, tn]`` block stays inside ``_WEIGHT_BLOCK_BYTES``."""
    tm = M if M <= _ROW_TILE else next(
        (t for t in (_ROW_TILE, 64, 32, 16, 8) if M % t == 0), M)
    fits = [tn for tn in range(128, N + 1, 128)
            if N % tn == 0 and K * tn * itemsize <= _WEIGHT_BLOCK_BYTES]
    return tm, (fits[-1] if fits else N)


def _kernel_ok(a, *ws) -> bool:
    if not _cfg.use_pallas():
        return False
    if _cfg.INTERPRET:
        return True
    # Mosaic: operands of one dtype (the served model's; a block cast in
    # VMEM is not measured), whole 128-lane tiles across K and N, whole
    # sublane tiles of rows, and a [K, 128] block inside the budget
    (M, K), N, itemsize = a.shape, ws[0].shape[2], a.dtype.itemsize
    return (all(w.dtype == a.dtype for w in ws)
            and K % 128 == 0 and N % 128 == 0
            and M % (8 * 4 // itemsize) == 0
            and K * 128 * itemsize <= _WEIGHT_BLOCK_BYTES)


def visit_metadata(sizes, M: int, tm: int):
    """The walk over ``sizes [G]`` in row tiles of ``tm``: ``(offsets
    [G + 1], group [V], tile [V], n_visits, visits [G])`` with ``V = M //
    tm + G - 1`` the most visits there can be.  Visit ``v < n_visits``
    multiplies row tile ``tile[v]`` by the matrix of ``group[v]``; groups
    come in order and a group's tiles in order, so visits that share a
    weight block or an output block are consecutive.  ``visits[g]``: the
    row tiles group ``g`` has a row in, 0 for an empty one."""
    G = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    visits = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    upto = jnp.cumsum(visits)
    v = jnp.arange(M // tm + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(upto, v, side="right"),
                        G - 1).astype(jnp.int32)
    tile = starts[group] // tm + v - (upto[group] - visits[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group, jnp.clip(tile, 0, M // tm - 1), upto[-1],
            visits)


def _kernel(offsets_ref, group_ref, tile_ref, a_ref, *refs, tm):
    """One visit: the row tile times the group's ``[K, tn]`` block (times
    both blocks and ``silu(gate) * up`` where there are two), stored on the
    group's own rows."""
    *w_refs, o_ref = refs
    v = pl.program_id(1)
    g = group_ref[v]
    row = tile_ref[v] * tm + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
    mine = jnp.logical_and(row >= offsets_ref[g], row < offsets_ref[g + 1])
    a = a_ref[...]
    out = _dot_f32(a, w_refs[0][...].astype(a.dtype))
    if len(w_refs) == 2:
        out = jax.nn.silu(out) * _dot_f32(a, w_refs[1][...].astype(a.dtype))
    o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


# Deferred pallas import, as ops/attention.py's.
pl = None
pltpu = None


def _bind_pallas():
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu
        pl, pltpu = _pl, _pltpu


# jitted so that a model's layers share one trace and one lowering of each
# of the kernel's two forms
@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_pallas(a, ws, sizes, interpret):
    _bind_pallas()
    (M, K), N = a.shape, ws[0].shape[2]
    tm, tn = _tiles(M, K, N, a.dtype.itemsize)
    offsets, group, tile, n_visits, visits = visit_metadata(sizes, M, tm)
    fused = len(ws) == 2
    weight = pl.BlockSpec((None, K, tn),
                          lambda n, v, offsets, group, tile: (group[v], 0, n))
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, K), lambda n, v, offsets, group,
                                   tile: (tile[v], 0))] + [weight] * len(ws),
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, offsets, group,
                                   tile: (tile[v], n))),
        out_shape=sds((M, N), a.dtype if fused else jnp.float32, a, *ws),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="grouped_swiglu" if fused else "grouped_matmul",
        interpret=interpret,
    )(offsets, group, tile, a, *ws)
    return out, visits


def grouped_matmul(a, w, sizes) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """``a[rows of group g] @ w[g]`` for ``a [M, K]``, ``w [G, K, N]`` and
    ``sizes [G]`` (int32, ``sum(sizes) <= M``): ``(out [M, N] float32,
    visits)``.  Rows past ``sum(sizes)`` hold anything.  ``visits [G]``
    (int32): the row tiles the kernel visited for each group, one fetch of
    the group's matrix between them; None from the XLA form
    (``lax.ragged_dot``), which runs off the TPU, under ``FORCE_XLA``, for
    operands of two dtypes and for shapes Mosaic cannot tile (the
    interpreter takes any: a block of ``w`` is cast where it lies)."""
    if _kernel_ok(a, w):
        return _grouped_pallas(a, (w,), sizes, _cfg.INTERPRET)
    return ragged_dot_f32(a, w, sizes), None


def grouped_swiglu(a, w_gate, w_up, sizes
                   ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """``silu(a @ w_gate[g]) * (a @ w_up[g])`` group by group, in float32,
    cast to ``a``'s dtype: ``(out [M, N], visits)`` as
    :func:`grouped_matmul`'s.  The kernel reads ``a`` once for both
    products and keeps the two float32 intermediates on the chip."""
    if _kernel_ok(a, w_gate, w_up):
        return _grouped_pallas(a, (w_gate, w_up), sizes, _cfg.INTERPRET)
    return (jax.nn.silu(ragged_dot_f32(a, w_gate, sizes))
            * ragged_dot_f32(a, w_up, sizes)).astype(a.dtype), None
