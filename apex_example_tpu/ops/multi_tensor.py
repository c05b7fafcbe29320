"""Multi-tensor ops: scale/unscale, axpby, L2 norms over parameter pytrees.

Reference (csrc/multi_tensor_{scale,axpby,l2norm}_kernel.cu driven by
apex/multi_tensor_apply/; SURVEY.md §2.1): CUDA pays per-launch overhead, so
apex chunks a *list* of tensors into fixed-size blocks and processes the whole
list in a handful of launches.

TPU-native design decision: XLA compiles the entire step into one program, so
launch amortization — the reason multi_tensor_apply exists — is moot.  What
still matters on TPU is HBM traffic: each op should read its operands once.
We therefore keep the *list-wise API* (pytrees in, pytrees out, one finite
flag / one global norm across the whole list) but implement each leaf as a
lane-aligned Pallas kernel (pad to (rows, 128), grid over row blocks), and the
cross-leaf reduction (norms, finite flags) as a tiny XLA combine of per-leaf
partials.  ``interpret=True`` (tests) runs the same kernels on CPU.

The scale kernel doubles as the overflow detector, exactly like
``amp_C.multi_tensor_scale`` whose out-of-band flag the loss scaler reads
(SURVEY.md §4.3) — here the flag is a traced bool, no host sync.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from apex_example_tpu.ops import _config as _cfg
from apex_example_tpu.ops._vma import sds

_LANES = 128
_BLOCK_ROWS = 512  # 512*128*4B = 256 KiB per buffer — comfortably in VMEM


def _interpret() -> bool:
    return _cfg.interpret()


def _use_pallas(*operands) -> bool:
    return _cfg.use_pallas_for(*operands)


def _to_lanes(x: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """Flatten a leaf and pad to a (rows, 128) lane-aligned 2-D buffer."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % _LANES
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANES), n


def _grid_rows(rows: int) -> Tuple[int, int]:
    """Pick (block_rows, pad_rows): rows pad to a sublane multiple (8), the
    block is the largest power-of-two divisor <= _BLOCK_ROWS so padding never
    exceeds 7 rows (a leaf just over a block boundary must not double its
    HBM traffic)."""
    padded = rows + ((-rows) % 8)
    block = _BLOCK_ROWS
    while padded % block:
        block //= 2
    return block, padded - rows


def _pad_rows(x2d, pad):
    return jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d


def _unpad(t, n, like):
    return t.reshape(-1)[:n].reshape(like.shape)


# --------------------------------------------------------------------------
# scale (+ finite check)
# --------------------------------------------------------------------------

def _scale_kernel(x_ref, s_ref, y_ref, bad_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        bad_ref[0, 0] = jnp.zeros((), jnp.int32)

    xf = x_ref[:].astype(jnp.float32)
    y = xf * s_ref[0]
    y_ref[:] = y.astype(y_ref.dtype)
    nonfinite = jnp.logical_not(jnp.isfinite(xf)).any()
    bad_ref[0, 0] += nonfinite.astype(jnp.int32)


def _scale_leaf_pallas(x: jnp.ndarray, scale: jnp.ndarray):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x2d, n = _to_lanes(x)
    rows = x2d.shape[0]
    block, pad_rows = _grid_rows(rows)
    x2d = _pad_rows(x2d, pad_rows)
    grid = x2d.shape[0] // block

    y, bad = pl.pallas_call(
        _scale_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            sds(x2d.shape, x.dtype, x2d),
            sds((1, 1), jnp.int32, x2d),
        ],
        name="scale",
        interpret=_interpret(),
    )(x2d, scale.astype(jnp.float32).reshape(1))
    return _unpad(y, n, x), bad[0, 0] > 0


def multi_tensor_scale(tree: Any, scale) -> Tuple[Any, jnp.ndarray]:
    """out = in * scale for every leaf; plus an any-nonfinite flag.

    Returns (scaled_tree, all_finite).  Matches amp_C.multi_tensor_scale's
    contract: the flag reflects the *input* values (a nonfinite input is the
    overflow signal, regardless of scale).
    """
    scale = jnp.asarray(scale, jnp.float32)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree, jnp.asarray(True)
    if _use_pallas(scale, *leaves):
        outs, bads = zip(*[_scale_leaf_pallas(l, scale) for l in leaves])
        all_finite = jnp.logical_not(jnp.stack(bads).any())
    else:
        outs = [(l.astype(jnp.float32) * scale).astype(l.dtype)
                for l in leaves]
        all_finite = jnp.stack(
            [jnp.all(jnp.isfinite(l)) for l in leaves]).all()
    return jax.tree_util.tree_unflatten(treedef, outs), all_finite


# --------------------------------------------------------------------------
# axpby
# --------------------------------------------------------------------------

def _axpby_kernel(x_ref, y_ref, ab_ref, o_ref):
    xf = x_ref[:].astype(jnp.float32)
    yf = y_ref[:].astype(jnp.float32)
    o_ref[:] = (ab_ref[0] * xf + ab_ref[1] * yf).astype(o_ref.dtype)


def _axpby_leaf_pallas(x, y, a, b):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x2d, n = _to_lanes(x)
    y2d, _ = _to_lanes(y)
    rows = x2d.shape[0]
    block, pad_rows = _grid_rows(rows)
    x2d = _pad_rows(x2d, pad_rows)
    y2d = _pad_rows(y2d, pad_rows)
    grid = x2d.shape[0] // block
    ab = jnp.stack([jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)])

    out = pl.pallas_call(
        _axpby_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=sds(x2d.shape, y.dtype, x2d, y2d),
        name="axpby",
        interpret=_interpret(),
    )(x2d, y2d, ab)
    return _unpad(out, n, x)


def multi_tensor_axpby(a, x_tree: Any, b, y_tree: Any) -> Any:
    """out = a*x + b*y, leafwise (reference: multi_tensor_axpby_kernel.cu)."""
    if _use_pallas(*jax.tree_util.tree_leaves((x_tree, y_tree))):
        return jax.tree_util.tree_map(
            lambda x, y: _axpby_leaf_pallas(x, y, a, b), x_tree, y_tree)
    return jax.tree_util.tree_map(
        lambda x, y: (a * x.astype(jnp.float32)
                      + b * y.astype(jnp.float32)).astype(y.dtype),
        x_tree, y_tree)


# --------------------------------------------------------------------------
# L2 norm (global and per-tensor — LAMB and grad clipping need both)
# --------------------------------------------------------------------------

def _sqsum_kernel(x_ref, acc_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        acc_ref[0, 0] = jnp.zeros((), jnp.float32)

    xf = x_ref[:].astype(jnp.float32)
    acc_ref[0, 0] += jnp.sum(xf * xf)


def _sqsum_leaf_pallas(x) -> jnp.ndarray:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x2d, _ = _to_lanes(x)
    rows = x2d.shape[0]
    block, pad_rows = _grid_rows(rows)
    x2d = _pad_rows(x2d, pad_rows)
    grid = x2d.shape[0] // block
    acc = pl.pallas_call(
        _sqsum_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=sds((1, 1), jnp.float32, x2d),
        name="l2norm",
        interpret=_interpret(),
    )(x2d)
    return acc[0, 0]


def _sqsum_leaf(x) -> jnp.ndarray:
    if _use_pallas(x):
        return _sqsum_leaf_pallas(x)
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf)


def sqsum_leaf(x) -> jnp.ndarray:
    """Public per-leaf ||x||² on the kernel path (NovoGrad's per-tensor
    second moment is the squared grad norm)."""
    return _sqsum_leaf(x)


def multi_tensor_l2norm(tree: Any, per_tensor: bool = False):
    """Global L2 norm of all leaves; optionally also per-leaf norms.

    Reference: multi_tensor_l2norm_kernel.cu (per-block partials + final
    reduce); used by grad clipping and LAMB stage 1.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        z = jnp.asarray(0.0, jnp.float32)
        return (z, []) if per_tensor else z
    sq = [_sqsum_leaf(l) for l in leaves]
    total = jnp.sqrt(jnp.stack(sq).sum())
    if per_tensor:
        return total, [jnp.sqrt(s) for s in sq]
    return total


def clip_grad_norm(grads: Any, max_norm: float, eps: float = 1e-6
                   ) -> Tuple[Any, jnp.ndarray]:
    """Global-norm gradient clipping on the multi_tensor_l2norm path
    (reference harness C5 uses clip_grad_norm with FusedLayerNorm models)."""
    total = multi_tensor_l2norm(grads)
    scale = jnp.minimum(1.0, max_norm / (total + eps))
    clipped, _ = multi_tensor_scale(grads, scale)
    return clipped, total


class MultiTensorApply:
    """API-parity shim for apex.multi_tensor_apply.multi_tensor_applier.

    The chunking machinery has no TPU analog (see module docstring); this
    callable simply dispatches to the list-wise ops above so code written
    against the apex pattern keeps a target to call.
    """

    def __init__(self, chunk_size: int = 2048 * 32):
        self.chunk_size = chunk_size  # recorded; chunking is the compiler's job

    def __call__(self, op, *args, **kwargs):
        return op(*args, **kwargs)
