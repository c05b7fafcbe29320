"""ShapeDtypeStruct construction that survives vma-checked shard_map.

Inside ``shard_map(..., check_vma=True)`` (the default, and required for
correct psum transposes — see engine.py), ``pallas_call`` demands that output
avals declare how they vary over mesh axes.  Kernel outputs vary exactly as
the union of their operands' variances, so every pallas_call in this package
builds its ``out_shape`` through :func:`sds`.
"""

from __future__ import annotations

import jax


def sds(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    vma = frozenset()
    for r in operands:
        vma = vma | jax.typeof(r).vma
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def align_param_grad(g, param):
    """psum a custom-VJP *parameter* cotangent over mesh axes the parameter
    is invariant in but the computed grad varies in.

    For regular primitives jax's vma-aware AD inserts exactly this psum when
    transposing the implicit broadcast of a replicated parameter; a
    custom_vjp backward bypasses that machinery, so its parameter grads
    would stay shard-varying — which both breaks vma typing under composed
    transforms (scan-over-backward in the pipeline schedules) and differs
    from what every non-custom op produces.  No-op outside shard_map or when
    the variances already agree.  Downstream reductions stay correct:
    allreduce_grads infers per-leaf from the aval whether a grad is already
    summed.
    """
    from jax import lax
    extra = tuple(sorted(jax.typeof(g).vma - jax.typeof(param).vma))
    return lax.psum(g, extra) if extra else g


def vary_like(x, ref):
    """``x`` cast to vary over every mesh axis ``ref`` varies over (a no-op
    outside shard_map, or where it already does)."""
    from jax import lax
    extra = tuple(sorted(jax.typeof(ref).vma - jax.typeof(x).vma))
    return lax.pcast(x, extra, to="varying") if extra else x
