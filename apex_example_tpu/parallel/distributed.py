"""Data-parallel gradient reduction — the DDP-equivalent layer.

Reference (apex/parallel/distributed.py, SURVEY.md §3.2/§4.3): apex's
``DistributedDataParallel`` registers per-param backward hooks that assemble
~10M-element buckets in grad-ready order and fire ``ncclAllReduce`` overlapped
with the rest of backward; ``delay_allreduce=True`` instead does one flat
allreduce after backward.  The C++ ``apex_C`` flatten/unflatten extension
exists purely to feed NCCL contiguous buffers.

TPU-native design: the gradient allreduce is a ``lax.psum`` over the ``data``
mesh axis *inside* the jitted step.  XLA's latency-hiding scheduler decomposes
and overlaps the collective with the backward computation automatically, which
subsumes the hand-built bucketing (bucket assembly, ready-order tracking, and
the flatten extension have no TPU analog — the compiler owns buffer layout;
this is the documented why for csrc/flatten_unflatten.cpp in SURVEY.md §2.1).
``delay_allreduce`` semantics (single reduction at end of backward) are the
*default* semantics of psum-at-step-end; hence the flag is accepted and
recorded but changes nothing on TPU.

What remains meaningful from the ctor surface is kept with identical names and
faithful numerics:

- ``gradient_average``            — divide the summed grads by world size.
- ``gradient_predivide_factor``   — pre-divide locally by f, post-divide the
  sum by world/f (overflow headroom for fp16 sums).
- ``allreduce_always_fp32``       — upcast grads to fp32 for the reduction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.parallel.mesh import DATA_AXIS


@dataclasses.dataclass(frozen=True)
class DDPConfig:
    """Ctor-surface parity with apex.parallel.DistributedDataParallel.

    ``quantized_allreduce`` (ISSUE 13; EQuARX, PAPERS.md) goes beyond
    the reference surface: the gradient exchange rides int8.  Per
    ``quant_chunk``-element chunk, the devices agree on ONE shared
    max-abs scale (a pmax — every replica must quantize onto the same
    grid or the sum is meaningless), round their local chunk onto it,
    psum the integers with an int32 accumulator (world * 127 per
    element can never wrap), and multiply the sum back by the scale.
    The exchange bytes drop 4x (f32) / 2x (bf16) wire-side; the psum's
    accumulator width is an implementation detail of the reduction,
    exactly as NCCL's fp32 accumulation is for the reference.

    Error bound, documented and pinned by tests/test_parallel.py: each
    replica contributes a rounding error <= scale/2 per element, so
    ``|quantized - exact| <= world * scale / 2`` element-wise, with
    ``scale = max_over_replicas(chunk max-abs) / 127``.  Composition
    with ``allreduce_always_fp32`` is strict: the quantized path always
    scales/accumulates/dequantizes in f32 (there is nothing wider to
    upcast to), then restores the gradient dtype — so flipping
    allreduce_always_fp32 under quantization changes nothing, which is
    the only composition that cannot silently double-round.

    ``quantized_allreduce=False`` (the default) leaves the psum path
    byte-identical to the unquantized implementation.
    """
    gradient_average: bool = True
    gradient_predivide_factor: float = 1.0
    allreduce_always_fp32: bool = False
    quantized_allreduce: bool = False
    quant_chunk: int = 1024
    # Accepted for CLI/API parity; no-ops on TPU (see module docstring):
    delay_allreduce: bool = True
    message_size: int = 10_000_000


def allreduce_grads(grads: Any, config: DDPConfig = DDPConfig(),
                    axis_name: str = DATA_AXIS,
                    already_reduced: Optional[bool] = None) -> Any:
    """psum gradients over the data axis with apex's averaging semantics.

    Must run inside a ``shard_map``/``pmap`` context where ``axis_name`` is
    bound.  Equivalent position in the reference call stack: the DDP backward
    hooks / flat allreduce (SURVEY.md §4.3).

    ``already_reduced``: under vma-checked shard_map (the default, and what
    the engine uses) this is inferred per leaf from the aval — jax.grad wrt
    replicated params yields already-psum'd (invariant) grads.  Under
    ``check_vma=False`` vma information is absent, so callers must pass it
    explicitly (False for raw per-shard grads).
    """
    world = lax.axis_size(axis_name)
    pre = config.gradient_predivide_factor
    post = (world / pre) if config.gradient_average else (1.0 / pre)

    def reduce_one(g):
        dt = g.dtype
        if already_reduced is None:
            reduced = axis_name not in jax.typeof(g).vma
        else:
            reduced = already_reduced
        if reduced:
            # Already cross-replica-summed: under shard_map's vma semantics,
            # jax.grad of a shard-local loss w.r.t. *replicated* params
            # transposes the implicit replication into a psum — the allreduce
            # has effectively happened inside backward (and XLA overlaps it
            # there, exactly like the reference's bucketed hooks).  Only the
            # averaging convention remains to apply.
            if config.gradient_average:
                g = (g.astype(jnp.float32) / world).astype(dt)
            return g
        if config.quantized_allreduce:
            g = _quantized_psum(g, axis_name, config)
            if post != 1.0:
                g = g / post
            return g.astype(dt)
        if config.allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if pre != 1.0:
            g = g / pre
        g = lax.psum(g, axis_name)
        if post != 1.0:
            g = g / post
        return g.astype(dt)

    return jax.tree_util.tree_map(reduce_one, grads)


def _quantized_psum(g, axis_name: str, config: DDPConfig):
    """Shared-scale int8 chunk reduction (DDPConfig docstring).  Input
    may be pre-divided; output is the f32 SUM (the caller applies the
    averaging convention, same as the unquantized path).
    """
    from apex_example_tpu.quant import core as qcore
    chunk = max(int(config.quant_chunk), 1)
    pre = config.gradient_predivide_factor
    flat = g.astype(jnp.float32).reshape(-1)
    if pre != 1.0:
        flat = flat / pre
    n = flat.shape[0]
    pad = (-n) % chunk
    flat = jnp.pad(flat, (0, pad)).reshape(-1, chunk)
    # One scale per chunk, agreed across the axis: pmax of the local
    # max-abs.  Every replica quantizes onto the SAME grid, so the
    # integer psum is exact and the only error is each replica's
    # rounding (<= scale/2 per element per replica).
    scale = lax.pmax(qcore.abs_max_scale(flat, axis=1), axis_name)
    q = qcore.quantize_int8(flat, scale).astype(jnp.int32)
    total = lax.psum(q, axis_name)
    out = total.astype(jnp.float32) * scale
    return out.reshape(-1)[:n].reshape(g.shape)


def broadcast_from_zero(tree: Any, axis_name: str = DATA_AXIS) -> Any:
    """Make replica 0's values authoritative on all replicas.

    Reference: DDP's ctor broadcast of rank-0 params via flat_dist_call
    (SURVEY.md §4.1 "first collective").  In JAX, jit with replicated sharding
    already guarantees consistency, so this is only needed when state was
    constructed per-replica (e.g. distinct RNG); implemented as a masked psum.
    """
    idx = lax.axis_index(axis_name)

    def bcast(x):
        masked = jnp.where(idx == 0, x, jnp.zeros_like(x))
        return lax.psum(masked, axis_name)

    return jax.tree_util.tree_map(bcast, tree)


def reduce_mean(x: jnp.ndarray, axis_name: str = DATA_AXIS) -> jnp.ndarray:
    """Metric averaging (reference harness: reduce_tensor / allreduce-mean)."""
    return lax.pmean(x, axis_name)


class DistributedDataParallel:
    """Thin apex-shaped facade: holds the config, exposes the grad reduction.

    The reference version wraps the module and intercepts backward; pure
    functions have no backward to intercept, so this class just pairs a
    :class:`DDPConfig` with the functions above for callers that want the
    apex ctor spelling::

        ddp = DistributedDataParallel(delay_allreduce=True)
        grads = ddp.allreduce(grads)          # inside shard_map
    """

    def __init__(self, module: Any = None, message_size: int = 10_000_000,
                 delay_allreduce: bool = True, gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 allreduce_always_fp32: bool = False,
                 allreduce_trigger_params: Optional[Any] = None):
        del allreduce_trigger_params  # bucket tuning — no TPU analog
        self.module = module
        self.config = DDPConfig(
            gradient_average=gradient_average,
            gradient_predivide_factor=gradient_predivide_factor,
            allreduce_always_fp32=allreduce_always_fp32,
            delay_allreduce=delay_allreduce,
            message_size=message_size)

    def allreduce(self, grads: Any, axis_name: str = DATA_AXIS) -> Any:
        return allreduce_grads(grads, self.config, axis_name)

    def __call__(self, *args, **kwargs):
        if self.module is None:
            raise ValueError("no module wrapped")
        return self.module(*args, **kwargs)


class Reducer(DistributedDataParallel):
    """apex.parallel.Reducer analog: MANUAL gradient (or buffer) allreduce.

    The reference's Reducer (apex/parallel/__init__.py) is the opt-out from
    DDP's automatic backward hooks — the user wraps the module and calls
    ``reducer.reduce()`` themselves, e.g. once per N accumulation steps.
    Here gradients are explicit values, so the class is the same idea with
    the pytree passed in: call :meth:`reduce` inside shard_map whenever a
    reduction should happen.  Same facade as the DDP class; ``reduce`` is
    the apex-named spelling of ``allreduce``.
    """

    def __init__(self, module: Any = None, gradient_average: bool = True):
        super().__init__(module, gradient_average=gradient_average)

    reduce = DistributedDataParallel.allreduce
