"""Quantized paged-KV helpers: the in-step scatter/gather numerics.

The block arena (ops/paged_cache.py layout and operations, serve/slots.py
policy, models/bert.py attention) stores int8 K/V with BLOCK-RESIDENT scales: per layer, alongside each
``[NB, BS, H*D]`` int8 arena sits a ``[NB, BS]`` bf16 scale table —
one symmetric max-abs scale per cached token (the [H, D] vector a
block row holds, stored as one merged dimension).  Scales live AT
block granularity in the arena, so every block operation carries
them for free:

- the tick's scatter writes ``quantize_write``'s int8 rows and their
  scales through the SAME flat block-table indices,
- a copy-on-write duplicates the scale rows with the payload rows
  (diverging a shared block must not re-derive scales the original
  tokens were quantized under),
- prefix sharing refs whole blocks, scales included — a shared system
  prompt's KV is quantized once and read by every sharer.

Per-token (not per-whole-block) scales are what make partial writes
composable: a block fills across several chunked-prefill ticks, and a
single running block scale would force requantization of rows written
under an earlier max.  bf16 scale storage halves the overhead vs f32
and costs <= 2^-9 relative scale error — quantization rounds against
the STORED scale (quant/core.py), so the round-trip bound still holds
exactly.

Per-token bytes at gpt_tiny geometry (H*D = 64): 64 int8 + 2 scale =
66 per K or V vs 128 bf16 — a 1.94x compression the ci_gate
``--quant-stream`` floor (>= 1.9x) keys on.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from apex_example_tpu.quant import core

KV_SCALE_DTYPE = jnp.bfloat16


def quantize_write(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize one tick's K (or V) span ``[S, C, H, D]`` for the arena
    scatter: returns ``(q int8 [S, C, H, D], scales KV_SCALE_DTYPE
    [S, C])`` — one max-abs scale per token over its [H, D] vector,
    rounded to storage precision BEFORE the division so dequant against
    the stored scale is exact to the int8 grid."""
    scale = core.abs_max_scale(x, axis=(-2, -1),
                               keepdims=False).astype(KV_SCALE_DTYPE)
    q = core.quantize_int8(x, scale[..., None, None])
    return q, scale


def dequantize_gather(q: jnp.ndarray, scale: jnp.ndarray,
                      dtype) -> jnp.ndarray:
    """Dequantize a gathered logical view ``[S, L, H, D]`` with its
    ``[S, L]`` scales — the scale-fused multiply the attention einsum
    consumes directly inside the compiled step."""
    return core.dequantize(q, scale[..., None, None], dtype)
