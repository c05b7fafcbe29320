"""What the served decoders share, one definition each.  ``models/xing4.py``,
``granite_hybrid.py``, ``pangu_moe.py``, ``trinity.py`` and ``lfm2.py`` import
this module and none of them imports another; this module imports ``ops/``
and ``transformer/expert_parallel.py`` and no model.  What differs between
the models (projections, head norms, rotation, gates, the block's frame, the
counters it sows) stays beside the model.

*The helpers*: ``matmul_f32`` / ``einsum_f32``, RMSNorm under its two
contracts (``rms_norm_f32`` returns float32, ``rms_norm`` the input's dtype),
``rotate_half``, the ``fan_in`` initializer, and ``need_host_state``, the
check every paged sublayer makes of the tick's ``paged`` dict.  *The layers*:
``SwiGLU``, ``RoutedExperts`` and ``LatentAttention`` (with the YaRN
frequencies it alone needs).  *A GQA sublayer's shared halves*:
``paged_gqa_step``, the cache protocol of a paged tick (the leaves, the
host-state check, copy-on-write, the rows' places, the write,
``ops.attention.paged_gqa_attention``), and ``causal_gqa_attention``, the
plain forward of the init trace and the unpaged call; the sublayer's own
arithmetic goes around the two.
"""

from __future__ import annotations

import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_example_tpu.obs.spans import device_span
from apex_example_tpu.ops import paged_cache
from apex_example_tpu.ops.attention import (paged_gqa_attention,
                                            paged_latent_attention)
from apex_example_tpu.transformer.expert_parallel import (dropless_experts,
                                                          dropless_route,
                                                          expert_load)

F32 = jnp.float32


def fan_in(n: int):
    return nn.initializers.normal(1.0 / math.sqrt(n))


def matmul_f32(a, b):
    """``a @ b`` accumulated and returned in float32 (on the TPU the MXU
    multiplies bfloat16 operands exactly and adds in float32)."""
    return jnp.matmul(a, b, preferred_element_type=F32)


def einsum_f32(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def rms_norm_f32(x, scale, eps):
    """RMSNorm with float32 statistics, returned in float32; ``scale`` None
    = no learned scale."""
    y = x.astype(F32)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y if scale is None else y * scale.astype(F32)


def rms_norm(x, scale, eps):
    """``rms_norm_f32`` returned in ``x``'s dtype."""
    return rms_norm_f32(x, scale, eps).astype(x.dtype)


def rotate_half(x, pos, theta: float):
    """``x [B, L, H, hd]`` rotated at positions ``pos [B, L]`` over all of
    ``hd`` (pairs ``(i, i + hd / 2)``), float32 inside."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def need_host_state(paged, ring: bool = False):
    """A paged sublayer past its init trace needs the tick's host state;
    one that reads a window leaf (``ring``) needs the ring table in it."""
    if paged is None or (ring and "ring_table" not in paged):
        raise ValueError(
            "paged slot decode needs the host state: pass "
            "paged={'block_table', " + ("'ring_table', " if ring else "")
            + "'fill', 'n_new', 'cow_src', 'cow_dst'} (serve/engine.py "
            "builds it each tick)")


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """The ``dim / 2`` rotary frequencies under YaRN: ``1/theta_i`` where
    more than ``beta_fast`` rotations fit the original context,
    ``1/(factor theta_i)`` where fewer than ``beta_slow`` do, and a linear
    blend over the dimensions between."""
    def corr_dim(rot):
        return dim * math.log(original_max / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=F32)
    extra = 1.0 / theta ** (2 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


class SwiGLU(nn.Module):
    hidden_size: int
    width: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d, f = self.hidden_size, self.width
        w_gate = self.param("w_gate", fan_in(d), (d, f), self.param_dtype)
        w_up = self.param("w_up", fan_in(d), (d, f), self.param_dtype)
        w_down = self.param("w_down", fan_in(f), (f, d), self.param_dtype)
        h = (jax.nn.silu(matmul_f32(x, w_gate))
             * matmul_f32(x, w_up)).astype(self.dtype)
        return matmul_f32(h, w_down).astype(self.dtype)


class RoutedExperts(nn.Module):
    """Dropless top-k of ``n_experts`` on sigmoid scores with a
    selection-only bias, plus ``n_shared`` shared experts as one SwiGLU of
    ``n_shared * width`` (none at 0).  ``experts_held = (first,
    count)``: the routed experts whose weights live here; the router always
    has its ``n_experts`` outputs, and what the other experts would add is
    left out (another chip's share).  Returns ``(y, load, visits)``: ``load
    [n_experts]`` the live lanes routed to each expert, ``visits
    [n_experts]`` the row tiles the grouped kernel visited for each (0 for
    one not held or not touched), None from the XLA form."""

    hidden_size: int
    width: int
    n_experts: int
    top_k: int
    scale: float
    experts_held: Tuple[int, int]
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    n_shared: int = 1

    @nn.compact
    def __call__(self, x, live=None):
        d, f, E = self.hidden_size, self.width, self.n_experts
        count = self.experts_held[1]
        router = self.param("router", fan_in(d), (d, E), F32)
        bias = self.param("router_bias", nn.initializers.zeros, (E,), F32)
        w_gate = self.param("w_gate", fan_in(d), (count, d, f),
                            self.param_dtype)
        w_up = self.param("w_up", fan_in(d), (count, d, f),
                          self.param_dtype)
        w_down = self.param("w_down", fan_in(f), (count, f, d),
                            self.param_dtype)
        flat = x.reshape(-1, d)
        idx, gates = dropless_route(flat, router, bias, self.top_k,
                                    self.scale)
        live = None if live is None else live.reshape(-1)
        y, visits = dropless_experts(flat, idx, gates, w_gate, w_up, w_down,
                                     self.experts_held, live)
        if visits is not None:
            first = self.experts_held[0]
            visits = jnp.pad(visits, (first, E - first - count))
        if self.n_shared:
            with device_span("shared_expert"):
                y = y + SwiGLU(d, f * self.n_shared, self.dtype,
                               self.param_dtype, name="shared")(flat)
        load = expert_load(idx, E, live)
        return y.reshape(x.shape), load, visits


class LatentAttention(nn.Module):
    """MLA, in two forms of the same mathematics.  The plain forward
    (training-shaped, no cache) *expands* the latent ``c_kv`` into per-head
    keys and values.  The paged slot-decode path (``decode=True,
    slot_decode=True``; the contract ``serve/slots.BlockPool`` and
    ``serve/engine.ServeEngine`` hold every served model to) caches
    ``c_kv ⊕ k_rope`` — ``kv_lora_rank + qk_rope_head_dim`` values a token
    a layer, after the norm and after the rotation, in ONE head-less
    ``[num_blocks, block_size, W]`` arena leaf (``W`` those 576 values
    rounded up to whole 128-lane tiles, 640: what the tiled layout occupies
    anyway) — and attends in the *absorbed* form: queries are carried into
    the latent space (``q_nope W_UK^T``), scores and the weighted sum run
    against the cached latents themselves, and the result is carried out
    through ``W_UV``.  The cache is never up-projected.  The leaf, its
    copy-on-write, write and gather are ops/paged_cache.py's, as
    models/bert.py's are (one layout, the cache donated: updated in place).

    Scores, mask, softmax and weighted sum of the paged path are one op,
    ``ops.attention.paged_latent_attention``, in two forms chosen by the
    backend as every kernel here is.  On the TPU (and under the
    interpreter, which the tests run) a Pallas kernel walks each slot's
    live blocks where they lie in the arena — ``ceil((fill + n_new) /
    block)`` of them, none for a dead slot, a decode slot's one live lane
    in one row tile — with an online softmax in float32 scratch, and no
    ``[S, L, W]`` view or ``[S, H, C, L]`` score tensor exists.  On the CPU
    and under ``FORCE_XLA`` the XLA form gathers every slot's whole row of
    the block table (``kv_gather``) and scores all ``L`` positions: the
    same function, the tests' golden.

    Returns ``(y, walked)``: ``walked [S]`` the cache positions the paged
    form read for each slot this call, None from the plain forward."""

    hidden_size: int
    num_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    q_lora_rank: int
    kv_lora_rank: int
    rms_norm_eps: float
    rope: Tuple[float, ...]      # theta, factor, original_max, beta_fast,
    #                              beta_slow, mscale, mscale_all_dim
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False
    slot_decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0

    def _rotate(self, x, pos):
        """x [B, L, (H,) dr] at positions pos [B, L] (or rows ``[R, (H,)
        dr]`` at ``[R]``), float32 inside."""
        theta, factor, orig, fast, slow, ms, ms_all = self.rope
        inv = yarn_inv_freq(self.qk_rope_head_dim, theta, factor, int(orig),
                            fast, slow)
        ang = pos.astype(F32)[..., None] * inv
        m = yarn_mscale(factor, ms) / yarn_mscale(factor, ms_all)
        cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
        if x.ndim == pos.ndim + 2:
            cos, sin = cos[..., None, :], sin[..., None, :]
        a, b = jnp.split(x.astype(F32), 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               -1).astype(x.dtype)

    @nn.compact
    def __call__(self, x, pos, paged=None, lanes=None):
        """``x`` is ``[B, L, d]`` at ``pos [B, L]``, or with ``lanes`` (a
        ``lane_pack.LaneMap``, paged path only) the tick's packed rows
        ``[R, d]``: everything but the paged kernel runs on what it is
        given, the latents go to the arena from their rows, the kernel
        sees the absorbed queries as ``[S, C, H, W]``."""
        d, H = self.hidden_size, self.num_heads
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        qr, kr, eps = self.q_lora_rank, self.kv_lora_rank, self.rms_norm_eps
        pd = self.param_dtype
        w_dq = self.param("w_dq", fan_in(d), (d, qr), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (qr,), pd)
        w_uq = self.param("w_uq", fan_in(qr), (qr, H * (dn + dr)), pd)
        w_dkv = self.param("w_dkv", fan_in(d), (d, kr + dr), pd)
        kv_norm = self.param("kv_norm", nn.initializers.ones, (kr,), pd)
        w_uk = self.param("w_uk", fan_in(kr), (kr, H, dn), pd)
        w_uv = self.param("w_uv", fan_in(kr), (kr, H, dv), pd)
        w_o = self.param("w_o", fan_in(H * dv), (H * dv, d), pd)
        scale = (dn + dr) ** -0.5 \
            * yarn_mscale(self.rope[1], self.rope[6]) ** 2
        mm = lambda a, w: matmul_f32(a, w).astype(self.dtype)
        ein = lambda spec, a, b: einsum_f32(spec, a, b)

        B, L = pos.shape
        lead = x.shape[:-1]                             # [B, L], or [R]
        at = pos if lanes is None else lanes.pack(pos)
        cq = rms_norm(mm(x, w_dq), q_norm, eps)
        q = mm(cq, w_uq).reshape(*lead, H, dn + dr)
        q_nope, q_rope = q[..., :dn], self._rotate(q[..., dn:], at)
        ckr = mm(x, w_dkv)
        ckv = rms_norm(ckr[..., :kr], kv_norm, eps)
        k_rope = self._rotate(ckr[..., kr:], at)        # one key, all heads

        if self.decode:
            if not self.slot_decode:
                raise ValueError("this model decodes through the block-"
                                 "paged slot path only (slot_decode=True)")
            NB, BS = self.kv_num_blocks, self.kv_block_size
            cache_ready = self.has_variable("cache", "cached_latent")
            # ONE head-less [NB, BS, W] leaf (ops/paged_cache.py): c_kv
            # (after the norm) and k_rope (after the rotation) side by
            # side, kr + dr values stored in whole 128-lane tiles.
            W = paged_cache.lane_tiles(kr + dr)
            cl = paged_cache.variable(self, "cached_latent", NB, BS,
                                      self.dtype, W)
            if cache_ready:
                need_host_state(paged)
                S, C = B, L
                table, n_new = paged["block_table"], paged["n_new"]
                cl.value = paged_cache.cow(cl.value, paged["cow_src"],
                                           paged["cow_dst"])
                flat = paged_cache.write_rows(table, pos, n_new, NB, BS)
                if lanes is not None:
                    # the latents are packed rows: so are their places in
                    # the arena (a dead row drops)
                    flat = lanes.pack(flat.reshape(S, C), fill=NB * BS)
                with device_span("kv_write"):
                    lat = jnp.concatenate(
                        [ckv, k_rope,
                         jnp.zeros(lead + (W - kr - dr,), self.dtype)], -1)
                cl.value = paged_cache.write(cl.value, flat, lat)
                with device_span("latent_attention"):
                    # absorbed: queries into the latent space, scores and
                    # the weighted sum against the cached latents, out
                    # through W_UV — the cache is never up-projected
                    qf = jnp.concatenate(
                        [ein("...hd,rhd->...hr", q_nope, w_uk).astype(
                            self.dtype), q_rope,
                         jnp.zeros(lead + (H, W - kr - dr), self.dtype)], -1)
                if lanes is not None:
                    qf = lanes.unpack(qf)               # [S, C, H, W]
                # scores, mask, softmax and weighted sum.  On the TPU one
                # Pallas call that walks each slot's live blocks where
                # they lie in the arena; on the CPU and under FORCE_XLA
                # the XLA form, which gathers every slot's [L, W] view
                # (kv_gather) and scores all L positions.  The op names
                # its own scopes (ops/attention.py).
                ol, walked = paged_latent_attention(
                    qf, cl.value, table, paged["fill"], n_new, scale=scale,
                    kr=kr)
                if lanes is not None:
                    ol = lanes.pack(ol)                 # [R, H, kr]
                with device_span("latent_attention"):
                    o = ein("...hr,rhd->...hd", ol, w_uv).astype(self.dtype)
                    return mm(o.reshape(*lead, H * dv), w_o), walked
            # init trace on the [B, max_len] dummy: the cache is allocated
            # above; fall through so that params and shapes initialize.
        with device_span("latent_attention"):
            # expanded: per-head keys and values from the latent
            k_nope = ein("blr,rhd->blhd", ckv, w_uk).astype(self.dtype)
            v = ein("blr,rhd->blhd", ckv, w_uv).astype(self.dtype)
            scores = (ein("bqhd,bkhd->bhqk", q_nope, k_nope)
                      + ein("bqhd,bkd->bhqk", q_rope, k_rope)) * scale
            keep = pos[:, None, :, None] >= pos[:, None, None, :]
            probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), -1)
            o = ein("bhqk,bkhd->bqhd", probs.astype(self.dtype),
                    v).astype(self.dtype)
            return mm(o.reshape(B, L, H * dv), w_o), None


def paged_gqa_step(module, q, k, v, pos, paged, num_blocks: int,
                   block_size: int, scale: float, window=None, lanes=None):
    """The paged K/V step of a grouped-query attention sublayer, called
    inside ``module``'s ``nn.compact`` method (the leaves lie at its path).
    ``q [S, C, Hq, hd]`` at ``pos [S, C]``; ``v`` this tick's value rows at
    the leaves' width, ``[S, C, Hk * hd]`` or with ``lanes`` (a
    ``lane_pack.LaneMap``) the packed ``[R, Hk * hd]``; ``k`` the key rows,
    in ``v``'s shape or with the head axis still apart.  ``window`` None: K
    and V live in block leaves ``[num_blocks, block_size, Hk * hd]`` under
    ``paged["block_table"]``, a shared block copied before it is written
    (COW).  ``window`` given: in window leaves ``[S * ring_blocks,
    block_size, Hk * hd]`` under ``paged["ring_table"]``, which share
    nothing.  Returns ``(None, None)`` from the init trace (the leaves are
    allocated here; the caller falls through to its plain forward so that
    params initialize), else ``(o [S, C, Hq, hd], walked [S])`` from
    ``ops.attention.paged_gqa_attention``, which names its own scope."""
    S, C = pos.shape
    names, width, ring = ("cached_key", "cached_value"), v.shape[-1], None
    if window is None:
        ready = module.has_variable("cache", names[0])
        ck, cv = (paged_cache.variable(module, n, num_blocks, block_size,
                                       v.dtype, width) for n in names)
    else:
        # the slots are the init trace's batch
        ready = paged_cache.has_window_variable(module, names[0], window)
        ck, cv = (paged_cache.window_variable(
            module, n, S, window, block_size, v.dtype, width) for n in names)
    if not ready:
        return None, None
    need_host_state(paged, ring=window is not None)
    fill, n_new = paged["fill"], paged["n_new"]
    if window is None:
        table = paged["block_table"]
        ck.value, cv.value = paged_cache.cow(
            (ck.value, cv.value), paged["cow_src"], paged["cow_dst"])
    else:
        table = paged["ring_table"]
        ring = table.shape[1]
    blocks = ck.value.shape[0]                          # the arena's
    flat = paged_cache.write_rows(table, pos, n_new, blocks, block_size,
                                  ring=window is not None)
    if lanes is not None:
        # the rows of k and v are the packed ones: so are their places in
        # the arena (a dead row drops)
        flat = lanes.pack(flat.reshape(S, C), fill=blocks * block_size)
    ck.value, cv.value = paged_cache.write(
        (ck.value, cv.value), flat, (k.reshape(v.shape), v))
    return paged_gqa_attention(q, ck.value, cv.value, table, fill, n_new,
                               scale=scale, window=window, ring=ring)


def causal_gqa_attention(q, k, v, pos, scale: float, window=None):
    """The plain forward of the same sublayer: ``q [B, L, Hq, hd]`` against
    this call's own ``k`` and ``v`` (``[B, L, Hk, hd]``, or the heads merged
    into the width); query head ``i`` reads key/value head ``i // (Hq /
    Hk)``; a position sees itself and what came before it, with ``window``
    no further back than ``window - 1``.  Softmax in float32; returns ``[B,
    L, Hq, hd]`` in ``q``'s dtype."""
    B, L, Hq, hd = q.shape
    with device_span("gqa_attention"):
        k, v = (t.reshape(B, L, -1, hd) for t in (k, v))
        Hk = k.shape[2]
        scores = einsum_f32("bqkgd,blkd->bkgql",
                            q.reshape(B, L, Hk, Hq // Hk, hd), k) * scale
        seen = pos[:, None, :] <= pos[:, :, None]              # [B, q, l]
        if window is not None:
            seen &= pos[:, None, :] > pos[:, :, None] - window
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None], scores, -1e30), -1)
        o = einsum_f32("bkgql,blkd->bqkgd", probs.astype(q.dtype), v)
        return o.astype(q.dtype).reshape(B, L, Hq, hd)
