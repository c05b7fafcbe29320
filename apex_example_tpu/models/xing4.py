"""Xing4.0-style decoder (``model_type: xing4_0``): the current-generation
block beside :class:`~apex_example_tpu.models.bert.BertLayer`'s post-LN one.

Per layer, two sublayers — latent attention (MLA) and a feed-forward that
is a dense SwiGLU in the leading layers and a routed expert layer with one
shared expert in the rest — each behind its own RMSNorm and each wrapped in
a *hyper-connection* unit: the residual is ``hc_mult`` streams, a sublayer
reads a data-dependent mixture of them and its output is written back
through data-dependent weights while the streams themselves are mixed by a
matrix that Sinkhorn iterations project onto the doubly stochastic ones.
``benchmarks/reference/xing4.py`` holds the same equations in plain
float32; the configuration file lists what the published config leaves
open (``assumed``).

Latent attention has two forms of the same mathematics.  The plain forward
(training-shaped, no cache) *expands* the latent ``c_kv`` into per-head
keys and values.  The paged slot-decode path (``decode=True,
slot_decode=True``; the contract ``serve/slots.BlockPool`` and
``serve/engine.ServeEngine`` hold every served model to) caches
``c_kv ⊕ k_rope`` — ``kv_lora_rank + qk_rope_head_dim`` values a token a
layer, after the norm and after the rotation, in ONE head-less
``[num_blocks, block_size, W]`` arena leaf (``W`` those 576 values rounded
up to whole 128-lane tiles, 640: what the tiled layout occupies anyway) —
and attends in the *absorbed*
form: queries are carried into the latent space (``q_nope W_UK^T``), scores
and the weighted sum run against the cached latents themselves, and the
result is carried out through ``W_UV``.  The cache is never up-projected.
The leaf, its copy-on-write, write and gather are ops/paged_cache.py's, as
models/bert.py's are (one layout, the cache donated: updated in place).

Scores, mask, softmax and weighted sum of the paged path are one op,
``ops.attention.paged_latent_attention``, in two forms chosen by the
backend as every kernel here is.  On the TPU (and under the interpreter,
which the tests run) a Pallas kernel walks each slot's live blocks where
they lie in the arena — ``ceil((fill + n_new) / block)`` of them, none for
a dead slot, a decode slot's one live lane in one row tile — with an online
softmax in float32 scratch, and no ``[S, L, W]`` view or ``[S, H, C, L]``
score tensor exists.  On the CPU and under ``FORCE_XLA`` the XLA form
gathers every slot's whole row of the block table (``kv_gather``) and
scores all ``L`` positions: the same function, the tests' golden.  The
model sows what either read, ``attn_positions_walked [layers, S]``, beside
``expert_load`` in the ``counters`` collection, and beside
``expert_weight_visits [layers, E]`` where the expert layers' grouped
products ran in their kernel (``ops/grouped_matmul.py``; the XLA form,
``lax.ragged_dot``, counts nothing).

**Packed lanes** (``packed_lanes = True``; ``ops/lane_pack.py``, PR 44).  The
paged program carries the tick's *live* lanes as dense rows ``[R, n, d]``,
``R = lane_pack.rows(SLOTS, C)`` static (256 of a ``64 x 16`` tick): ONE
``LaneMap`` a call, built from ``n_new``; the token ids are packed before
the embedding, and the hyper-connection units, norms, projections, the
rotation, the absorb and un-absorb products, ``w_o``, the dense and shared
SwiGLUs, the router, dispatch and combine all run on rows (``RoutedExperts``
takes the map's ``row_live`` as its ``live``).  The latents go to the arena
straight from their rows (their ``[S, C]`` destinations packed with them, a
dead row's dropping); only the paged kernel sees ``[SLOTS, C, H, W]``, the
absorbed queries unpacked in front of it and its output packed behind it.
Dead rows hold zeros, are selected away and never multiplied in.  The
engine budgets prefill chunks to the rows' groups; ``lanes_live [1, S]`` and
``rows_dense [1, 1]`` in ``counters`` say how full the rows were.  The plain
forward and the init trace know nothing of it; a subclass with
``packed_lanes = False`` (the tests') is the ``[SLOTS, C]`` program.

In the paged path the vocabulary head runs on each slot's *sampled lane*
only (``[SLOTS, 1, V]`` logits): at a vocabulary of 131072 the all-lane
head would be half a gigabyte of logits a tick for 64 used rows.  The
engine therefore refuses speculation for this model
(``all_lane_logits = False``); ``kv_quant`` and tensor parallelism are
refused too — a head-less latent leaf has no head axis to shard and no
per-head scale table (ROADMAP).

Weights and activations are ``dtype``/``param_dtype`` (bfloat16 as
served); RMSNorm statistics, the rotation, the hyper-connection
coefficients and Sinkhorn, the router, the softmax and the logits are
float32.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_example_tpu.obs.spans import device_span
from apex_example_tpu.ops import lane_pack, paged_cache
from apex_example_tpu.ops.attention import paged_latent_attention
from apex_example_tpu.transformer.expert_parallel import (dropless_experts,
                                                          dropless_route,
                                                          expert_load)

F32 = jnp.float32


def _fan_in(fan_in: int):
    return nn.initializers.normal(1.0 / math.sqrt(fan_in))


def matmul_f32(a, b):
    """``a @ b`` accumulated and returned in float32 (on the TPU the MXU
    multiplies bfloat16 operands exactly and adds in float32)."""
    return jnp.matmul(a, b, preferred_element_type=F32)


def einsum_f32(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def rms_norm(x, scale, eps):
    """RMSNorm with float32 statistics; ``scale`` None = no learned scale."""
    y = x.astype(F32)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    if scale is not None:
        y = y * scale.astype(F32)
    return y.astype(x.dtype)


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


def sinkhorn(logits, iters: int, eps: float, lo: float, hi: float):
    """``exp`` of the clamped logits, then ``iters`` times rows then
    columns divided by their sums (float32)."""
    m = jnp.exp(jnp.clip(logits.astype(F32), lo, hi))
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


class HyperConnection(nn.Module):
    """One hyper-connection unit: ``mix_in`` gives the sublayer's input and
    the coefficients, ``mix_out`` the streams after the sublayer."""

    n: int
    hidden_size: int
    rms_norm_eps: float
    sinkhorn_iters: int
    hc_eps: float
    clamp: Tuple[float, float]
    param_dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        n, nd = self.n, self.n * self.hidden_size
        w = lambda name, cols: self.param(name, _fan_in(nd), (nd, cols),
                                          self.param_dtype)
        self.w_pre, self.w_post = w("w_pre", n), w("w_post", n)
        self.w_res = w("w_res", n * n)
        const = lambda v: (lambda key, shape, dtype: jnp.broadcast_to(
            jnp.asarray(v, dtype), shape))
        self.alpha = self.param("alpha", const(0.01), (3,), F32)
        # H_pre = 1/n and H_post = 1 at a zero input; H_res near identity
        self.b_pre = self.param("b_pre", const(-math.log(n - 1.0)), (n,), F32)
        self.b_post = self.param("b_post", const(0.0), (n,), F32)
        self.b_res = self.param("b_res", const(8.0 * jnp.eye(n)), (n, n), F32)

    def mix_in(self, X):
        """X [.., n, d] -> u [.., d], (h_res [.., n, n], h_post [.., n])"""
        with device_span("hc_mix"):
            n = self.n
            xt = rms_norm(X.reshape(*X.shape[:-2], -1).astype(F32), None,
                          self.rms_norm_eps)
            proj = lambda w: jnp.matmul(xt, w.astype(F32),
                                        precision=jax.lax.Precision.HIGHEST)
            h_pre = jax.nn.sigmoid(self.alpha[0] * proj(self.w_pre)
                                   + self.b_pre)
            h_post = 2.0 * jax.nn.sigmoid(self.alpha[1] * proj(self.w_post)
                                          + self.b_post)
            res = self.alpha[2] * proj(self.w_res)
            h_res = sinkhorn(res.reshape(*res.shape[:-1], n, n) + self.b_res,
                             self.sinkhorn_iters, self.hc_eps, *self.clamp)
            u = sum(h_pre[..., j, None] * X[..., j, :].astype(F32)
                    for j in range(n))
            return u.astype(X.dtype), (h_res, h_post)

    def mix_out(self, X, y, coeff):
        """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` (as broadcast
        multiply-adds: a batch of 4x4 products would waste the MXU)."""
        with device_span("hc_mix"):
            h_res, h_post = coeff
            Xf, yf = X.astype(F32), y.astype(F32)
            out = [sum(h_res[..., i, j, None] * Xf[..., j, :]
                       for j in range(self.n)) + h_post[..., i, None] * yf
                   for i in range(self.n)]
            return jnp.stack(out, axis=-2).astype(X.dtype)


class SwiGLU(nn.Module):
    hidden_size: int
    width: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d, f = self.hidden_size, self.width
        w_gate = self.param("w_gate", _fan_in(d), (d, f), self.param_dtype)
        w_up = self.param("w_up", _fan_in(d), (d, f), self.param_dtype)
        w_down = self.param("w_down", _fan_in(f), (f, d), self.param_dtype)
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
        router = self.param("router", _fan_in(d), (d, E), F32)
        bias = self.param("router_bias", nn.initializers.zeros, (E,), F32)
        w_gate = self.param("w_gate", _fan_in(d), (count, d, f),
                            self.param_dtype)
        w_up = self.param("w_up", _fan_in(d), (count, d, f),
                          self.param_dtype)
        w_down = self.param("w_down", _fan_in(f), (count, f, d),
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
    """MLA; see the module docstring for the two forms.  Returns ``(y,
    walked)``: ``walked [S]`` the cache positions the paged form read for
    each slot this call, None from the plain forward."""

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
        w_dq = self.param("w_dq", _fan_in(d), (d, qr), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (qr,), pd)
        w_uq = self.param("w_uq", _fan_in(qr), (qr, H * (dn + dr)), pd)
        w_dkv = self.param("w_dkv", _fan_in(d), (d, kr + dr), pd)
        kv_norm = self.param("kv_norm", nn.initializers.ones, (kr,), pd)
        w_uk = self.param("w_uk", _fan_in(kr), (kr, H, dn), pd)
        w_uv = self.param("w_uv", _fan_in(kr), (kr, H, dv), pd)
        w_o = self.param("w_o", _fan_in(H * dv), (H * dv, d), pd)
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
                if paged is None:
                    raise ValueError(
                        "paged slot decode needs the host state: pass "
                        "paged={'block_table', 'fill', 'n_new', 'cow_src', "
                        "'cow_dst'} (serve/engine.py builds it each tick)")
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


class Xing4Layer(nn.Module):
    """Attention and feed-forward, each behind its RMSNorm and inside its
    hyper-connection unit.  ``cfg`` is the model's own field values."""

    cfg: Tuple[Tuple[str, object], ...]
    dense: bool

    @nn.compact
    def __call__(self, X, pos, paged, live, lanes=None):
        """``X [B, L, n, d]``, or with ``lanes`` the tick's packed rows
        ``[R, n, d]`` (``live`` then ``[R]``); ``pos [B, L]`` either way."""
        c = dict(self.cfg)
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        dtype, pd = c["dtype"], c["param_dtype"]
        norm = lambda name: self.param(name, nn.initializers.ones, (d,), pd)
        hyper = lambda name: HyperConnection(
            c["hc_mult"], d, eps, c["hc_sinkhorn_iters"], c["hc_eps"],
            (float(c["hc_clamp_min"]), float(c["hc_clamp_max"])), pd,
            name=name)
        rope = tuple(float(c["rope_" + k]) for k in (
            "theta", "factor", "original_max_position", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim"))
        hc = hyper("attn_hc")
        u, coeff = hc.mix_in(X)
        y, walked = LatentAttention(
            d, c["num_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["q_lora_rank"], c["kv_lora_rank"], eps, rope,
            dtype, pd, c["decode"], c["slot_decode"], c["kv_num_blocks"],
            c["kv_block_size"], name="attn")(
                rms_norm(u, norm("attn_norm"), eps), pos, paged, lanes)
        X = hc.mix_out(X, y, coeff)
        hc = hyper("ffn_hc")
        u, coeff = hc.mix_in(X)
        u = rms_norm(u, norm("ffn_norm"), eps)
        load = visits = None
        if self.dense:
            y = SwiGLU(d, c["intermediate_size"], dtype, pd, name="mlp")(u)
        else:
            E = c["n_routed_experts"]
            y, load, visits = RoutedExperts(
                d, c["moe_intermediate_size"], E, c["num_experts_per_tok"],
                float(c["routed_scaling_factor"]),
                tuple(c["experts_held"] or (0, E)), dtype, pd,
                name="moe")(u, live)
        return hc.mix_out(X, y, coeff), load, visits, walked


class Xing4ForCausalLM(nn.Module):
    """Returns float32 logits: ``[B, L, V]`` from the plain forward,
    ``[SLOTS, 1, V]`` (each slot's sampled lane) from the paged one."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    first_k_dense: int = 2
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position: int = 262144
    # the routed experts held here, (first, count); None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    # the serving contract (serve/slots.BlockPool clones with these)
    tensor_parallel: bool = False
    fused_attention: bool = False
    decode: bool = False
    slot_decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0
    kv_quant: bool = False

    # the paged head runs on the sampled lane only, so the engine cannot
    # verify draft lanes against this model (serve/engine.py)
    all_lane_logits = False
    # the paged program's token-wise sublayers take the tick's live lanes
    # as lane_pack.rows(SLOTS, C) dense rows: the engine budgets to that
    packed_lanes = True

    @nn.compact
    def __call__(self, input_ids, train: bool = True, paged=None):
        del train
        if self.kv_quant or self.tensor_parallel:
            raise ValueError(
                "the latent cache leaf is head-less: kv_quant (per-head "
                "scale tables) and tensor parallelism (a head axis to "
                "shard) are not built for it (ROADMAP)")
        d = self.hidden_size
        cfg = tuple((f, getattr(self, f)) for f in self.__dataclass_fields__
                    if f not in ("parent", "name"))
        B, L = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        live = lanes = None
        if paged is not None:
            # positions come from the host's per-slot fill levels; rotary
            # positions need no table, so nothing clips
            pos = paged["fill"][:, None] + pos
            live = jnp.arange(L)[None, :] < paged["n_new"][:, None]
            if self.packed_lanes:
                # ONE map a call: every layer packs and unpacks through it
                lanes = lane_pack.LaneMap(paged["n_new"], L)
                input_ids, live = lanes.pack(input_ids), lanes.row_live
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (self.vocab_size, d), self.param_dtype)
        x = embed[input_ids].astype(self.dtype)
        if lanes is not None:
            # a dead row holds zeros from here on (lane_pack's promise)
            x = jnp.where(live[:, None], x, 0)
        X = jnp.repeat(x[..., None, :], self.hc_mult, axis=-2)  # [..,n,d]
        loads, visits, walks = [], [], []
        for i in range(self.num_layers):
            X, load, visited, walked = Xing4Layer(
                cfg, i < self.first_k_dense, name=f"layer_{i}")(
                    X, pos, paged, live, lanes)
            for rows, row in ((loads, load), (visits, visited),
                              (walks, walked)):
                if row is not None:
                    rows.append(row)
        # what the layers counted this call — live lanes routed to each
        # expert of each expert layer [expert layers, E]; row tiles the
        # grouped kernel visited for each expert, one fetch of its weights
        # a product [expert layers, E] (nothing from the XLA form); cache
        # positions paged attention read for each slot [layers, S] — read
        # by the engine when the "counters" collection is mutable, dropped
        # otherwise
        keep = dict(reduce_fn=lambda _, new: new, init_fn=lambda: None)
        for name, rows in (("expert_load", loads),
                           ("expert_weight_visits", visits),
                           ("attn_positions_walked", walks)):
            if rows:
                self.sow("counters", name, jnp.stack(rows), **keep)
        if paged is not None:
            # how full the rows of the token-wise products were this tick
            self.sow("counters", "lanes_live", paged["n_new"][None, :],
                     **keep)
            self.sow("counters", "rows_dense", jnp.full(
                (1, 1), x.size // d, jnp.int32), **keep)
            # the head on each slot's sampled lane only
            if lanes is not None:
                X = lanes.last(X)[:, None]
            else:
                lane = jnp.clip(paged["n_new"] - 1, 0, L - 1)
                X = jnp.take_along_axis(X, lane[:, None, None, None],
                                        axis=1)
        x = jnp.sum(X.astype(F32), axis=2).astype(self.dtype)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones, (d,),
                                   self.param_dtype), self.rms_norm_eps)
        head = self.param("head", _fan_in(d), (d, self.vocab_size),
                          self.param_dtype)
        return matmul_f32(x, head)


def xing4_29b_a4b_cut(**kw) -> Xing4ForCausalLM:
    """The published widths, every expert and the whole vocabulary, cut in
    depth to one stage of a pipeline of one-chip stages: the first leading
    dense layer and five expert layers (benchmarks/configs/
    xing4_29b_a4b.json)."""
    kw.setdefault("num_layers", 6)
    kw.setdefault("first_k_dense", 1)
    return Xing4ForCausalLM(**kw)


def xing4_tiny(**kw) -> Xing4ForCausalLM:
    """Test-scale configuration (same code path, CPU-friendly, float32)."""
    for k, v in dict(vocab_size=256, hidden_size=64, num_layers=3,
                     first_k_dense=1, num_heads=4, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24,
                     kv_lora_rank=32, intermediate_size=128,
                     moe_intermediate_size=32, n_routed_experts=8,
                     num_experts_per_tok=2, max_position=4096,
                     dtype=jnp.float32, param_dtype=jnp.float32).items():
        kw.setdefault(k, v)
    return Xing4ForCausalLM(**kw)
