"""LFM2-style decoder (``model_type: lfm2_moe``): gated short-convolution
layers with a grouped-query attention layer every few, a dense SwiGLU in the
leading layers and a sigmoid-routed dropless expert layer with no shared
expert in the rest.

``x_0 = E[ids]`` (no multiplier).  Per layer ``u = x + Op(N1(x))``, ``x' = u
+ FFN(N2(u))``, ``N`` an RMSNorm with a learned scale and float32
statistics; logits ``N_f(x_L) E^T`` (tied).  ``layer_types`` names each
layer's ``Op``:

*conv* (``Lfm2MoeShortConv``): ``[B, C, z] = h W_in`` (three chunks of
``d`` in that order); ``v = B * z``; ``c_t = sum_{k < K} w[k] * v_{t-K+1+k}``
(depthwise, causal, ``K = conv_L_cache`` taps, no bias, no activation, ``v``
before a sequence's start is 0); ``y = (C * c) W_out``.

*full_attention*: ``q = N_q(h W_q)``, ``k = N_k(h W_k)`` per head (RMSNorm
over ``head_dim``, one learned scale each shared by the heads), both then
rotated (rotate-half over all of ``head_dim`` at ``rope_theta``) on every
attention layer; ``v = h W_v``; query head ``i`` reads key/value head ``i //
(heads / kv heads)``; causal, scale ``head_dim ** -0.5``; ``y = concat_h(p
v) W_o``.  No bias, no gate.

*Expert FFN*: ``models/layers.RoutedExperts`` with ``n_shared = 0`` (sigmoid
scores, the ``k`` largest of score + selection bias, gates the chosen
experts' own scores over their sum, times ``routed_scaling_factor``).
``benchmarks/reference/lfm2.py`` holds the same equations in plain float32;
the configuration file lists what the published config leaves open
(``assumed``).

**Two kinds of cache** in the paged slot-decode path (``decode=True,
slot_decode=True``; the contract ``serve/slots.BlockPool`` and
``serve/engine.ServeEngine`` hold every served model to).  An attention
layer keeps K (after norm and rotation) and V in block leaves ``[num_blocks,
block_size, Hk * hd]`` addressed through the block table and read by
``ops.attention.paged_gqa_attention`` (at ``head_dim`` 64 a pair of heads a
lane tile), as ``models/granite_hybrid.py``'s does.  A conv layer keeps a
*per-slot* state, the last ``K - 1`` live rows of ``v`` (``[slots, (K - 1) *
d]`` in ``dtype``, ``paged_cache.slot_variable``): read once and written once
a tick in place, carried over the chunks of a chunked prefill and over decode
ticks, zeroed *inside the tick* where a slot starts a request (``fill == 0``
and ``n_new > 0``), kept bit for bit where ``n_new == 0``.  The convolution
is ``ops/ssd.causal_conv``, which ``granite_hybrid`` runs at ``K = 4``.  The
pool therefore shares no prefix and refuses speculation
(``per_slot_state``).

Counters: ``expert_load``, ``expert_weight_visits`` ``[expert layers, E]``,
``attn_positions_walked [attention layers, S]``, ``conv_slots_advanced [conv
layers, S]`` (1 where a slot's rows moved this tick), ``lanes_live [1, S]``.
Scopes: ``short_conv`` (a conv mixer whole), ``gqa_attention`` (projections,
head norms, rotation, ``W_o``), ``kv_write``, ``paged_gqa_attention``,
``dense_mlp``, ``moe_*``.

Weights, activations, the kept rows and K/V are ``dtype``/``param_dtype``
(bfloat16 as served); norm statistics, the rotation, the taps' sum, both
gates, the router, the softmax and the logits are float32.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax.numpy as jnp

from apex_example_tpu.models.layers import (RoutedExperts, SwiGLU,
                                            causal_gqa_attention, einsum_f32,
                                            fan_in, matmul_f32,
                                            need_host_state, paged_gqa_step,
                                            rms_norm, rotate_half)
from apex_example_tpu.obs.spans import device_span
from apex_example_tpu.ops import paged_cache, ssd

CONV, FULL = "conv", "full_attention"


class ShortConv(nn.Module):
    """One gated short convolution.  Returns ``(y, advanced)``: ``advanced
    [S]`` 1 where the paged path moved a slot's kept rows, None from the
    plain forward."""

    hidden_size: int
    taps: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False

    @nn.compact
    def __call__(self, h, paged=None):
        d, K, pd = self.hidden_size, self.taps, self.param_dtype
        w_in = self.param("in_proj", fan_in(d), (d, 3 * d), pd)
        # seeded at 1/sqrt(K) so that the taps' sum keeps v's scale
        conv_w = self.param("conv_w", fan_in(K), (K, d), pd)
        w_out = self.param("out_proj", fan_in(d), (d, d), pd)
        S, L = h.shape[:2]
        rows = reset = cv = None
        n_new = jnp.full((S,), L, jnp.int32)
        if self.decode:
            ready = paged_cache.has_slot_variable(self, "conv_rows")
            # the per-slot kind of cache (ops/paged_cache.py): row s is
            # slot s's, whatever blocks the attention layers map
            cv = paged_cache.slot_variable(self, "conv_rows", S,
                                           ((K - 1) * d,), self.dtype)
            if ready:
                need_host_state(paged)
                n_new = paged["n_new"]
                # a slot's first chunk starts its request: from zero,
                # whatever the slot's last request left
                reset = (paged["fill"] == 0) & (n_new > 0)
                rows = cv.value.reshape(S, K - 1, d)
            # init trace on the [B, max_len] dummy: the leaf is allocated
            # above; fall through so that params initialize
        carried = rows is not None
        if not carried:
            rows = jnp.zeros((S, K - 1, d), self.dtype)
        bcz = matmul_f32(h, w_in)                           # [S, L, 3d]
        v = (bcz[..., :d] * bcz[..., 2 * d:]).astype(self.dtype)
        c, rows = ssd.causal_conv(rows, v, conv_w, None, n_new, reset)
        if carried:
            cv.value = rows.reshape(S, (K - 1) * d)
        y = matmul_f32((bcz[..., d:2 * d] * c).astype(self.dtype),
                       w_out).astype(self.dtype)
        return y, (n_new > 0).astype(jnp.int32) if carried else None


class RotaryGQAttention(nn.Module):
    """One attention sublayer.  Returns ``(y, walked)``: ``walked [S]`` the
    cache positions the paged form read for each slot, None from the plain
    forward."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0

    @nn.compact
    def __call__(self, h, pos, paged=None):
        d, Hq, Hk, hd = (self.hidden_size, self.num_heads, self.num_kv_heads,
                         self.head_dim)
        pd, eps = self.param_dtype, self.norm_eps
        wq = self.param("wq", fan_in(d), (d, Hq * hd), pd)
        wk = self.param("wk", fan_in(d), (d, Hk * hd), pd)
        wv = self.param("wv", fan_in(d), (d, Hk * hd), pd)
        wo = self.param("wo", fan_in(Hq * hd), (Hq * hd, d), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (hd,), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (hd,), pd)
        mm = lambda a, w: matmul_f32(a, w).astype(self.dtype)
        B, L = pos.shape
        scale = hd ** -0.5

        with device_span("gqa_attention"):
            q = rotate_half(rms_norm(mm(h, wq).reshape(B, L, Hq, hd), q_norm,
                                     eps), pos, self.rope_theta)
            k = rotate_half(rms_norm(mm(h, wk).reshape(B, L, Hk, hd), k_norm,
                                     eps), pos, self.rope_theta)
            v = mm(h, wv)

        o = walked = None
        if self.decode:
            # nothing from the init trace, which allocates the leaves:
            # fall through so that params initialize
            o, walked = paged_gqa_step(
                self, q, k, v, pos, paged, self.kv_num_blocks,
                self.kv_block_size, scale)
        if o is None:
            o = causal_gqa_attention(q, k, v, pos, scale)
        with device_span("gqa_attention"):
            return mm(o.reshape(B, L, Hq * hd), wo), walked


class Lfm2Layer(nn.Module):
    """Mixer and feed-forward, each behind its RMSNorm.  ``cfg`` is the
    model's own field values.  Returns ``(x, (advanced, walked, load,
    visits))``: a conv layer's, an attention layer's and an expert layer's
    two counts on the paged path, None otherwise."""

    cfg: Tuple[Tuple[str, object], ...]
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, pos, paged, live):
        c = dict(self.cfg)
        d, eps = c["hidden_size"], c["norm_eps"]
        dtype, pd = c["dtype"], c["param_dtype"]
        norm = lambda name, t: rms_norm(
            t, self.param(name, nn.initializers.ones, (d,), pd), eps)

        h = norm("operator_norm", x)
        advanced = walked = load = visits = None
        if self.kind == CONV:
            with device_span("short_conv"):
                y, advanced = ShortConv(d, c["conv_L_cache"], dtype, pd,
                                        c["decode"], name="conv")(h, paged)
        else:
            y, walked = RotaryGQAttention(
                d, c["num_heads"], c["num_kv_heads"], c["head_dim"],
                float(c["rope_theta"]), eps, dtype, pd, c["decode"],
                c["kv_num_blocks"], c["kv_block_size"],
                name="attn")(h, pos, paged)
        u = x + y
        h = norm("ffn_norm", u)
        if self.dense:
            with device_span("dense_mlp"):
                y = SwiGLU(d, c["intermediate_size"], dtype, pd,
                           name="mlp")(h)
        else:
            E = c["num_experts"]
            y, load, visits = RoutedExperts(
                d, c["moe_intermediate_size"], E, c["num_experts_per_tok"],
                float(c["routed_scaling_factor"]), (0, E), dtype, pd,
                n_shared=0, name="moe")(h, live)
        return u + y, (advanced, walked, load, visits)


class Lfm2ForCausalLM(nn.Module):
    """Returns float32 logits: ``[B, L, V]`` from the plain forward,
    ``[SLOTS, 1, V]`` (each slot's sampled lane) from the paged one."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_layers: int = 24
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = (
        (CONV, CONV, FULL) + (CONV, CONV, CONV, FULL) * 4
        + (CONV, CONV, FULL, CONV, CONV))
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position: int = 128000
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

    # the paged head runs on the sampled lane only
    all_lane_logits = False

    def __post_init__(self):
        # (a configuration file gives a list; a module's fields are hashed:
        # serve/engine.py caches its step on the module)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        super().__post_init__()

    def layer_kinds(self) -> Tuple[str, ...]:
        """``layer_types`` of the published config."""
        kinds = self.layer_types
        if len(kinds) != self.num_layers or set(kinds) - {CONV, FULL}:
            raise ValueError(f"layer_types wants {self.num_layers} of "
                             f"{CONV!r} / {FULL!r}, got {kinds}")
        return kinds

    @nn.compact
    def __call__(self, input_ids, train: bool = True, paged=None):
        del train
        if self.kv_quant:
            raise ValueError(
                "kv_quant: the conv layers' kept rows carry a whole request "
                "in bfloat16 and the paged kernel reads bfloat16 pages; a "
                "quantized cache is not built for this model")
        if self.tensor_parallel:
            raise ValueError(
                "tensor_parallel: the per-slot rows' channels and the "
                "experts over a 'model' axis have no sharding rule yet; "
                "each layer is served whole on one chip")
        if self.decode and not self.slot_decode:
            raise ValueError("this model decodes through the block-paged "
                             "slot path only (slot_decode=True)")
        d = self.hidden_size
        cfg = tuple((f, getattr(self, f)) for f in self.__dataclass_fields__
                    if f not in ("parent", "name"))
        B, L = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        live = None
        if paged is not None:
            pos = paged["fill"][:, None] + pos
            live = jnp.arange(L)[None, :] < paged["n_new"][:, None]
        # seeded at 1/sqrt(d): N_f(x_L) E^T then has unit scale, and the
        # tied head does not echo the input token (the first norm rescales
        # x_0 whatever its size)
        embed = self.param("embed", fan_in(d), (self.vocab_size, d),
                           self.param_dtype)
        x = embed[input_ids].astype(self.dtype)
        names = ("conv_slots_advanced", "attn_positions_walked",
                 "expert_load", "expert_weight_visits")
        counted = tuple([] for _ in names)
        for i, kind in enumerate(self.layer_kinds()):
            x, counts = Lfm2Layer(cfg, kind, i < self.num_dense_layers,
                                  name=f"layer_{i}")(x, pos, paged, live)
            for rows, row in zip(counted, counts):
                if row is not None:
                    rows.append(row)
        keep = dict(reduce_fn=lambda _, new: new, init_fn=lambda: None)
        for name, rows in zip(names, counted):
            if rows:
                self.sow("counters", name, jnp.stack(rows), **keep)
        if paged is not None:
            self.sow("counters", "lanes_live", paged["n_new"][None, :],
                     **keep)
            # the head on each slot's sampled lane only
            lane = jnp.clip(paged["n_new"] - 1, 0, L - 1)
            x = jnp.take_along_axis(x, lane[:, None, None], axis=1)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones, (d,),
                                   self.param_dtype), self.norm_eps)
        return einsum_f32("bld,vd->blv", x, embed)


def lfm2_8b_a1b_cut(**kw) -> Lfm2ForCausalLM:
    """LiquidAI/LFM2-8B-A1B at its published widths, all 32 experts and the
    whole vocabulary, cut in depth to one stage of a pipeline of one-chip
    stages: one leading dense layer (published layer 0, conv), then three
    whole periods of expert layers (full_attention, conv, conv, conv:
    published layers 2-13) (benchmarks/configs/lfm2_8b_a1b.json)."""
    for k, v in dict(num_layers=13, num_dense_layers=1,
                     layer_types=(CONV,) + (FULL, CONV, CONV, CONV) * 3
                     ).items():
        kw.setdefault(k, v)
    return Lfm2ForCausalLM(**kw)


def lfm2_tiny(**kw) -> Lfm2ForCausalLM:
    """Test-scale configuration (same code path, CPU-friendly, float32):
    heads of 64, so that the paged kernel pairs them under the
    interpreter as at the published widths."""
    for k, v in dict(vocab_size=256, hidden_size=128, num_layers=5,
                     num_dense_layers=1,
                     layer_types=(CONV, FULL, CONV, CONV, CONV),
                     num_heads=4, num_kv_heads=2, head_dim=64,
                     intermediate_size=256, moe_intermediate_size=128,
                     num_experts=8, num_experts_per_tok=4,
                     max_position=4096, dtype=jnp.float32,
                     param_dtype=jnp.float32).items():
        kw.setdefault(k, v)
    return Lfm2ForCausalLM(**kw)
