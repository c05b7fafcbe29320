"""Granite 4.0-H style hybrid decoder (``model_type: granitemoehybrid``
with no experts): Mamba-2 state-space layers with a GQA attention layer
every few, a shared SwiGLU MLP in every layer, no positional encoding, and
Granite's four multipliers.

Per layer ``u = x + r Mixer(RMSNorm(x))``, ``x' = u + r MLP(RMSNorm(u))``
(``r = residual_multiplier``); ``x_0 = embedding_multiplier E[ids]``; logits
``RMSNorm(x_L) E^T / logits_scaling`` (tied).  ``benchmarks/reference/
granite_hybrid.py`` holds the same equations in plain float32, token by
token.

**The mixers.**  *Mamba-2*: ``[z, xBC, dt] = h W_in``; a depthwise causal
convolution of width 4 and a SiLU over ``xBC``, split into ``x`` (``H``
heads of ``P``), ``B`` and ``C`` (``N`` each, shared by the heads);
``dt = softplus(dt + dt_bias)``; the recurrence ``S_t = exp(-dt_t e^{A_log})
S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` in its chunked form
(``ops/ssd.py``); ``RMSNorm_w(y silu(z))`` over all channels; ``W_out``.
*Attention*: GQA (query head ``i`` reads K/V head ``i // group``), no bias,
no rotary or learned position, scores scaled by ``attention_multiplier``.
On the paged path the scores, mask, softmax and weighted sum are
``ops.attention.paged_gqa_attention`` (PR 41): on the TPU, and under the
tests' interpreter, a Pallas kernel that walks each slot's live blocks where
they lie in the arenas (two heads of 64 a lane tile); on a plain CPU drive
and under ``FORCE_XLA`` the op's XLA form, which gathers every row of the
table.  The plain forward keeps its einsums.

**Two kinds of cache** in the paged slot-decode path (``decode=True,
slot_decode=True``; the contract ``serve/slots.BlockPool`` and
``serve/engine.ServeEngine`` hold every served model to).  An attention
layer keeps K and V in block-paged arena leaves ``[num_blocks, block_size,
kv_heads * head_dim]`` through ``ops/paged_cache.py``, as models/bert.py
does.  A Mamba layer keeps a *per-slot* state — ``[slots, H, P, N]``
float32 and the last ``d_conv - 1`` live ``xBC`` rows, bfloat16 — declared
per-slot where it is created (``paged_cache.slot_variable``), read once and
written once a tick in place (the cache is donated), carried over the
chunks of a chunked prefill and over decode ticks, and zeroed *inside the
tick* where a slot starts a request (``fill == 0`` and ``n_new > 0``): the
host zeroes nothing.  A slot with ``n_new == 0`` keeps state and rows bit
for bit.

**Packed lanes** (``packed_lanes = True``; ``ops/lane_pack.py``).  The
paged path's residual stream is not ``[SLOTS, C, d]`` but the tick's live
lanes as dense rows ``[R, d]``, ``R = lane_pack.rows(SLOTS, C)`` static: the
embedding, every norm and residual add, ``W_in``, the gate with its norm,
``W_out``, the MLP, ``wq/wk/wv/wo`` and the head's pick are token-wise and
run on ``R`` rows; the convolution with the scan and the attention over a
slot's paged K/V see ``[SLOTS, C, ...]`` through the map's ``unpack`` and
hand back through ``pack`` (under the device span ``lane_pack``, outside
``ssm_scan`` and ``shared_mlp``).  K and V are written to the arena from their packed
rows.  The engine reads the attribute and grants no more multi-lane chunks
a tick than the rows hold.  A subclass with ``packed_lanes = False`` runs
the same layers over ``[SLOTS, C, d]`` (the tests' other side).

The paged head runs on each slot's sampled lane only (``[SLOTS, 1, V]``
float32 logits; ``all_lane_logits = False``).  Refused, with the reason:
speculation (by the pool: a state advanced over rejected lanes cannot be
rolled back), ``kv_quant`` and ``tensor_parallel`` (below).

Weights and activations are ``dtype``/``param_dtype`` (bfloat16 as
served); the state and its recurrence, softplus, ``exp``, norm statistics,
softmax and logits are float32; convolution rows and K/V are ``dtype``.
The model sows ``ssm_slots_advanced [mamba layers, slots]`` (1 where a
slot's state moved this tick), ``ssm_state_visits [mamba layers, slots]``
(1 where the scan's kernel fetched and wrote a slot's state, ``ops/ssd.py``;
nothing where the XLA form ran, which reads every slot's),
``attn_positions_walked [attention layers, slots]`` (the cache positions
attention read for each slot: the kernel's live blocks times the block
size, the whole table row from the XLA form), ``lanes_live [1, slots]``
(``n_new``) and ``rows_dense [1, 1]`` (the rows its token-wise products ran
on: ``R`` packed, ``SLOTS * C`` not) into the ``counters`` collection.
"""

from __future__ import annotations

import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_example_tpu.models.layers import (F32, causal_gqa_attention,
                                            einsum_f32, fan_in, matmul_f32,
                                            need_host_state, paged_gqa_step,
                                            rms_norm_f32)
from apex_example_tpu.obs.spans import device_span
from apex_example_tpu.ops import lane_pack, paged_cache, ssd


def _dt_bias_init(lo: float = 1e-3, hi: float = 1e-1):
    """The inverse softplus of a step drawn log-uniform on [lo, hi]."""
    def init(key, shape, dtype):
        step = jnp.exp(jax.random.uniform(key, shape, F32, math.log(lo),
                                          math.log(hi)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return init


def _uniform(bound: float):
    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, F32, -bound, bound).astype(
            dtype)
    return init


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)).astype(
        dtype)


class MambaMixer(nn.Module):
    """Returns ``(y, advanced, visits)``: ``advanced [S]`` 1 where the
    paged path moved a slot's state, ``visits [S]`` 1 where the scan's
    kernel fetched and wrote it; None from the plain forward (and ``visits``
    from the scan's XLA form).  ``h`` is ``[S, L, d]``, or with ``lanes`` (a
    ``lane_pack.LaneMap``) its packed rows ``[R, d]``: the projections, the
    gate and its norm run on what they are given, the convolution and the
    scan on ``[S, L, ...]``."""

    hidden_size: int
    n_heads: int
    d_head: int
    d_state: int
    d_conv: int
    chunk_size: int
    rms_norm_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False

    @nn.compact
    def __call__(self, h, paged=None, lanes=None):
        d, H, P, N, K = (self.hidden_size, self.n_heads, self.d_head,
                         self.d_state, self.d_conv)
        di, ch = H * P, H * P + 2 * N
        pd = self.param_dtype
        w_in = self.param("in_proj", fan_in(d), (d, di + ch + H), pd)
        conv_w = self.param("conv_w", _uniform(1 / math.sqrt(K)), (K, ch), pd)
        conv_b = self.param("conv_b", nn.initializers.zeros, (ch,), pd)
        dt_bias = self.param("dt_bias", _dt_bias_init(), (H,), F32)
        a_log = self.param("A_log", _a_log_init, (H,), F32)
        D = self.param("D", nn.initializers.ones, (H,), F32)
        norm = self.param("norm", nn.initializers.ones, (di,), pd)
        w_out = self.param("out_proj", fan_in(di), (di, d), pd)

        S, L = h.shape[:2] if lanes is None else (lanes.slots, lanes.chunk)
        state = rows = reset = n_new = None
        carried = False
        if self.decode:
            ready = paged_cache.has_slot_variable(self, "ssm_state")
            # the per-slot kind of cache (ops/paged_cache.py): row s is
            # slot s's, whatever blocks its attention layers map
            sv = paged_cache.slot_variable(self, "ssm_state", S, (H, P, N),
                                           F32)
            cv = paged_cache.slot_variable(self, "conv_rows", S,
                                           ((K - 1) * ch,), self.dtype)
            if ready:
                need_host_state(paged)
                n_new = paged["n_new"]
                # a slot's first chunk starts its request: from zero,
                # whatever the slot's last request left
                reset = (paged["fill"] == 0) & (n_new > 0)
                state, rows = sv.value, cv.value.reshape(S, K - 1, ch)
                carried = True
            # init trace on the [B, max_len] dummy: the leaves are
            # allocated above; fall through so that params initialize
        if state is None:
            state = jnp.zeros((S, H, P, N), F32)
            rows = jnp.zeros((S, K - 1, ch), self.dtype)
            n_new = jnp.full((S,), L, jnp.int32)
        live = jnp.arange(L)[None, :] < n_new[:, None]

        zxd = matmul_f32(h, w_in)                     # [.., di + ch + H]
        z = zxd[..., :di].astype(self.dtype)
        xbc = zxd[..., di:di + ch].astype(self.dtype)
        dt = zxd[..., di + ch:]
        if lanes is not None:
            xbc, dt = lanes.unpack(xbc), lanes.unpack(dt)
        with device_span("ssm_scan"):
            dt = jax.nn.softplus(dt + dt_bias)
            xbc, rows = ssd.causal_conv(rows, xbc, conv_w, conv_b, n_new,
                                        reset)
            xbc = jax.nn.silu(xbc)
            y, state, visits = ssd.ssd_scan_counted(
                state, xbc[..., :di].reshape(S, L, H, P), dt, a_log,
                xbc[..., di:di + N], xbc[..., di + N:], D, live,
                chunk=self.chunk_size, reset=reset)
            if carried:
                sv.value = state
                cv.value = rows.reshape(S, (K - 1) * ch)
        y = y.reshape(S, L, di)
        if lanes is not None:
            y = lanes.pack(y)
        y = y * jax.nn.silu(z.astype(F32))
        y = rms_norm_f32(y, norm, self.rms_norm_eps).astype(self.dtype)
        out = matmul_f32(y, w_out).astype(self.dtype)
        if not carried:
            return out, None, None
        return out, (n_new > 0).astype(jnp.int32), visits


class GQAttention(nn.Module):
    """Returns ``(y, walked)``: ``walked [S]`` the cache positions the
    paged form read for each slot (``ops.attention.paged_gqa_attention``),
    None from the plain forward."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    scale: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0

    @nn.compact
    def __call__(self, h, pos, paged=None, lanes=None):
        """``h`` is ``[S, L, d]``, or with ``lanes`` its packed rows ``[R,
        d]``: the four projections run on what they are given, K and V go
        to the arena from their rows, the scores see ``[S, L, ...]``."""
        d, Hq, Hk, hd = (self.hidden_size, self.num_heads, self.num_kv_heads,
                         self.head_dim)
        pd = self.param_dtype
        wq = self.param("wq", fan_in(d), (d, Hq * hd), pd)
        wk = self.param("wk", fan_in(d), (d, Hk * hd), pd)
        wv = self.param("wv", fan_in(d), (d, Hk * hd), pd)
        wo = self.param("wo", fan_in(Hq * hd), (Hq * hd, d), pd)
        mm = lambda a, w: matmul_f32(a, w).astype(self.dtype)
        S, L = pos.shape
        q, k, v = mm(h, wq), mm(h, wk), mm(h, wv)          # [.., Hk * hd]
        if lanes is not None:
            q = lanes.unpack(q)
        q = q.reshape(S, L, Hq, hd)
        o = walked = None
        if self.decode:
            # K and V to the arena from their packed rows, scores over the
            # slot's blocks; nothing from the init trace, which allocates
            # the leaves: fall through so that params initialize
            o, walked = paged_gqa_step(
                self, q, k, v, pos, paged, self.kv_num_blocks,
                self.kv_block_size, self.scale, lanes=lanes)
        if o is None:
            o = causal_gqa_attention(q, k, v, pos, self.scale)
        o = o.reshape(S, L, Hq * hd)
        if lanes is not None:
            o = lanes.pack(o)
        with device_span("gqa_attention"):
            return mm(o, wo), walked


class SharedMLP(nn.Module):
    hidden_size: int
    width: int
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        d, f = self.hidden_size, self.width
        w_in = self.param("w_in", fan_in(d), (d, 2 * f), self.param_dtype)
        w_out = self.param("w_out", fan_in(f), (f, d), self.param_dtype)
        with device_span("shared_mlp"):
            gv = matmul_f32(h, w_in)                       # gate half first
            a = (jax.nn.silu(gv[..., :f]) * gv[..., f:]).astype(self.dtype)
            return matmul_f32(a, w_out).astype(self.dtype)


class GraniteHybridLayer(nn.Module):
    """One layer; ``cfg`` is the model's own field values.  Returns ``(x,
    (advanced, visits, walked))``: the first two a Mamba layer's, the
    third an attention layer's on the paged path, None otherwise."""

    cfg: Tuple[Tuple[str, object], ...]
    kind: str

    @nn.compact
    def __call__(self, x, pos, paged, lanes=None):
        c = dict(self.cfg)
        d, eps, r = c["hidden_size"], c["rms_norm_eps"], \
            c["residual_multiplier"]
        dtype, pd = c["dtype"], c["param_dtype"]
        norm = lambda name, t: rms_norm_f32(
            t, self.param(name, nn.initializers.ones, (d,), pd),
            eps).astype(dtype)
        h, counts, walked = norm("norm1", x), (None, None), None
        if self.kind == "mamba":
            with device_span("ssm_mixer"):
                y, *counts = MambaMixer(
                    d, c["mamba_n_heads"], c["mamba_d_head"],
                    c["mamba_d_state"], c["mamba_d_conv"],
                    c["mamba_chunk_size"], eps, dtype, pd, c["decode"],
                    name="mixer")(h, paged, lanes)
        else:
            y, walked = GQAttention(
                d, c["num_heads"], c["num_kv_heads"], c["head_dim"],
                c["attention_multiplier"], dtype, pd, c["decode"],
                c["kv_num_blocks"], c["kv_block_size"],
                name="mixer")(h, pos, paged, lanes)
        x = (x.astype(F32) + r * y.astype(F32)).astype(dtype)
        y = SharedMLP(d, c["intermediate_size"], dtype, pd,
                      name="mlp")(norm("norm2", x))
        return (x.astype(F32) + r * y.astype(F32)).astype(dtype), \
            (*counts, walked)


class GraniteHybridForCausalLM(nn.Module):
    """Returns float32 logits: ``[B, L, V]`` from the plain forward,
    ``[SLOTS, 1, V]`` (each slot's sampled lane) from the paged one."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    num_layers: int = 40
    # every ``attention_period``-th layer is attention, the one at
    # ``attention_offset`` in each period; the rest are Mamba-2
    attention_period: int = 10
    attention_offset: int = 5
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position: int = 131072      # no position table: the context served
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
    # the paged program's token-wise sublayers take the tick's live lanes
    # as lane_pack.rows(SLOTS, C) dense rows: the engine budgets to that
    packed_lanes = True

    def layer_kinds(self) -> Tuple[str, ...]:
        """``layer_types`` of the published config."""
        return tuple("attention" if i % self.attention_period
                     == self.attention_offset else "mamba"
                     for i in range(self.num_layers))

    @nn.compact
    def __call__(self, input_ids, train: bool = True, paged=None):
        del train
        if self.kv_quant:
            raise ValueError(
                "kv_quant: the Mamba layers' per-slot state is float32 by "
                "design (it is carried for a whole request) and the four "
                "attention layers' K/V are an eighth of the cache's bytes; "
                "a quantized cache is not built for this model")
        if self.tensor_parallel:
            raise ValueError(
                "tensor_parallel: the per-slot state's head axis and the "
                "shared convolution channels have no sharding rule yet; "
                "this model is served whole on one chip (ROADMAP M4)")
        if self.decode and not self.slot_decode:
            raise ValueError("this model decodes through the block-paged "
                             "slot path only (slot_decode=True)")
        d = self.hidden_size
        cfg = tuple((f, getattr(self, f)) for f in self.__dataclass_fields__
                    if f not in ("parent", "name"))
        B, L = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        lanes = None
        if paged is not None:
            pos = paged["fill"][:, None] + pos       # for the masks only
            if self.packed_lanes:
                lanes = lane_pack.LaneMap(paged["n_new"], L)
                input_ids = lanes.pack(input_ids)                   # [R]
        # seeded so that x_0 = embedding_multiplier E[ids] has a projection's
        # scale: at 1/sqrt(d) a tied head echoes its input token
        embed = self.param("embed",
                           fan_in(d * self.embedding_multiplier ** 2),
                           (self.vocab_size, d), self.param_dtype)
        x = (embed[input_ids].astype(F32)
             * self.embedding_multiplier).astype(self.dtype)
        if lanes is not None:
            # a dead row holds zeros from here on (lane_pack's promise)
            x = jnp.where(lanes.row_live[:, None], x, 0)
        counted = ([], [], [])                # moved, visited, walked
        for i, kind in enumerate(self.layer_kinds()):
            x, counts = GraniteHybridLayer(
                cfg, kind, name=f"layer_{i}")(x, pos, paged, lanes)
            for rows, row in zip(counted, counts):
                if row is not None:
                    rows.append(row)
        if paged is not None:
            # what the layers did this tick, read by the engine when the
            # "counters" collection is mutable, dropped otherwise
            keep = dict(reduce_fn=lambda _, new: new, init_fn=lambda: None)
            for name, rows in zip(("ssm_slots_advanced", "ssm_state_visits",
                                   "attn_positions_walked"), counted):
                if rows:
                    self.sow("counters", name, jnp.stack(rows), **keep)
            self.sow("counters", "lanes_live", paged["n_new"][None, :],
                     **keep)
            self.sow("counters", "rows_dense",
                     jnp.full((1, 1), x.size // d, jnp.int32), **keep)
            # the head on each slot's sampled lane only
            if lanes is not None:
                x = lanes.last(x)[:, None]
            else:
                lane = jnp.clip(paged["n_new"] - 1, 0, L - 1)
                x = jnp.take_along_axis(x, lane[:, None, None], axis=1)
        x = rms_norm_f32(x, self.param("final_norm", nn.initializers.ones, (d,),
                                   self.param_dtype),
                     self.rms_norm_eps).astype(self.dtype)
        return einsum_f32("bld,vd->blv", x, embed) / self.logits_scaling


def granite_4_0_h_micro(**kw) -> GraniteHybridForCausalLM:
    """ibm-granite/granite-4.0-h-micro at its published sizes: 40 layers
    (36 Mamba-2, attention at 5, 15, 25, 35), the whole vocabulary
    (benchmarks/configs/granite_4_0_h_micro.json)."""
    return GraniteHybridForCausalLM(**kw)


def granite_hybrid_tiny(**kw) -> GraniteHybridForCausalLM:
    """Test-scale configuration (same code path, CPU-friendly, float32):
    five layers, attention at 2."""
    for k, v in dict(vocab_size=256, hidden_size=64, num_layers=5,
                     attention_period=3, attention_offset=2, num_heads=4,
                     num_kv_heads=2, head_dim=16, intermediate_size=128,
                     mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                     mamba_chunk_size=8, max_position=4096,
                     dtype=jnp.float32, param_dtype=jnp.float32).items():
        kw.setdefault(k, v)
    return GraniteHybridForCausalLM(**kw)
