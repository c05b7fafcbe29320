"""Trinity-style decoder (``model_type: afmoe``): window and full attention
layers mixed, each a gated, QK-normed grouped-query attention, between
sandwich norms with a dense SwiGLU in the leading layers and a sigmoid-
routed dropless expert layer beside one shared expert in the rest.

``x_0 = sqrt(d) E[ids]`` (``mup_enabled``).  Per layer ``u = x +
N2(Attn(N1(x)))``, ``x' = u + N4(FFN(N3(u)))`` (as ``models/pangu_moe.py``);
logits ``N_f(x_L) W_head^T``.  Attention: ``q = N_q(h W_q)`` per head, ``k =
N_k(h W_k)`` (one scale each over a head's ``head_dim``, shared by the
heads), ``v = h W_v``, ``g = sigmoid(h W_g)``; on *window* layers
(``layer_types`` ``sliding_attention``) q and k are rotated (rotate-half
over all of ``head_dim`` at ``rope_theta``) and a position sees itself and
the ``sliding_window - 1`` before it; *full* layers carry no position and
see everything before them.  Query head ``i`` reads key/value head ``i //
(heads / kv heads)``; the output is ``(concat_h(p v) * g) W_o``.
``benchmarks/reference/trinity.py`` holds the same equations in plain
float32; the configuration file lists what the published config leaves open
(``assumed``).

**Two lifetimes of cache** (the serving contract is otherwise
``models/xing4.py``'s: ``decode=True, slot_decode=True``, float32 logits on
each slot's sampled lane, a ``counters`` collection).  A full layer caches
K (after norm) and V in block leaves ``[num_blocks, block_size, Hk * hd]``
addressed through the block table, as ``models/granite_hybrid.py``'s
attention does.  A window layer never reads past its window, so its K
(after norm and rotation) and V live in *window leaves*
(``ops/paged_cache.window_variable``): ``[slots * ring_blocks, block_size,
Hk * hd]``, written and read through the tick's ``ring_table`` (logical
block ``j`` in column ``j mod ring_blocks``), whose blocks the host hands
back as the slot's fill passes them (serve/slots.BlockPool).  Scores, mask,
softmax and weighted sum of both kinds are one op,
``ops.attention.paged_gqa_attention``: a Pallas kernel on the TPU and under
the interpreter that walks only the blocks a layer may see, the XLA gather
form elsewhere and under ``FORCE_XLA``.

Counters: ``expert_load``, ``expert_weight_visits`` ``[expert layers, E]``,
``attn_positions_walked [layers, S]``, ``lanes_live [1, S]``.  Scopes:
``sandwich_norm``, ``gqa_attention`` (projections, head norms, rotation,
gate, ``W_o``), ``kv_write``, ``paged_gqa_attention``, ``moe_*``,
``shared_expert``.

Weights, activations and K/V are ``dtype``/``param_dtype`` (bfloat16 as
served); norm statistics, the rotation, the router, the softmax and the
logits are float32.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_example_tpu.models.layers import (F32, RoutedExperts, SwiGLU,
                                            causal_gqa_attention, fan_in,
                                            matmul_f32, paged_gqa_step,
                                            rms_norm, rotate_half)
from apex_example_tpu.obs.spans import device_span

WINDOW, FULL = "sliding_attention", "full_attention"


class GatedGQAttention(nn.Module):
    """One attention sublayer; ``window`` None for a full layer.  Returns
    ``(y, walked)``: ``walked [S]`` the cache positions the paged form read
    for each slot, None from the plain forward."""

    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]
    rope_theta: float
    rms_norm_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False
    slot_decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0

    @nn.compact
    def __call__(self, h, pos, paged=None):
        d, Hq, Hk, hd = (self.hidden_size, self.num_heads, self.num_kv_heads,
                         self.head_dim)
        pd, eps, W = self.param_dtype, self.rms_norm_eps, self.window
        wq = self.param("wq", fan_in(d), (d, Hq * hd), pd)
        wk = self.param("wk", fan_in(d), (d, Hk * hd), pd)
        wv = self.param("wv", fan_in(d), (d, Hk * hd), pd)
        wg = self.param("wg", fan_in(d), (d, Hq * hd), pd)
        wo = self.param("wo", fan_in(Hq * hd), (Hq * hd, d), pd)
        q_norm = self.param("q_norm", nn.initializers.ones, (hd,), pd)
        k_norm = self.param("k_norm", nn.initializers.ones, (hd,), pd)
        mm = lambda a, w: matmul_f32(a, w).astype(self.dtype)
        B, L = pos.shape
        scale = hd ** -0.5

        with device_span("gqa_attention"):
            q = rms_norm(mm(h, wq).reshape(B, L, Hq, hd), q_norm, eps)
            k = rms_norm(mm(h, wk).reshape(B, L, Hk, hd), k_norm, eps)
            v = mm(h, wv)
            gate = jax.nn.sigmoid(matmul_f32(h, wg))
            if W is not None:
                q = rotate_half(q, pos, self.rope_theta)
                k = rotate_half(k, pos, self.rope_theta)

        o = walked = None
        if self.decode:
            if not self.slot_decode:
                raise ValueError("this model decodes through the block-"
                                 "paged slot path only (slot_decode=True)")
            # a full layer's block leaves or a window layer's ring;
            # nothing from the init trace, which allocates them: fall
            # through so that params initialize
            o, walked = paged_gqa_step(
                self, q, k, v, pos, paged, self.kv_num_blocks,
                self.kv_block_size, scale, window=W)
        if o is None:
            o = causal_gqa_attention(q, k, v, pos, scale, window=W)
        with device_span("gqa_attention"):
            o = (o.reshape(B, L, Hq * hd).astype(F32)
                 * gate).astype(self.dtype)
            return mm(o, wo), walked


class TrinityLayer(nn.Module):
    """Attention and feed-forward, each between its two RMSNorms.  ``cfg``
    is the model's own field values.  Returns ``(x, load, visits,
    walked)`` as ``models/pangu_moe.PanguLayer``."""

    cfg: Tuple[Tuple[str, object], ...]
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, pos, paged, live):
        c = dict(self.cfg)
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        dtype, pd = c["dtype"], c["param_dtype"]

        def norm(name, t):
            with device_span("sandwich_norm"):
                return rms_norm(t, self.param(name, nn.initializers.ones,
                                              (d,), pd), eps)

        y, walked = GatedGQAttention(
            d, c["num_heads"], c["num_kv_heads"], c["head_dim"],
            c["sliding_window"] if self.kind == WINDOW else None,
            float(c["rope_theta"]), eps, dtype, pd, c["decode"],
            c["slot_decode"], c["kv_num_blocks"], c["kv_block_size"],
            name="attn")(norm("attn_norm", x), pos, paged)
        u = x + norm("attn_post_norm", y)
        h = norm("ffn_norm", u)
        load = visits = None
        if self.dense:
            y = SwiGLU(d, c["intermediate_size"], dtype, pd, name="mlp")(h)
        else:
            E = c["num_experts"]
            y, load, visits = RoutedExperts(
                d, c["moe_intermediate_size"], E, c["num_experts_per_tok"],
                float(c["route_scale"]), (0, E), dtype, pd,
                name="moe")(h, live)
        return u + norm("ffn_post_norm", y), load, visits, walked


class TrinityForCausalLM(nn.Module):
    """Returns float32 logits: ``[B, L, V]`` from the plain forward,
    ``[SLOTS, 1, V]`` (each slot's sampled lane) from the paged one."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    num_layers: int = 32
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    sliding_window: int = 2048
    # every ``global_attn_every_n_layers``-th layer attends everything, the
    # others a window; ``layer_types`` says it layer by layer instead
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position: int = 131072
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
        if self.layer_types is not None:
            # (a configuration file gives a list; a module's fields are
            # hashed: serve/engine.py caches its step on the module)
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        super().__post_init__()

    def layer_kinds(self) -> Tuple[str, ...]:
        """``layer_types`` of the published config."""
        if self.layer_types is not None:
            kinds = self.layer_types
            if len(kinds) != self.num_layers or set(kinds) - {WINDOW, FULL}:
                raise ValueError(
                    f"layer_types wants {self.num_layers} of {WINDOW!r} / "
                    f"{FULL!r}, got {kinds}")
            return kinds
        n = self.global_attn_every_n_layers
        return tuple(FULL if (i + 1) % n == 0 else WINDOW
                     for i in range(self.num_layers))

    @nn.compact
    def __call__(self, input_ids, train: bool = True, paged=None):
        del train
        if self.kv_quant:
            raise ValueError(
                "kv_quant: a window leaf has no scale table and the paged "
                "kernel reads bfloat16 pages; a low-bit ring is not built "
                "(ROADMAP M3: quantised window leaves)")
        if self.tensor_parallel:
            raise ValueError(
                "tensor_parallel: 4 key/value heads over a 'model' axis and "
                "a sharded ring have no rule yet; each layer is served "
                "whole on one chip (ROADMAP M3)")
        d = self.hidden_size
        cfg = tuple((f, getattr(self, f)) for f in self.__dataclass_fields__
                    if f not in ("parent", "name"))
        B, L = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        live = None
        if paged is not None:
            pos = paged["fill"][:, None] + pos
            live = jnp.arange(L)[None, :] < paged["n_new"][:, None]
        # seeded at 1/sqrt(d), so that x_0 = sqrt(d) E[ids] has unit scale
        embed = self.param("embed", fan_in(d), (self.vocab_size, d),
                           self.param_dtype)
        x = embed[input_ids]
        if self.mup_enabled:
            x = x.astype(F32) * math.sqrt(d)
        x = x.astype(self.dtype)
        loads, visits, walks = [], [], []
        for i, kind in enumerate(self.layer_kinds()):
            x, load, visited, walked = TrinityLayer(
                cfg, kind, i < self.num_dense_layers, name=f"layer_{i}")(
                    x, pos, paged, live)
            for rows, row in ((loads, load), (visits, visited),
                              (walks, walked)):
                if row is not None:
                    rows.append(row)
        for name, rows in (("expert_load", loads),
                           ("expert_weight_visits", visits),
                           ("attn_positions_walked", walks)):
            if rows:
                self.sow("counters", name, jnp.stack(rows),
                         reduce_fn=lambda _, new: new, init_fn=lambda: None)
        if paged is not None:
            self.sow("counters", "lanes_live", paged["n_new"][None, :],
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
            # the head on each slot's sampled lane only
            lane = jnp.clip(paged["n_new"] - 1, 0, L - 1)
            x = jnp.take_along_axis(x, lane[:, None, None], axis=1)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones, (d,),
                                   self.param_dtype), self.rms_norm_eps)
        head = self.param("head", fan_in(d), (d, self.vocab_size),
                          self.param_dtype)
        return matmul_f32(x, head)


def trinity_mini_cut(**kw) -> TrinityForCausalLM:
    """arcee-ai/Trinity-Mini at its published widths, every expert and the
    whole vocabulary, cut in depth to one stage of a pipeline of one-chip
    stages: one leading dense layer, then one whole period of expert layers
    (window, window, window, full: published layers 4-7)
    (benchmarks/configs/trinity_mini.json)."""
    for k, v in dict(num_layers=5, num_dense_layers=1,
                     layer_types=(WINDOW,) * 4 + (FULL,)).items():
        kw.setdefault(k, v)
    return TrinityForCausalLM(**kw)


def trinity_tiny(**kw) -> TrinityForCausalLM:
    """Test-scale configuration (same code path, CPU-friendly, float32): a
    window of 8, so that with blocks of 4 a 40-token request crosses the
    window, wraps the ring and frees blocks."""
    for k, v in dict(vocab_size=256, hidden_size=64, num_layers=5,
                     num_dense_layers=1, num_heads=4, num_kv_heads=2,
                     head_dim=16, intermediate_size=128,
                     moe_intermediate_size=32, num_experts=8,
                     num_experts_per_tok=2, sliding_window=8,
                     layer_types=(WINDOW,) * 4 + (FULL,),
                     max_position=4096, dtype=jnp.float32,
                     param_dtype=jnp.float32).items():
        kw.setdefault(k, v)
    return TrinityForCausalLM(**kw)
