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

Latent attention (``LatentAttention``, which tells its two forms: the plain
forward expands the latent, the paged path caches ``c_kv ⊕ k_rope`` in ONE
head-less arena leaf and attends absorbed), the routed experts and the SwiGLU
are ``models/layers.py``'s.  The model sows what the paged attention read,
``attn_positions_walked [layers, S]``, beside ``expert_load`` in the
``counters`` collection, and beside ``expert_weight_visits [layers, E]``
where the expert layers' grouped products ran in their kernel
(``ops/grouped_matmul.py``; the XLA form, ``lax.ragged_dot``, counts
nothing).

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

from apex_example_tpu.models.layers import (F32, LatentAttention,
                                            RoutedExperts, SwiGLU, fan_in,
                                            matmul_f32, rms_norm)
from apex_example_tpu.obs.spans import device_span
from apex_example_tpu.ops import lane_pack


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
        w = lambda name, cols: self.param(name, fan_in(nd), (nd, cols),
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
        head = self.param("head", fan_in(d), (d, self.vocab_size),
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
