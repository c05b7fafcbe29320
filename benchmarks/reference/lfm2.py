"""Plain reference for the LFM2 decoder (model_type ``lfm2_moe``): gated
short-convolution layers with a QK-normed, rotary grouped-query attention
layer every few, a dense SwiGLU in the leading layers and a sigmoid-scored
dropless top-k expert layer with no shared expert in the rest.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: the full forward over the whole sequence, the
convolution as ``conv_L_cache`` shifted copies of the whole sequence behind
zeros, a dense causal mask, one query head at a time and ``cfg["block"]``
query positions at a time (so that an 8,192-position pass fits one chip: a
``[block, L]`` score tile instead of ``[L, L]``), every expert applied to
every token and kept where chosen.  No kernel, no cache, no kept rows, no
batching trick; it imports nothing of the program.  Weights are made here
from the seed **in bfloat16** (the precision the configuration states), in
the nested layout the system under test accepts, and upcast a matrix or an
expert at a time where they are multiplied.

``x_0 = E[ids]``.  Per layer ``u = x + Op(N1(x))``, ``x' = u + FFN(N2(u))``;
logits ``N_f(x_L) E^T`` (tied).  *conv*: ``[B, C, z] = h W_in``, ``v = B *
z``, ``c_t = sum_k w[k] * v_{t-K+1+k}`` (no bias, no activation, zeros before
the start), ``y = (C * c) W_out``.  *full_attention*: ``q = N_q(h W_q)``,
``k = N_k(h W_k)`` per head over ``head_dim``, both rotated (rotate-half over
all of ``head_dim``) on every attention layer, ``v = h W_v``; query head
``i`` reads key/value head ``i // group``; ``y = concat_h(p v) W_o``.
*Experts*: ``s = sigmoid(h W_r)``, the ``k`` largest of ``s + b``, gates the
chosen experts' ``s`` over (their sum + 1e-6), times
``routed_scaling_factor``.

What the published ``config.json`` does not say is ``ASSUMED`` below, and
the same list stands in the configuration file.

``prec``: ``highest`` is the reference; ``fp8`` is the *control*: every
activation rounded to bfloat16 and every matrix operand to float8_e4m3
under a per-tensor scale.  A control has to come out as not correct.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
CONV, FULL = "conv", "full_attention"

ASSUMED = {
    "tied_head": "logits are N_f(x_L) E^T: tie_word_embeddings is not "
                 "among the catalog's keys and the family ties",
    "in_proj_order": "W_in's 3 x hidden_size columns are B, C, z in that "
                     "order",
    "positions": "rotate-half over all of head_dim at rope_theta on every "
                 "attention layer, after the head norms; no rope_scaling",
    "head_norms": "RMSNorm over head_dim on q and k, one scale each for "
                  "all heads, before the rotation",
    "gate_sum_eps": 1e-6,
    "router_bias": 0.0,
    "rope_pairs": "(i, i + head_dim / 2)",
}


# ---------------------------------------------------------- arithmetic

def _act(x, prec):
    if prec == "highest":
        return x
    return x.astype(jnp.bfloat16).astype(F32)


def _operand(x, prec):
    x = x.astype(F32)                  # a stored matrix: upcast where used
    if prec == "fp8":
        s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x


def _ein(spec, a, b, prec):
    return _act(jnp.einsum(spec, _operand(a, prec), _operand(b, prec),
                           precision=HI), prec)


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * scale.astype(F32)


def _rope(x, cfg):
    """x [L, H, hd] rotated at positions 0..L-1 over all of hd."""
    L, _, hd = x.shape
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(L, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ----------------------------------------------------------- the block

def _short_conv(x, p, cfg, prec):
    """x [L, d] of one sequence."""
    L, d = x.shape
    K = cfg["conv_L_cache"]
    bcz = _ein("ld,dk->lk", x, p["in_proj"], prec)
    B, C, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    v = jnp.concatenate([jnp.zeros((K - 1, d), F32), _act(B * z, prec)])
    w = p["conv_w"].astype(F32)
    c = sum(w[k] * v[k:k + L] for k in range(K))
    return _ein("ld,dk->lk", _act(C * c, prec), p["out_proj"], prec)


def _attention(x, p, cfg, prec):
    """x [L, d] of one sequence."""
    L = x.shape[0]
    Hq, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    blk = min(int(cfg.get("block") or L), L)
    if L % blk:
        raise ValueError(f"block {blk} does not divide {L} positions")
    q = _rms(_ein("ld,dk->lk", x, p["wq"], prec).reshape(L, Hq, hd),
             p["q_norm"], eps)
    k = _rms(_ein("ld,dk->lk", x, p["wk"], prec).reshape(L, Hk, hd),
             p["k_norm"], eps)
    q, k = _act(_rope(_act(q, prec), cfg), prec), \
        _act(_rope(_act(k, prec), cfg), prec)
    v = _ein("ld,dk->lk", x, p["wv"], prec).reshape(L, Hk, hd)
    at = jnp.arange(L)

    def head(i):                       # one query head at a time
        qh, kh, vh = q[:, i], k[:, i // (Hq // Hk)], v[:, i // (Hq // Hk)]

        def rows(b):                   # and a block of query positions
            qpos = b * blk + jnp.arange(blk)
            seen = at[None, :] <= qpos[:, None]
            scores = _ein("qd,kd->qk", jax.lax.dynamic_slice_in_dim(
                qh, b * blk, blk), kh, prec) * hd ** -0.5
            probs = _act(jax.nn.softmax(jnp.where(seen, scores, -1e30), -1),
                         prec)
            return _ein("qk,kd->qd", probs, vh, prec)

        return jax.lax.map(rows, jnp.arange(L // blk)).reshape(L, hd)

    o = jnp.moveaxis(jax.lax.map(head, jnp.arange(Hq)), 0, 1)   # [L, Hq, hd]
    return _ein("lk,kd->ld", o.reshape(L, Hq * hd), p["wo"], prec)


def _swiglu(x, p, prec):
    g = _ein("...d,df->...f", x, p["w_gate"], prec)
    u = _ein("...d,df->...f", x, p["w_up"], prec)
    return _ein("...f,fd->...d", _act(jax.nn.silu(g) * u, prec),
                p["w_down"], prec)


def lfm2_route(x, p, cfg):
    """Chosen experts [.., k] and their gates [.., k] of tokens x, float32:
    sigmoid scores, the k largest of score + bias, gates the chosen
    experts' own scores, normalized (``norm_topk_prob``), times
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(F32), precision=HI))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + ASSUMED["gate_sum_eps"])
    return idx, g * cfg["routed_scaling_factor"]


def lfm2_moe(x, p, cfg, prec="highest"):
    """The expert layer over x [..., d]: every expert over every token,
    kept where chosen.  No shared expert."""
    E = cfg["num_experts"]
    idx, g = lfm2_route(x, p, cfg)
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * g[..., None], -2)

    def expert(y, e):
        pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        w = jnp.take_along_axis(
            weight, jnp.broadcast_to(e, weight.shape[:-1] + (1,)), -1)
        return y + w * _swiglu(x, pe, prec), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return _act(y, prec)


def _layer(x, p, cfg, kind, prec):
    eps = cfg["norm_eps"]
    n = lambda t, name: _act(_rms(t, p[name], eps), prec)
    h = n(x, "operator_norm")
    y = _short_conv(h, p["conv"], cfg, prec) if kind == CONV \
        else _attention(h, p["attn"], cfg, prec)
    u = _act(x + y, prec)
    h = n(u, "ffn_norm")
    y = _swiglu(h, p["mlp"], prec) if "mlp" in p \
        else lfm2_moe(h, p["moe"], cfg, prec)
    return _act(u + y, prec)


def lfm2_hidden(params, ids, cfg, prec="highest"):
    """The normed last hidden state [B, L, d] of whole sequences ``ids``,
    one sequence at a time."""

    def one(seq):
        x = _act(params["embed"][seq].astype(F32), prec)
        for i, kind in enumerate(cfg["layer_types"]):
            x = _layer(x, params[f"layer_{i}"], cfg, kind, prec)
        return _act(_rms(x, params["final_norm"], cfg["norm_eps"]), prec)

    return jnp.stack([one(ids[b]) for b in range(ids.shape[0])])


def lfm2_head(params, h, cfg, prec="highest"):
    """Logits [.., V] of normed hidden states h [.., d]: the tied table."""
    del cfg
    return jnp.einsum("...d,vd->...v", _operand(h, prec),
                      _operand(params["embed"], prec), precision=HI)


def lfm2_logits(params, ids, cfg, prec="highest"):
    return lfm2_head(params, lfm2_hidden(params, ids, cfg, prec), cfg, prec)


# ------------------------------------------------------------- weights

def lfm2_weights(key, cfg: Dict[str, Any], dtype=jnp.bfloat16):
    """Seeded weights in the layout the program takes: matrices normal at
    ``1/sqrt(fan-in)`` (the taps at ``1/sqrt(conv_L_cache)``), the tied
    embedding at ``1/sqrt(d)``, norm scales one, the router's selection bias
    zero."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    Hq, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    E, fe, fd = (cfg["num_experts"], cfg["moe_intermediate_size"],
                 cfg["intermediate_size"])
    K, kinds = cfg["conv_L_cache"], cfg["layer_types"]
    keys = iter(jax.random.split(key, 16 * (len(kinds) + 1)))
    ones = lambda n: jnp.ones((n,), dtype)

    def mat(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                / math.sqrt(fan_in)).astype(dtype)

    def swiglu(f, lead=()):
        return {"w_gate": mat(lead + (d, f), d), "w_up": mat(lead + (d, f), d),
                "w_down": mat(lead + (f, d), f)}

    def layer(kind, dense):
        out = {"operator_norm": ones(d), "ffn_norm": ones(d)}
        if kind == CONV:
            out["conv"] = {"in_proj": mat((d, 3 * d), d),
                           "conv_w": mat((K, d), K),
                           "out_proj": mat((d, d), d)}
        else:
            out["attn"] = {"wq": mat((d, Hq * hd), d),
                           "wk": mat((d, Hk * hd), d),
                           "wv": mat((d, Hk * hd), d),
                           "wo": mat((Hq * hd, d), Hq * hd),
                           "q_norm": ones(hd), "k_norm": ones(hd)}
        if dense:
            out["mlp"] = swiglu(fd)
        else:
            out["moe"] = dict(
                swiglu(fe, (E,)), router=mat((d, E), d).astype(F32),
                router_bias=jnp.full((E,), ASSUMED["router_bias"], F32))
        return out

    params = {"embed": mat((V, d), d), "final_norm": ones(d)}
    for i, kind in enumerate(kinds):
        params[f"layer_{i}"] = layer(kind, i < cfg["num_dense_layers"])
    return {"params": params}
