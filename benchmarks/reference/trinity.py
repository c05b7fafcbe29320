"""Plain reference for the Trinity decoder (model_type ``afmoe``): window and
full attention layers mixed, each a gated, QK-normed grouped-query
attention between sandwich norms, a dense SwiGLU in the leading layers and
a sigmoid-scored dropless top-k expert layer beside one shared expert in
the rest.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: the full forward over the whole sequence, a dense
mask per layer kind (``p' <= p``, and in a window layer ``p' > p - W``), one
query head at a time and ``cfg["block"]`` query positions at a time (so that
a 16,384-position pass fits one chip: a ``[block, L]`` score tile instead
of ``[L, L]``), every expert applied to every token and kept where chosen.
No kernel, no cache, no ring, no batching trick; it imports nothing of the
program.  Weights are made here from the seed **in bfloat16** (the
precision the configuration states), in the nested layout the system under
test accepts, and upcast a matrix or an expert at a time where they are
multiplied.

``x_0 = sqrt(d) E[ids]`` (``mup_enabled``).  Per layer ``u = x +
N2(Attn(N1(x)))``, ``x' = u + N4(FFN(N3(u)))``; logits ``N_f(x_L)
W_head^T``.  Attention: ``q = N_q(h W_q)``, ``k = N_k(h W_k)`` per head over
``head_dim``, ``v = h W_v``, ``g = sigmoid(h W_g)``; window layers rotate q
and k (rotate-half over all of ``head_dim``); query head ``i`` reads
key/value head ``i // group``; output ``(concat_h(p v) * g) W_o``.

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
WINDOW, FULL = "sliding_attention", "full_attention"

ASSUMED = {
    "output_gate": "g = sigmoid(h W_g), 2048 -> 4096, multiplies the "
                   "concatenated heads before W_o",
    "head_norms": "RMSNorm over head_dim on q and k, one scale each for "
                  "all heads, before the rotation",
    "positions": "rotation on sliding_attention layers only; "
                 "full_attention layers carry no position",
    "window_edge": "a position sees itself and the sliding_window - 1 "
                   "before it",
    "sandwich_norm": "N1 before and N2 after attention, N3 before and N4 "
                     "after the feed-forward; the residual adds the normed "
                     "output",
    "mup": "sqrt(hidden_size) on the embedding alone",
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

def _attention(x, p, cfg, kind, prec):
    """x [L, d] of one sequence."""
    L = x.shape[0]
    Hq, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, W = cfg["rms_norm_eps"], cfg["sliding_window"]
    blk = min(int(cfg.get("block") or L), L)
    if L % blk:
        raise ValueError(f"block {blk} does not divide {L} positions")
    q = _act(_rms(_ein("ld,dk->lk", x, p["wq"], prec).reshape(L, Hq, hd),
                  p["q_norm"], eps), prec)
    k = _act(_rms(_ein("ld,dk->lk", x, p["wk"], prec).reshape(L, Hk, hd),
                  p["k_norm"], eps), prec)
    v = _ein("ld,dk->lk", x, p["wv"], prec).reshape(L, Hk, hd)
    gate = jax.nn.sigmoid(_ein("ld,dk->lk", x, p["wg"], prec))
    if kind == WINDOW:
        q, k = _act(_rope(q, cfg), prec), _act(_rope(k, cfg), prec)
    at = jnp.arange(L)

    def head(i):                       # one query head at a time
        qh, kh, vh = q[:, i], k[:, i // (Hq // Hk)], v[:, i // (Hq // Hk)]

        def rows(b):                   # and a block of query positions
            qpos = b * blk + jnp.arange(blk)
            seen = at[None, :] <= qpos[:, None]
            if kind == WINDOW:
                seen &= at[None, :] > qpos[:, None] - W
            scores = _ein("qd,kd->qk", jax.lax.dynamic_slice_in_dim(
                qh, b * blk, blk), kh, prec) * hd ** -0.5
            probs = _act(jax.nn.softmax(jnp.where(seen, scores, -1e30), -1),
                         prec)
            return _ein("qk,kd->qd", probs, vh, prec)

        return jax.lax.map(rows, jnp.arange(L // blk)).reshape(L, hd)

    o = jnp.moveaxis(jax.lax.map(head, jnp.arange(Hq)), 0, 1)   # [L, Hq, hd]
    return _ein("lk,kd->ld", _act(o.reshape(L, Hq * hd) * gate, prec),
                p["wo"], prec)


def _swiglu(x, p, prec):
    g = _ein("...d,df->...f", x, p["w_gate"], prec)
    u = _ein("...d,df->...f", x, p["w_up"], prec)
    return _ein("...f,fd->...d", _act(jax.nn.silu(g) * u, prec),
                p["w_down"], prec)


def trinity_route(x, p, cfg):
    """Chosen experts [.., k] and their gates [.., k] of tokens x, float32:
    sigmoid scores, the k largest of score + bias, gates the chosen
    experts' own scores, normalized (``route_norm``), times
    ``route_scale``."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(F32), precision=HI))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return idx, g * cfg["route_scale"]


def trinity_moe(x, p, cfg, prec="highest"):
    """The expert layer over x [..., d]: every expert over every token,
    kept where chosen, plus the shared expert."""
    E = cfg["num_experts"]
    idx, g = trinity_route(x, p, cfg)
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * g[..., None], -2)

    def expert(y, e):
        pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        w = jnp.take_along_axis(
            weight, jnp.broadcast_to(e, weight.shape[:-1] + (1,)), -1)
        return y + w * _swiglu(x, pe, prec), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return _act(y + _swiglu(x, p["shared"], prec), prec)


def _layer(x, p, cfg, kind, prec):
    eps = cfg["rms_norm_eps"]
    n = lambda t, name: _act(_rms(t, p[name], eps), prec)
    u = _act(x + n(_attention(n(x, "attn_norm"), p["attn"], cfg, kind,
                              prec), "attn_post_norm"), prec)
    h = n(u, "ffn_norm")
    y = _swiglu(h, p["mlp"], prec) if "mlp" in p \
        else trinity_moe(h, p["moe"], cfg, prec)
    return _act(u + n(y, "ffn_post_norm"), prec)


def trinity_hidden(params, ids, cfg, prec="highest"):
    """The normed last hidden state [B, L, d] of whole sequences ``ids``,
    one sequence at a time."""
    d = cfg["hidden_size"]

    def one(seq):
        x = _act(params["embed"][seq].astype(F32) * math.sqrt(d), prec)
        for i, kind in enumerate(cfg["layer_types"]):
            x = _layer(x, params[f"layer_{i}"], cfg, kind, prec)
        return _act(_rms(x, params["final_norm"], cfg["rms_norm_eps"]), prec)

    return jnp.stack([one(ids[b]) for b in range(ids.shape[0])])


def trinity_head(params, h, cfg, prec="highest"):
    """Logits [.., V] of normed hidden states h [.., d]."""
    del cfg
    return jnp.einsum("...d,dv->...v", _operand(h, prec),
                      _operand(params["head"], prec), precision=HI)


def trinity_logits(params, ids, cfg, prec="highest"):
    return trinity_head(params, trinity_hidden(params, ids, cfg, prec), cfg,
                        prec)


# ------------------------------------------------------------- weights

def trinity_weights(key, cfg: Dict[str, Any], dtype=jnp.bfloat16):
    """Seeded weights in the layout the program takes: matrices normal at
    ``1/sqrt(fan-in)``, the embedding at ``1/sqrt(d)``, norm scales one, the
    router's selection bias zero."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    Hq, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    E, fe, fd = (cfg["num_experts"], cfg["moe_intermediate_size"],
                 cfg["intermediate_size"])
    kinds = cfg["layer_types"]
    keys = iter(jax.random.split(key, 16 * (len(kinds) + 1)))
    ones = lambda n: jnp.ones((n,), dtype)

    def mat(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                / math.sqrt(fan_in)).astype(dtype)

    def swiglu(f, lead=()):
        return {"w_gate": mat(lead + (d, f), d), "w_up": mat(lead + (d, f), d),
                "w_down": mat(lead + (f, d), f)}

    def layer(dense):
        out = {
            "attn_norm": ones(d), "attn_post_norm": ones(d),
            "ffn_norm": ones(d), "ffn_post_norm": ones(d),
            "attn": {"wq": mat((d, Hq * hd), d), "wk": mat((d, Hk * hd), d),
                     "wv": mat((d, Hk * hd), d), "wg": mat((d, Hq * hd), d),
                     "wo": mat((Hq * hd, d), Hq * hd),
                     "q_norm": ones(hd), "k_norm": ones(hd)}}
        if dense:
            out["mlp"] = swiglu(fd)
        else:
            out["moe"] = dict(
                swiglu(fe, (E,)), shared=swiglu(fe),
                router=mat((d, E), d).astype(F32),
                router_bias=jnp.full((E,), ASSUMED["router_bias"], F32))
        return out

    params = {"embed": mat((V, d), d), "head": mat((d, V), d),
              "final_norm": ones(d)}
    for i in range(len(kinds)):
        params[f"layer_{i}"] = layer(i < cfg["num_dense_layers"])
    return {"params": params}
