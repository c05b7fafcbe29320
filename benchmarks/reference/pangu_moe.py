"""Plain reference for the openPangu-Ultra-MoE decoder block (model_type
``pangu_ultra_moe``): sandwich-normed latent attention (MLA) with plain
decoupled rotary keys, a sigmoid-scored dropless top-k expert layer beside
one shared expert, and the one multi-token-prediction module
(``num_nextn_predict_layers``, DeepSeek-V3's form).

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: *expanded* attention over the whole sequence (per-head
keys and values from the latent, one head at a time), every expert held
applied to every token and kept where chosen, no cache, no kernel, no
batching trick.  It imports nothing of the program.  Weights are made here
from the seed **in bfloat16** (the precision the configuration states), in
the nested layout the system under test accepts, and upcast a matrix or an
expert at a time where they are multiplied, so that a chip's share at the
published widths and a 2,048-token pass fit one chip.

Per layer: ``u = x + N2(Attn(N1(x)))``, ``x' = u + N4(FFN(N3(u)))``; logits
``N_f(x_L) W_head^T``.  The module, for position ``i``: ``m_i = W_eh
[N_e(E[t_{i+1}]) ; N_h(h_i)]`` with ``h_i`` the model's output after
``N_f``, ``z_i = Block(m)_i`` (one expert layer, own weights, rotary
position ``i``), logits of ``t_{i+2}`` ``= N_m(z_i) W_head^T``.

``cfg["experts_held"] = (first, count)``: the routed experts whose weights
exist here (a chip's share); the router keeps ``n_routed_experts`` outputs
and what the other experts would add is left out, as the program leaves it
out.  ``cfg["vocab_size"]`` is the slice of the vocabulary held.

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

ASSUMED = {
    "scoring": "sigmoid scores, no group limit, selection bias zero",
    "sandwich_norm": "N1 before and N2 after attention, N3 before and N4 "
                     "after the feed-forward; the residual adds the normed "
                     "output",
    "mtp_input": "h_i after the final norm; [embedding ; hidden]; rotary "
                 "position i",
    "kv_b_proj": "kept as W_UK, W_UV",
    "router_bias": 0.0,
    "rope_pairs": "(i, i + rope_dim / 2)",
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


def _rope(x, pos, cfg):
    """x [..., L, (H,) dr] rotated at positions ``pos`` [L]: plain rotary."""
    dim = cfg["qk_rope_head_dim"]
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        2 * jnp.arange(dim // 2, dtype=F32) / dim)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ----------------------------------------------------------- the block

def _attention(x, p, cfg, prec):
    b, L, _ = x.shape
    H, dn, dr, dv = (cfg["num_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    cq = _act(_rms(_ein("bld,dr->blr", x, p["w_dq"], prec), p["q_norm"],
                   eps), prec)
    q = _ein("blr,rk->blk", cq, p["w_uq"], prec).reshape(b, L, H, dn + dr)
    ckr = _ein("bld,dr->blr", x, p["w_dkv"], prec)
    ckv = _act(_rms(ckr[..., :rank], p["kv_norm"], eps), prec)
    pos = jnp.arange(L)
    k_rope = _act(_rope(ckr[..., rank:], pos, cfg), prec)   # one, all heads
    q_rope = _act(_rope(q[..., dn:], pos, cfg), prec)
    k_nope = _ein("blr,rhd->blhd", ckv, p["w_uk"], prec)
    v = _ein("blr,rhd->blhd", ckv, p["w_uv"], prec)
    keep = jnp.tril(jnp.ones((L, L), bool))
    scale = (dn + dr) ** -0.5

    def head(t):                       # one head at a time, to fit memory
        qn, qr, kn, vh = t             # [b, L, .]
        scores = (_ein("bqd,bkd->bqk", qn, kn, prec)
                  + _ein("bqd,bkd->bqk", qr, k_rope, prec)) * scale
        probs = _act(jax.nn.softmax(jnp.where(keep, scores, -1e30), -1),
                     prec)
        return _ein("bqk,bkd->bqd", probs, vh, prec)

    heads_first = lambda t: jnp.moveaxis(t, 2, 0)
    o = jax.lax.map(head, (heads_first(q[..., :dn]), heads_first(q_rope),
                           heads_first(k_nope), heads_first(v)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, L, H * dv)
    return _ein("blk,kd->bld", o, p["w_o"], prec)


def _swiglu(x, p, prec):
    g = _ein("...d,df->...f", x, p["w_gate"], prec)
    u = _ein("...d,df->...f", x, p["w_up"], prec)
    return _ein("...f,fd->...d", _act(jax.nn.silu(g) * u, prec),
                p["w_down"], prec)


def pangu_route(x, p, cfg):
    """Chosen experts [.., k] and their gates [.., k] of tokens x, float32:
    sigmoid scores, the k largest of score + bias, gates the chosen
    experts' own scores, normalized, times the routed scaling factor."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(F32), precision=HI))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return idx, g * cfg["routed_scaling_factor"]


def pangu_moe(x, p, cfg, prec="highest", shared=True):
    """The expert layer over x [..., d]: only the routed experts of
    ``cfg["experts_held"]`` (first, count; all by default), whose stacked
    weights ``p`` holds, add to the result; ``shared`` switches the shared
    expert's term, which every chip computes alike."""
    E = cfg["n_routed_experts"]
    first, count = cfg.get("experts_held") or (0, E)
    idx, g = pangu_route(x, p, cfg)
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * g[..., None], -2)

    def expert(y, e):                  # every expert held over every token
        pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        w = jnp.take_along_axis(
            weight, jnp.broadcast_to(first + e, weight.shape[:-1] + (1,)),
            -1)
        return y + w * _swiglu(x, pe, prec), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))
    if shared:
        y = y + _swiglu(x, p["shared"], prec)
    return _act(y, prec)


def _layer(x, p, cfg, prec):
    eps = cfg["rms_norm_eps"]
    n = lambda t, name: _act(_rms(t, p[name], eps), prec)
    u = _act(x + n(_attention(n(x, "attn_norm"), p["attn"], cfg, prec),
                   "attn_post_norm"), prec)
    h = n(u, "ffn_norm")
    y = _swiglu(h, p["mlp"], prec) if "mlp" in p \
        else pangu_moe(h, p["moe"], cfg, prec)
    return _act(u + n(y, "ffn_post_norm"), prec)


def pangu_hidden(params, ids, cfg, prec="highest"):
    """The normed last hidden state [B, L, d] of whole sequences ``ids``."""
    x = _act(params["embed"][ids].astype(F32), prec)
    for i in range(cfg["num_layers"]):
        x = _layer(x, params[f"layer_{i}"], cfg, prec)
    return _act(_rms(x, params["final_norm"], cfg["rms_norm_eps"]), prec)


def pangu_head(params, h, cfg, prec="highest"):
    """Logits [.., V] of normed hidden states h [.., d]."""
    del cfg
    return jnp.einsum("...d,dv->...v", _operand(h, prec),
                      _operand(params["head"], prec), precision=HI)


def pangu_logits(params, ids, cfg, prec="highest"):
    return pangu_head(params, pangu_hidden(params, ids, cfg, prec), cfg,
                      prec)


def pangu_mtp_hidden(params, ids, cfg, prec="highest", hidden=None):
    """The module's normed output [B, L, d] over whole sequences: position
    ``i`` from the model's ``h_i`` (``hidden``, computed if not given) and
    the token ``ids[:, i + 1]``; its head gives the logits of token ``i +
    2``.  The last position reads token 0 in the missing one's place."""
    p, eps = params["mtp"], cfg["rms_norm_eps"]
    h = pangu_hidden(params, ids, cfg, prec) if hidden is None else hidden
    nxt = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)
    e = _act(params["embed"][nxt].astype(F32), prec)
    both = jnp.concatenate([_act(_rms(e, p["enorm"], eps), prec),
                            _act(_rms(h, p["hnorm"], eps), prec)], -1)
    m = _ein("blk,kd->bld", both, p["eh_proj"], prec)
    return _act(_rms(_layer(m, p["block"], cfg, prec), p["norm"], eps), prec)


def pangu_mtp_logits(params, ids, cfg, prec="highest"):
    return pangu_head(params, pangu_mtp_hidden(params, ids, cfg, prec), cfg,
                      prec)


# ------------------------------------------------------------- weights

def pangu_weights(key, cfg: Dict[str, Any], dtype=jnp.bfloat16):
    """Seeded weights in the layout the program takes: matrices normal at
    ``1/sqrt(fan-in)``, the embedding at 1, norm scales one, the router's
    selection bias zero.  Only the experts of ``cfg["experts_held"]`` and
    ``cfg["vocab_size"]`` rows of the vocabulary are made."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, dn, dr, dv = (cfg["num_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    E, fe, fd = (cfg["n_routed_experts"], cfg["moe_intermediate_size"],
                 cfg["intermediate_size"])
    count = (cfg.get("experts_held") or (0, E))[1]
    keys = iter(jax.random.split(key, 32 * (cfg["num_layers"] + 2)))
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
            "attn": {"w_dq": mat((d, qr), d), "q_norm": ones(qr),
                     "w_uq": mat((qr, H * (dn + dr)), qr),
                     "w_dkv": mat((d, kr + dr), d), "kv_norm": ones(kr),
                     "w_uk": mat((kr, H, dn), kr),
                     "w_uv": mat((kr, H, dv), kr),
                     "w_o": mat((H * dv, d), H * dv)}}
        if dense:
            out["mlp"] = swiglu(fd)
        else:
            out["moe"] = dict(
                swiglu(fe, (count,)), shared=swiglu(fe),
                router=mat((d, E), d).astype(F32),
                router_bias=jnp.full((E,), ASSUMED["router_bias"], F32))
        return out

    params = {"embed": mat((V, d), 1.0), "head": mat((d, V), d),
              "final_norm": ones(d)}
    for i in range(cfg["num_layers"]):
        params[f"layer_{i}"] = layer(i < cfg["first_k_dense"])
    params["mtp"] = {"enorm": ones(d), "hnorm": ones(d), "norm": ones(d),
                     "eh_proj": mat((2 * d, d), 2 * d),
                     "block": layer(False)}
    return {"params": params}
