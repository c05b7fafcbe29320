"""Plain reference for the Xing4.0 decoder block (model_type ``xing4_0``):
a 4-stream hyper-connected residual whose mixing matrices are projected
onto doubly stochastic ones by Sinkhorn iterations, latent attention (MLA)
with decoupled YaRN rotary keys, and a sigmoid-scored, bias-selected,
dropless top-k expert layer beside one shared expert.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: expanded attention over the whole sequence, every
expert applied to every token and kept where chosen, no cache, no kernel,
no batching trick.  It imports nothing of the program.  Weights are made
here from the seed **in bfloat16** (the precision the configuration
states), in the nested layout the system under test accepts, and upcast
one layer at a time.

What the published ``config.json`` does not say is ``ASSUMED`` below, and
the same list stands in the configuration file:

- a token's ``hc_mult`` streams all start as its embedding;
- the mixing coefficients read ``RMSNorm(vec(X))`` with no learned scale;
- Sinkhorn: ``exp`` of the clamped logits, then ``hc_sinkhorn_iters`` times
  rows then columns, each divided by its sum plus ``hc_eps``;
- after the last layer the streams are summed;
- seeded values: every alpha 0.01, ``b_pre``/``b_post`` such that
  ``H_pre = 1/n`` and ``H_post = 1`` at a zero input, ``b_res`` 8 on the
  diagonal, ``W_*`` normal at ``1/sqrt(n d)``; the router's selection bias
  zero;
- rotary pairs are (i, i + 32) of the 64 rotary dimensions (a naming of the
  weights' columns, not a value).

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
    "streams_start": "every stream starts as the token's embedding",
    "hc_norm": "RMSNorm over the n*d concatenated streams, no learned scale",
    "sinkhorn": "exp(clamped), then iters x (rows / (sum + eps), columns / "
                "(sum + eps))",
    "streams_end": "streams are summed before the final norm",
    "hc_alpha": 0.01,
    "hc_res_diag": 8.0,
    "router_bias": 0.0,
    "rope_pairs": "(i, i + rope_dim / 2)",
}


# ---------------------------------------------------------- arithmetic

def _act(x, prec):
    if prec == "highest":
        return x
    return x.astype(jnp.bfloat16).astype(F32)


def _operand(x, prec):
    if prec == "fp8":
        s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x


def _ein(spec, a, b, prec):
    return _act(jnp.einsum(spec, _operand(a, prec), _operand(b, prec),
                           precision=HI), prec)


def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if scale is None else y * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda t: t.astype(F32), tree)


# -------------------------------------------------------------- rotary

def xing4_yarn_inv_freq(cfg: Dict[str, Any]):
    """The 32 rotary frequencies under YaRN: ``1/theta_i`` where more than
    ``beta_fast`` rotations fit the original context, ``1/(factor
    theta_i)`` where fewer than ``beta_slow`` do, a linear blend over the
    dimensions between."""
    r, dim = cfg["rope"], cfg["qk_rope_head_dim"]
    base, orig = float(r["theta"]), r["original_max_position_embeddings"]

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(r["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(r["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=F32)
    extra = 1.0 / base ** (2 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / r["factor"] * ramp + extra * (1.0 - ramp)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def xing4_softmax_scale(cfg: Dict[str, Any]) -> float:
    r = cfg["rope"]
    m = _mscale(r["factor"], r["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(x, pos, cfg):
    """x [..., L, (H,) 64] rotated at positions ``pos`` [L]."""
    r = cfg["rope"]
    ang = pos.astype(F32)[:, None] * xing4_yarn_inv_freq(cfg)[None, :]
    m = _mscale(r["factor"], r["mscale"]) \
        / _mscale(r["factor"], r["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ----------------------------------------------------------- the block

def _sinkhorn(logits, cfg):
    m = jnp.exp(jnp.clip(logits, cfg["hc_clamp_min"], cfg["hc_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, -1, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, -2, keepdims=True) + cfg["hc_eps"])
    return m


def _hyper(X, p, fn, cfg, prec):
    """One hyper-connection unit round sublayer ``fn``: X [B, L, n, d]."""
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    xt = _rms(X.reshape(*X.shape[:-2], -1), None, eps)
    a = p["alpha"]
    h_pre = jax.nn.sigmoid(
        a[0] * jnp.matmul(xt, p["w_pre"], precision=HI) + p["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(
        a[1] * jnp.matmul(xt, p["w_post"], precision=HI) + p["b_post"])
    res = a[2] * jnp.matmul(xt, p["w_res"], precision=HI)
    h_res = _sinkhorn(res.reshape(*res.shape[:-1], n, n) + p["b_res"], cfg)
    u = _act(jnp.einsum("...n,...nd->...d", h_pre, X, precision=HI), prec)
    y = fn(u)
    return _act(jnp.einsum("...ij,...jd->...id", h_res, X, precision=HI)
                + h_post[..., None] * y[..., None, :], prec)


def _attention(x, p, norm, cfg, prec):
    b, L, _ = x.shape
    H, dn, dr, dv = (cfg["num_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    x = _act(_rms(x, norm, eps), prec)
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

    def head(t):                       # one head at a time, to fit memory
        qn, qr, kn, vh = t             # [b, L, .]
        scores = (_ein("bqd,bkd->bqk", qn, kn, prec)
                  + _ein("bqd,bkd->bqk", qr, k_rope, prec)) \
            * xing4_softmax_scale(cfg)
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


def xing4_route(x, p, cfg):
    """Chosen experts [.., k] and their gates [.., k] of tokens x, float32:
    sigmoid scores, the k largest of score + bias, gates the chosen
    experts' own scores, normalized, times the routed scaling factor."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(F32), precision=HI))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(F32),
                           cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return idx, g * cfg["routed_scaling_factor"]


def xing4_moe(x, p, cfg, prec="highest", experts_held=None, shared=True):
    """The expert layer over x [..., d].  ``experts_held`` (first, count):
    only those routed experts add to the result (a chip's share; what the
    others would have added is left out); ``shared`` switches the shared
    expert's term, which every chip computes alike."""
    E = cfg["n_routed_experts"]
    first, count = experts_held or (0, E)
    idx, g = xing4_route(x, p, cfg)
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * g[..., None], -2)

    def expert(y, e):                  # every expert over every token
        pe = {k: p[k][e].astype(F32) for k in ("w_gate", "w_up", "w_down")}
        w = jnp.take_along_axis(
            weight, jnp.broadcast_to(e, weight.shape[:-1] + (1,)), -1)
        return y + w * _swiglu(x, pe, prec), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        first + jnp.arange(count))
    if shared:
        y = y + _swiglu(x, _f32(p["shared"]), prec)
    return _act(y, prec)


def _ffn(x, p, norm, cfg, prec):
    x = _act(_rms(x, norm, cfg["rms_norm_eps"]), prec)
    if "mlp" in p:
        return _swiglu(x, p["mlp"], prec)
    return xing4_moe(x, p["moe"], cfg, prec, cfg.get("experts_held"))


def xing4_hidden(params, ids, cfg, prec="highest"):
    """The normed last hidden state [B, L, d] of whole sequences ``ids``."""
    X = params["embed"].astype(F32)[ids]
    X = jnp.repeat(_act(X, prec)[..., None, :], cfg["hc_mult"], -2)
    for i in range(cfg["num_layers"]):
        p = dict(params[f"layer_{i}"])             # one layer at a time
        p = dict(_f32({k: v for k, v in p.items() if k != "moe"}),
                 **({"moe": p["moe"]} if "moe" in p else {}))
        X = _hyper(X, p["attn_hc"],
                   lambda u: _attention(u, p["attn"], p["attn_norm"], cfg,
                                        prec), cfg, prec)
        X = _hyper(X, p["ffn_hc"],
                   lambda u: _ffn(u, p, p["ffn_norm"], cfg, prec), cfg, prec)
    x = jnp.sum(X, -2)
    return _act(_rms(x, params["final_norm"].astype(F32),
                     cfg["rms_norm_eps"]), prec)


def xing4_head(params, h, cfg, prec="highest"):
    """Logits [.., V] of normed hidden states h [.., d]."""
    del cfg
    return jnp.einsum("...d,dv->...v", _operand(h, prec),
                      _operand(params["head"].astype(F32), prec),
                      precision=HI)


def xing4_logits(params, ids, cfg, prec="highest"):
    return xing4_head(params, xing4_hidden(params, ids, cfg, prec), cfg,
                      prec)


# ------------------------------------------------------------- weights

def xing4_weights(key, cfg: Dict[str, Any], dtype=jnp.bfloat16):
    """Seeded weights in the layout the program takes, matrices normal at
    ``1/sqrt(fan-in)`` (PERF.md section 4), norm scales one, the
    hyper-connection parameters as ``ASSUMED``."""
    d, n, V = cfg["hidden_size"], cfg["hc_mult"], cfg["vocab_size"]
    H, dn, dr, dv = (cfg["num_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    E, fe, fd = (cfg["n_routed_experts"], cfg["moe_intermediate_size"],
                 cfg["intermediate_size"])
    keys = iter(jax.random.split(key, 64 * (cfg["num_layers"] + 1)))

    def mat(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                / math.sqrt(fan_in)).astype(dtype)

    def hyper():
        nd = n * d
        return {"w_pre": mat((nd, n), nd), "w_post": mat((nd, n), nd),
                "w_res": mat((nd, n * n), nd),
                "alpha": jnp.full((3,), ASSUMED["hc_alpha"], F32),
                "b_pre": jnp.full((n,), -math.log(n - 1.0), F32),
                "b_post": jnp.zeros((n,), F32),
                "b_res": ASSUMED["hc_res_diag"] * jnp.eye(n, dtype=F32)}

    def swiglu(f, lead=()):
        return {"w_gate": mat(lead + (d, f), d), "w_up": mat(lead + (d, f), d),
                "w_down": mat(lead + (f, d), f)}

    params = {"embed": mat((V, d), 1.0), "head": mat((d, V), d),
              "final_norm": jnp.ones((d,), dtype)}
    for i in range(cfg["num_layers"]):
        layer = {
            "attn_hc": hyper(), "ffn_hc": hyper(),
            "attn_norm": jnp.ones((d,), dtype),
            "ffn_norm": jnp.ones((d,), dtype),
            "attn": {"w_dq": mat((d, qr), d), "q_norm": jnp.ones((qr,), dtype),
                     "w_uq": mat((qr, H * (dn + dr)), qr),
                     "w_dkv": mat((d, kr + dr), d),
                     "kv_norm": jnp.ones((kr,), dtype),
                     "w_uk": mat((kr, H, dn), kr),
                     "w_uv": mat((kr, H, dv), kr),
                     "w_o": mat((H * dv, d), H * dv)}}
        if i < cfg["first_k_dense"]:
            layer["mlp"] = swiglu(fd)
        else:
            layer["moe"] = dict(
                swiglu(fe, (E,)), shared=swiglu(fe),
                router=mat((d, E), d).astype(F32),
                router_bias=jnp.full((E,), ASSUMED["router_bias"], F32))
        params[f"layer_{i}"] = layer
    return {"params": params}
