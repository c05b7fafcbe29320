"""Plain reference for the Granite 4.0-H hybrid decoder (model_type
``granitemoehybrid`` with ``num_local_experts`` 0): Mamba-2 state-space
layers, a GQA attention layer where ``layer_types`` says so, a shared
SwiGLU MLP in every layer, no positional encoding, and Granite's
embedding, attention, residual and logits multipliers.

Straightforward ``jax.numpy`` in float32 with every product at ``highest``
precision: the state-space recurrence **token by token** (``lax.scan`` over
positions, no chunking, the state carried from zero), full causal attention
with the K/V heads repeated for their query heads, no cache, no kernel, no
batching trick.  It imports nothing of the program.  Weights are made here
from the seed **in bfloat16** (the precision the configuration states), in
the nested layout the system under test accepts, and upcast one layer at a
time.

Equations (``h`` a layer's normed input, ``d_inner = H P``):

- ``[z, xBC, dt] = h W_in`` (widths ``d_inner``, ``d_inner + 2 N``, ``H``);
  ``xBC_t <- silu(b + sum_k w_k xBC_{t-K+1+k})`` (zeros before the
  sequence); ``[x, B, C] = xBC``; ``dt_t = softplus(dt_t + dt_bias)``;
  ``a_t = exp(-dt_t exp(A_log))``; ``S_t = a_t S_{t-1} + dt_t x_t B_t^T``;
  ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm_w(y silu(z))`` over all
  ``d_inner`` channels; ``y W_out``.
- attention: ``softmax(attention_multiplier q k^T)`` causal, query head
  ``i`` on K/V head ``i // (heads / kv_heads)``, no position signal.
- ``u = x + r Mixer(RMSNorm(x))``, ``x' = u + r MLP(RMSNorm(u))``,
  ``MLP(h) = (silu(g) v) W_out`` with ``[g, v] = h W_in``;
  ``x_0 = embedding_multiplier E[ids]``; logits ``RMSNorm(x_L) E^T /
  logits_scaling``.

What the published ``config.json`` cannot give is ``ASSUMED`` below (seeded
values, the Mamba-2 paper's initialisation), and the same list stands in
the configuration file.

``prec``: ``highest`` is the reference; ``fp8`` is the *control*: every
activation and the carried state rounded to bfloat16 and every matrix
operand to float8_e4m3 under a per-tensor scale.  A control has to come out
as not correct.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

ASSUMED = {
    "matrices": "normal at 1/sqrt(fan-in)",
    "embedding": "normal at 1/(embedding_multiplier sqrt(hidden_size)): "
                 "x_0 = embedding_multiplier E[ids] then has the scale of "
                 "a 1/sqrt(fan-in) projection's output; at 1/sqrt("
                 "hidden_size) a seeded tied head echoes its input token "
                 "at every position and even the fp8 control passes",
    "A_log": "log(u), u uniform on [1, 16]",
    "dt_bias": "inverse softplus of a step log-uniform on [1e-3, 1e-1]",
    "D": 1.0,
    "conv": "weights uniform on [-1/sqrt(d_conv), 1/sqrt(d_conv)], bias 0",
    "norm_scales": 1.0,
}
DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)


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
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda t: t.astype(F32), tree)


# -------------------------------------------------------------- mixers

def granite_mamba(h, p, cfg, prec="highest"):
    """One Mamba-2 mixer over whole sequences ``h`` [B, L, d]: the
    recurrence one position at a time from a zero state."""
    H, P, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], cfg["mamba_d_conv"])
    di = H * P
    B_, L = h.shape[:2]
    zxd = _ein("bld,de->ble", h, p["in_proj"], prec)
    z, xbc, dt = zxd[..., :di], zxd[..., di:2 * di + 2 * N], \
        zxd[..., 2 * di + 2 * N:]
    padded = jnp.concatenate(
        [jnp.zeros((B_, K - 1, xbc.shape[-1]), F32), xbc], axis=1)
    xbc = p["conv_b"] + sum(p["conv_w"][k] * padded[:, k:k + L]
                            for k in range(K))
    xbc = _act(jax.nn.silu(xbc), prec)
    x = xbc[..., :di].reshape(B_, L, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [B, L, H]
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))

    def step(S, t):
        x_t, B_t, C_t, dt_t, a_t = t
        S = a_t[..., None, None] * S + jnp.einsum(
            "bh,bhp,bn->bhpn", dt_t, x_t, B_t, precision=HI)
        S = _act(S, prec)
        y = jnp.einsum("bhpn,bn->bhp", S, C_t, precision=HI) \
            + p["D"][:, None] * x_t
        return S, y

    lanes = tuple(jnp.swapaxes(t, 0, 1) for t in (x, Bm, Cm, dt, a))
    _, y = jax.lax.scan(step, jnp.zeros((B_, H, P, N), F32), lanes)
    y = jnp.swapaxes(y, 0, 1).reshape(B_, L, di) * jax.nn.silu(z)
    y = _act(_rms(y, p["norm"], cfg["rms_norm_eps"]), prec)
    return _ein("ble,ed->bld", y, p["out_proj"], prec)


def granite_attention(h, p, cfg, prec="highest"):
    Hq, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    B_, L = h.shape[:2]
    q = _ein("bld,de->ble", h, p["wq"], prec).reshape(B_, L, Hq, hd)
    k = _ein("bld,de->ble", h, p["wk"], prec).reshape(B_, L, Hk, hd)
    v = _ein("bld,de->ble", h, p["wv"], prec).reshape(B_, L, Hk, hd)
    k, v = (jnp.repeat(t, Hq // Hk, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        * cfg["attention_multiplier"]
    seen = jnp.tril(jnp.ones((L, L), bool))
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    o = _act(jnp.einsum("bhqk,bkhd->bqhd", _act(probs, prec), v,
                        precision=HI), prec)
    return _ein("ble,ed->bld", o.reshape(B_, L, Hq * hd), p["wo"], prec)


def granite_mlp(h, p, prec="highest"):
    f = p["w_out"].shape[0]
    gv = jnp.einsum("bld,de->ble", _operand(h, prec),
                    _operand(p["w_in"], prec), precision=HI)
    a = _act(jax.nn.silu(gv[..., :f]) * gv[..., f:], prec)
    return _ein("blf,fd->bld", a, p["w_out"], prec)


# ------------------------------------------------------------- forward

def granite_hidden(params, ids, cfg, prec="highest"):
    """The normed last hidden state [B, L, d] of whole sequences ``ids``."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = _act(params["embed"].astype(F32)[ids] * cfg["embedding_multiplier"],
             prec)
    for i, kind in enumerate(cfg["layer_types"]):
        p = _f32(params[f"layer_{i}"])                 # one layer at a time
        h = _act(_rms(x, p["norm1"], eps), prec)
        mixer = granite_mamba if kind == "mamba" else granite_attention
        x = _act(x + r * mixer(h, p["mixer"], cfg, prec), prec)
        h = _act(_rms(x, p["norm2"], eps), prec)
        x = _act(x + r * granite_mlp(h, p["mlp"], prec), prec)
    return _act(_rms(x, params["final_norm"].astype(F32), eps), prec)


def granite_head(params, h, cfg, prec="highest"):
    """Logits [.., V] of normed hidden states h [.., d] (tied)."""
    return jnp.einsum("...d,vd->...v", _operand(h, prec),
                      _operand(params["embed"].astype(F32), prec),
                      precision=HI) / cfg["logits_scaling"]


def granite_logits(params, ids, cfg, prec="highest"):
    return granite_head(params, granite_hidden(params, ids, cfg, prec), cfg,
                        prec)


# ------------------------------------------------------------- weights

def granite_weights(key, cfg: Dict[str, Any], dtype=jnp.bfloat16):
    """Seeded weights in the layout the program takes, as ``ASSUMED``."""
    d, V, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, P, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], cfg["mamba_d_conv"])
    Hq, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    di, ch = H * P, H * P + 2 * N
    keys = iter(jax.random.split(key, 16 * (len(cfg["layer_types"]) + 1)))

    def mat(shape, fan_in):
        return (jax.random.normal(next(keys), shape, F32)
                / math.sqrt(fan_in)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    def mamba():
        step = jnp.exp(uniform((H,), *map(math.log, DT_RANGE)))
        bound = 1.0 / math.sqrt(K)
        return {"in_proj": mat((d, di + ch + H), d),
                "conv_w": uniform((K, ch), -bound, bound).astype(dtype),
                "conv_b": jnp.zeros((ch,), dtype),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform((H,), *A_RANGE)),
                "D": jnp.full((H,), ASSUMED["D"], F32),
                "norm": jnp.ones((di,), dtype),
                "out_proj": mat((di, d), di)}

    def attention():
        return {"wq": mat((d, Hq * hd), d), "wk": mat((d, Hk * hd), d),
                "wv": mat((d, Hk * hd), d), "wo": mat((Hq * hd, d), Hq * hd)}

    params = {"embed": mat((V, d), d * cfg["embedding_multiplier"] ** 2),
              "final_norm": jnp.ones((d,), dtype)}
    for i, kind in enumerate(cfg["layer_types"]):
        params[f"layer_{i}"] = {
            "norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype),
            "mixer": mamba() if kind == "mamba" else attention(),
            "mlp": {"w_in": mat((d, 2 * f), d), "w_out": mat((f, d), f)}}
    return {"params": params}
