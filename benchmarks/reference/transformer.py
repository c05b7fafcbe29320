"""Plain reference for the post-LN transformer block: BERT (Devlin et al.
2018, masked LM) and GPT-1 (Radford et al. 2018, causal LM).

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; no kernel, no cache, no batching tricks.  It imports
nothing of the program.  Weights are made here from the seed, in the nested
layout the system under test accepts (a layout is a naming, not a value),
and handed to the program; the reference never takes a value back.

Departures from the published models, followed because the system under
test computes them (each is also listed in the configuration file):
BERT has no token-type embedding here; LayerNorm's epsilon is the
configuration's ``layer_norm_eps``; GPT-1 gets a final LayerNorm and an
output bias that the published model lacks.

``prec`` selects the arithmetic: ``highest`` is the reference; ``bf16`` and
``fp8`` are *controls* of "How correct is decided": the same mathematics
with every operand and activation rounded to bfloat16, and for ``fp8`` the
matrix operands further to float8_e4m3 under a per-tensor scale (the casts
are differentiated as JAX differentiates them: cotangents pass through the
same types, unscaled).  A control has to come out as not correct.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------- arithmetic

def _act(x, prec):
    """Round an activation to the control's storage type."""
    if prec == "highest":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _operand(x, prec):
    if prec == "fp8":
        s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return _act(x, prec)


def _mm(a, b, prec):
    return _act(jnp.matmul(_operand(a, prec), _operand(b, prec),
                           precision=HI), prec)


def _layer_norm(x, p, eps, prec):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return _act((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
                + p["bias"], prec)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _dense(x, p, prec):
    return _act(_mm(x, p["kernel"], prec) + p["bias"], prec)


def _block(x, p, heads, causal, eps, prec):
    b, s, d = x.shape
    hd = d // heads
    a = p["attention"]
    split = lambda t: t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    q = split(_dense(x, a["query"], prec))
    k = split(_dense(x, a["key"], prec))
    v = split(_dense(x, a["value"], prec))
    scores = _mm(q, k.transpose(0, 1, 3, 2), prec) / math.sqrt(hd)
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -1e9)
    probs = _act(jax.nn.softmax(scores, axis=-1), prec)
    ctx = _mm(probs, v, prec).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = _layer_norm(_act(x + _dense(ctx, a["output"], prec), prec),
                    p["attention_ln"], eps, prec)
    h = _act(_gelu(_dense(x, p["intermediate"], prec)), prec)
    return _layer_norm(_act(x + _dense(h, p["output"], prec), prec),
                       p["output_ln"], eps, prec)


def _trunk(params, ids, cfg, causal, prec):
    s = ids.shape[1]
    x = params["word_embeddings"]["embedding"][ids] \
        + params["position_embeddings"]["embedding"][jnp.arange(s)][None]
    eps = cfg["layer_norm_eps"]
    x = _layer_norm(_act(x, prec), params["embeddings_ln"], eps, prec)
    for i in range(cfg["num_layers"]):
        x = _block(x, params[f"layer_{i}"], cfg["num_heads"], causal, eps,
                   prec)
    return x


def bert_logits(params, ids, cfg, prec="highest"):
    x = _trunk(params, ids, cfg, False, prec)
    x = _act(_gelu(_dense(x, params["mlm_dense"], prec)), prec)
    x = _layer_norm(x, params["mlm_ln"], cfg["layer_norm_eps"], prec)
    return _mm(x, params["word_embeddings"]["embedding"].T, prec) \
        + params["mlm_bias"]


def gpt_logits(params, ids, cfg, prec="highest"):
    x = _trunk(params, ids, cfg, True, prec)
    x = _layer_norm(x, params["final_ln"], cfg["layer_norm_eps"], prec)
    return _mm(x, params["word_embeddings"]["embedding"].T, prec) \
        + params["lm_bias"]


# -------------------------------------------------------------- weights

def _weights(key, cfg, head: Dict[str, Any]):
    """Seeded weights: normal(0.02) matrices and embeddings (the published
    initialisation of both models), biases and LayerNorm offsets small but
    not zero, so that no path multiplies by an exact zero or one.

    ``cfg["dense_init"] == "fan_in"`` draws the dense matrices at
    1/sqrt(fan-in) instead.  At 0.02 every sublayer adds little to the
    residual stream, so a decoder with a tied head echoes its input token
    with a margin of 1.5 logits or more, and no precision, however low,
    changes a served token: a comparison of served tokens would see nothing.
    At fan-in scale the stream is mixed as a trained model's is and first
    places lie 0.01-0.1 apart (PERF.md section 4)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    fan_in = cfg.get("dense_init") == "fan_in"
    n = [0]

    def rnd(shape, std=0.02, mean=0.0):
        n[0] += 1
        return mean + std * jax.random.normal(
            jax.random.fold_in(key, n[0]), shape, jnp.float32)

    dense = lambda i, o: {
        "kernel": rnd((i, o), std=i ** -0.5 if fan_in else 0.02),
        "bias": rnd((o,))}
    ln = lambda: {"scale": rnd((d,), mean=1.0), "bias": rnd((d,))}
    p = {"word_embeddings": {"embedding": rnd((cfg["vocab_size"], d))},
         "position_embeddings": {
             "embedding": rnd((cfg["max_position"], d))},
         "embeddings_ln": ln()}
    for i in range(cfg["num_layers"]):
        p[f"layer_{i}"] = {
            "attention": {"query": dense(d, d), "key": dense(d, d),
                          "value": dense(d, d), "output": dense(d, d)},
            "attention_ln": ln(),
            "intermediate": dense(d, f), "output": dense(f, d),
            "output_ln": ln()}
    for name, kind in head.items():
        p[name] = {"ln": ln, "dense": lambda: dense(d, d),
                   "vocab_bias": lambda: rnd((cfg["vocab_size"],))}[kind]()
    return p


def bert_weights(key, cfg):
    return {"params": _weights(key, cfg, {
        "mlm_dense": "dense", "mlm_ln": "ln", "mlm_bias": "vocab_bias"})}


def gpt_weights(key, cfg):
    return {"params": _weights(key, cfg, {
        "final_ln": "ln", "lm_bias": "vocab_bias"})}


# ---------------------------------------------------- training objective

def bert_loss_sum(params, batch, cfg, prec="highest"):
    """Sum over this block of rows of the masked positions' cross-entropy;
    the caller divides by the whole batch's mask count (``loss_denom``)."""
    ids, (labels, weights) = batch
    logits = bert_logits(params, ids, cfg, prec)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(ce * weights)


def bert_loss_denom(batch):
    return jnp.maximum(jnp.sum(batch[1][1]), 1.0)


def bert_rows(batch, lo, hi):
    ids, (labels, weights) = batch
    return ids[lo:hi], (labels[lo:hi], weights[lo:hi])


bert_row_blocks = True      # rows are independent: gradients add by blocks
