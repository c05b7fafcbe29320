"""Plain reference for ResNet-50 v1.5 (He et al. 2015; stride on the 3x3 of
each bottleneck, as torchvision builds it), training mode.

Straightforward ``jax.numpy``/``lax`` in float32 with every convolution and
product at ``highest`` precision; batch statistics over the whole batch.
Each bottleneck is under ``jax.checkpoint`` only so that 256 images in
float32 fit one chip beside nothing else; that changes no value.  It
imports nothing of the program; weights are made here from the seed in the
nested layout the system under test accepts.

Departure from torchvision, followed because the system under test computes
it: the strided convolutions and the max-pool pad as XLA's ``SAME`` does
(2 before and 3 after for the 7x7 stem on an even side) where torch pads
3 and 3.

``prec``: ``highest`` is the reference; ``bf16``/``fp8`` are controls (see
reference/transformer.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
STAGES = (3, 4, 6, 3)
EPS = 1e-5


def _act(x, prec):
    if prec == "highest":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _operand(x, prec):
    if prec == "fp8":
        s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return _act(x, prec)


def _conv(x, w, stride, prec):
    return _act(jax.lax.conv_general_dilated(
        _operand(x, prec), _operand(w, prec), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI), prec)


def _bn(x, p, prec):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return _act((x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"]
                + p["bias"], prec)


def _bottleneck(x, p, stride, prec):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"], 1, prec),
                        p["SyncBatchNorm_0"], prec))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride, prec),
                        p["SyncBatchNorm_1"], prec))
    y = _bn(_conv(y, p["Conv_2"]["kernel"], 1, prec),
            p["SyncBatchNorm_2"], prec)
    if "downsample_conv" in p:
        x = _bn(_conv(x, p["downsample_conv"]["kernel"], stride, prec),
                p["downsample_bn"], prec)
    return jax.nn.relu(y + x)


def resnet50_logits(params, images, cfg, prec="highest"):
    del cfg
    x = _conv(images, params["conv_init"]["kernel"], 2, prec)
    x = jax.nn.relu(_bn(x, params["bn_init"], prec))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    n = 0
    for stage, blocks in enumerate(STAGES):
        for j in range(blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            block = jax.checkpoint(_bottleneck, static_argnums=(2, 3))
            x = block(x, params[f"Bottleneck_{n}"], stride, prec)
            n += 1
    x = jnp.mean(x, (1, 2))
    return _act(jnp.matmul(_operand(x, prec),
                           _operand(params["fc"]["kernel"], prec),
                           precision=HI) + params["fc"]["bias"], prec)


def resnet50_weights(key, cfg):
    """He-normal (fan-out) convolutions as torchvision initialises them,
    BatchNorm scale near one and offset near zero, a small classifier."""
    n = [0]

    def rnd(shape, std, mean=0.0):
        n[0] += 1
        return mean + std * jax.random.normal(
            jax.random.fold_in(key, n[0]), shape, jnp.float32)

    conv = lambda k, i, o: {"kernel": rnd((k, k, i, o),
                                          (2.0 / (k * k * o)) ** 0.5)}
    bn = lambda c: {"scale": rnd((c,), 0.02, 1.0), "bias": rnd((c,), 0.02)}
    stats = lambda c: {"mean": jnp.zeros((c,), jnp.float32),
                       "var": jnp.ones((c,), jnp.float32)}
    p = {"conv_init": conv(7, 3, 64), "bn_init": bn(64)}
    s = {"bn_init": stats(64)}
    cin, idx = 64, 0
    for stage, blocks in enumerate(STAGES):
        f = 64 * 2 ** stage
        for j in range(blocks):
            b = {"Conv_0": conv(1, cin, f), "SyncBatchNorm_0": bn(f),
                 "Conv_1": conv(3, f, f), "SyncBatchNorm_1": bn(f),
                 "Conv_2": conv(1, f, 4 * f), "SyncBatchNorm_2": bn(4 * f)}
            bs = {"SyncBatchNorm_0": stats(f), "SyncBatchNorm_1": stats(f),
                  "SyncBatchNorm_2": stats(4 * f)}
            if j == 0:
                b["downsample_conv"] = conv(1, cin, 4 * f)
                b["downsample_bn"] = bn(4 * f)
                bs["downsample_bn"] = stats(4 * f)
            p[f"Bottleneck_{idx}"], s[f"Bottleneck_{idx}"] = b, bs
            cin, idx = 4 * f, idx + 1
    p["fc"] = {"kernel": rnd((cin, cfg["num_classes"]), 0.01),
               "bias": rnd((cfg["num_classes"],), 0.01)}
    return {"params": p, "batch_stats": s}


def resnet50_loss_sum(params, batch, cfg, prec="highest"):
    images, labels = batch
    logp = jax.nn.log_softmax(resnet50_logits(params, images, cfg, prec))
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


def resnet50_loss_denom(batch):
    return jnp.asarray(batch[1].shape[0], jnp.float32)


def resnet50_rows(batch, lo, hi):
    return batch[0][lo:hi], batch[1][lo:hi]


# Batch statistics couple the rows: the whole batch goes through at once.
resnet50_row_blocks = False
