"""Analytic FLOPs models for the benchmark configs → MFU accounting, and
the one table of device peaks every roofline number divides by.

``bench.py`` must state what fraction of the chip's peak each throughput
number represents, not just raw img/s / tok/s.  The models here are
deterministic closed forms (no device, no tracing):

- **Transformers** (BERT/GPT/TXL): the standard training-compute model —
  ``6 · N_matmul`` FLOPs per token (2 per MAC × 3 for fwd+bwd, counting
  every matmul weight: QKVO, FFN, the vocab head, TXL's relative-position
  projection) **plus** the attention quadratic ``12 · L · S_attn · d`` per
  token (QKᵀ and AV are S·d MACs each per token per layer, ×2 FLOPs/MAC
  ×3 train), which the 6N form omits.  Embedding gathers are not matmuls
  and count 0.  (Kaplan et al.'s C ≈ 6ND convention, with the attention
  term made explicit since seq/d is not small for the long-context rows.)
- **ResNets**: per-conv enumeration — each conv is ``2·K²·Cin·Cout·Hout²``
  FLOPs per image forward, training ×3 (dgrad and wgrad are each conv-
  shaped).  BN/ReLU/pool FLOPs are noise against the convs and count 0.

MFU uses the device's bf16 peak uniformly — also for the fp32 c1 row, so
every row is comparable against the same roofline (the fp32 row's MFU is
then conservative: fp32 MXU peak is lower).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one ``device_kind``."""
    bf16_flops: float           # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# What jax.devices()[0].device_kind reads on a v5e (read off the chip).
V5E = "TPU v5 lite"

# Keyed by ``jax.devices()[0].device_kind``.  A device that is not here is
# an error (device_peaks), never a default: a utilization against the wrong
# peak is a wrong number under a right name.
DEVICE_PEAKS = {
    V5E: DevicePeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2e, 819 GB/s per chip"),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """The table row for ``device_kind``; KeyError names what is missing."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in utils.flops."
            f"DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); add its "
            "published peaks with their source") from None


def mfu_pct(items_per_sec: float, flops_per_item: float,
            peak_flops: float) -> float:
    """Model-FLOPs utilization in percent of ``peak_flops`` (a
    ``DevicePeaks.bf16_flops``)."""
    return 100.0 * items_per_sec * flops_per_item / peak_flops


# --------------------------------------------------------------------------
# ResNet
# --------------------------------------------------------------------------

_RESNET_CFG = {
    # stage_sizes, bottleneck
    "resnet18": ([2, 2, 2, 2], False),
    "resnet34": ([3, 4, 6, 3], False),
    "resnet50": ([3, 4, 6, 3], True),
    "resnet101": ([3, 4, 23, 3], True),
    "resnet152": ([3, 8, 36, 3], True),
}


def _resnet_convs(stage_sizes, bottleneck, image_size):
    """[(k, cin, cout, hout)] for the torchvision-parity geometry
    (models/resnet.py: 7×7/2 stem + 3×3/2 maxpool, stages at strides
    1,2,2,2, projection shortcut on each stage's first block)."""
    convs = []
    h = image_size // 2                      # stem stride 2
    convs.append((7, 3, 64, h))
    h = -(-h // 2)                           # maxpool stride 2 (SAME)
    cin = 64
    for si, n_blocks in enumerate(stage_sizes):
        f = 64 * 2 ** si
        for b in range(n_blocks):
            s = 2 if (si > 0 and b == 0) else 1
            hout = -(-h // s)
            if bottleneck:
                convs += [(1, cin, f, h), (3, f, f, hout),
                          (1, f, 4 * f, hout)]
                cout = 4 * f
            else:
                convs += [(3, cin, f, hout), (3, f, f, hout)]
                cout = f
            if b == 0 and (s != 1 or cin != cout):
                convs.append((1, cin, cout, hout))
            cin, h = cout, hout
    return convs


def resnet_train_flops_per_image(arch: str, image_size: int,
                                 num_classes: int) -> float:
    stage_sizes, bottleneck = _RESNET_CFG[arch]
    convs = _resnet_convs(stage_sizes, bottleneck, image_size)
    fwd = sum(2.0 * k * k * cin * cout * hout * hout
              for k, cin, cout, hout in convs)
    fwd += 2.0 * 512 * (4 if bottleneck else 1) * num_classes   # fc
    return 3.0 * fwd


# --------------------------------------------------------------------------
# Transformers
# --------------------------------------------------------------------------

def transformer_train_flops_per_token(*, num_layers: int, d_model: int,
                                      d_ff: int, vocab_size: int,
                                      attn_span: int,
                                      extra_proj_per_layer: int = 0) -> float:
    """``attn_span``: sequence length each query attends over (seq for
    BERT/GPT; seq + mem_len for Transformer-XL's recurrence).
    ``extra_proj_per_layer``: extra d→d matmul params per layer beyond
    QKVO+FFN (TXL's relative-position r_net: d·d)."""
    per_layer_params = 4 * d_model * d_model + 2 * d_model * d_ff \
        + extra_proj_per_layer
    n_matmul = num_layers * per_layer_params + d_model * vocab_size
    attn = 12.0 * num_layers * attn_span * d_model
    return 6.0 * n_matmul + attn


def model_train_flops_per_token(model, seq_len: int) -> float:
    """Dispatch on the framework's model families by their config attrs."""
    if hasattr(model, "d_inner"):            # TransformerXL
        return transformer_train_flops_per_token(
            num_layers=model.num_layers, d_model=model.d_model,
            d_ff=model.d_inner, vocab_size=model.vocab_size,
            attn_span=seq_len + model.mem_len,
            extra_proj_per_layer=model.d_model * model.d_model)
    # BERT / GPT (MoE: each token still runs one expert FFN per layer under
    # top-1; top-2 doubles the FFN term — model FLOPs follow routed compute)
    ff_mult = getattr(model, "moe_top_k", 1) if getattr(
        model, "moe_experts", 0) else 1
    return transformer_train_flops_per_token(
        num_layers=model.num_layers, d_model=model.hidden_size,
        d_ff=model.intermediate_size * ff_mult, vocab_size=model.vocab_size,
        attn_span=seq_len)
