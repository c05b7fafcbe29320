"""Pallas TPU kernels + XLA reference implementations.

The TPU-native counterpart of the reference's ``csrc/`` native-extension layer
(SURVEY.md §2.1 ledger).  Each op ships a Pallas kernel (the fused path used
on TPU) and an XLA reference implementation (CPU fallback + test golden).
"""

from apex_example_tpu.ops.attention import (
    attention_reference, flash_attention, flash_attention_with_lse,
    paged_latent_attention, paged_latent_attention_reference)
from apex_example_tpu.ops.layer_norm import (layer_norm,
                                             layer_norm_reference, rms_norm,
                                             rms_norm_reference)
from apex_example_tpu.ops.multi_tensor import (
    MultiTensorApply, clip_grad_norm, multi_tensor_axpby, multi_tensor_l2norm,
    multi_tensor_scale, sqsum_leaf)
from apex_example_tpu.ops.fused_optim import (
    adagrad_update_leaf, adagrad_update_leaf_reference, adam_update_leaf,
    adam_update_leaf_reference, lamb_stage1_leaf, lamb_stage2_leaf,
    novograd_update_leaf, sgd_update_leaf)
from apex_example_tpu.ops.xentropy import (softmax_cross_entropy,
                                           softmax_cross_entropy_reference)

__all__ = [
    "MultiTensorApply", "adagrad_update_leaf",
    "adagrad_update_leaf_reference", "adam_update_leaf",
    "adam_update_leaf_reference",
    "attention_reference", "flash_attention", "flash_attention_with_lse",
    "softmax_cross_entropy", "softmax_cross_entropy_reference",
    "clip_grad_norm", "lamb_stage1_leaf", "lamb_stage2_leaf", "layer_norm",
    "layer_norm_reference", "multi_tensor_axpby", "multi_tensor_l2norm",
    "multi_tensor_scale", "novograd_update_leaf", "rms_norm",
    "rms_norm_reference", "sgd_update_leaf", "sqsum_leaf",
]


def set_interpret_mode(enable: bool) -> None:
    """Run all Pallas kernels in interpreter mode (CPU tests)."""
    from apex_example_tpu.ops import _config
    _config.INTERPRET = bool(enable)
