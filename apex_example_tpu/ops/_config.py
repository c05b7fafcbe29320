"""Kernel-dispatch configuration shared by all ops."""

import contextlib

import jax

INTERPRET = False  # run Pallas kernels in interpreter mode (CPU tests)

# Force the XLA reference implementations even on TPU.  The GSPMD tensor-
# parallel path (engine.make_gspmd_train_step) sets this: pallas_call custom
# calls are opaque to the SPMD partitioner, so inside a plain jit over a
# multi-axis mesh they would be wrapped in gather/replicate instead of
# partitioned — the XLA-native forms partition cleanly.  shard_map paths
# (DP/ZeRO/ring) are unaffected: there the kernels run per-shard by
# construction and keep the pallas dispatch.
FORCE_XLA = False


def set_force_xla(value: bool) -> None:
    global FORCE_XLA
    FORCE_XLA = bool(value)


def get_force_xla() -> bool:
    return FORCE_XLA


@contextlib.contextmanager
def force_xla(value: bool = True):
    """Scoped FORCE_XLA pin, restoring the prior value on exit.

    The flag is process-global and read at TRACE time: anything else that
    first-traces inside the pinned window (another thread, an interleaved
    jit) compiles with this dispatch and caches it — the same caveat as
    train.py's run-long set_force_xla(True), scoped smaller here."""
    global FORCE_XLA
    prev = FORCE_XLA
    FORCE_XLA = bool(value)
    try:
        yield
    finally:
        FORCE_XLA = prev


def interpret() -> bool:
    return INTERPRET


def use_pallas() -> bool:
    """Pallas path on TPU (or under the interpreter); XLA reference
    implementations elsewhere."""
    if FORCE_XLA:
        return False
    if INTERPRET:
        return True
    return jax.default_backend() == "tpu"


def use_pallas_for(*operands) -> bool:
    """Like use_pallas, but under the interpreter (CPU tests) falls back to
    the XLA reference path when an operand varies over a shard_map mesh axis:
    the HLO interpreter evaluates the kernel body with vma-typed values and
    trips on mixed varying/invariant arithmetic.  Real mosaic lowering erases
    vma at the pallas_call boundary, so TPU always keeps the kernel."""
    if FORCE_XLA:
        return False
    if INTERPRET:
        return not any(jax.typeof(x).vma for x in operands)
    return jax.default_backend() == "tpu"
