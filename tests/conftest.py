"""Test rig: 8 logical CPU devices + Pallas interpret mode.

SURVEY.md §5: multi-device semantics are tested on real XLA CPU devices via
--xla_force_host_platform_device_count=8 (the actual pjit/psum code path, not
a mock — this exceeds the reference's "need 2 physical GPUs" test gap), and
Pallas kernels run under the interpreter so kernel tests execute on CPU.
Env vars must be set before jax initializes, hence the import-time block.
"""

import os

# Overwrite (not setdefault): tests always run on the 8-logical-device CPU
# rig, whatever the shell selects.  Set APEX_TPU_TESTS=1 to run on the
# platform the env selects instead.
if not os.environ.get("APEX_TPU_TESTS"):
    os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "all-reduce-promotion" not in flags:
    # XLA CPU's all-reduce-promotion pass check-fails on the bf16 model-axis
    # all-reduces GSPMD emits inside the TP×PP partially-manual shard_map
    # (__graft_entry__._dryrun_tp_pp_train documents the crash).  Disabling
    # it keeps bf16 all-reduces in bf16 — the TPU backend's semantics (it
    # has no such pass), so the CPU rig matches the real target more
    # closely, not less.
    flags = (flags + " --xla_disable_hlo_passes=all-reduce-promotion").strip()
os.environ["XLA_FLAGS"] = flags
# The entry points place a persistent compile cache (utils/compile_cache.py);
# tests stay hermetic: no state carried between runs in .jax_cache, no
# cache-hit log lines, children included (they inherit the variable).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402

from apex_example_tpu import ops  # noqa: E402

ops.set_interpret_mode(True)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("need 8 logical devices")
    return devs[:8]


@pytest.fixture
def compile_events():
    """Recompile-regression guard (ISSUE 7): a callable mapping a
    telemetry JSONL path (or already-parsed records) to the per-function
    ``compile_event`` counts via obs.costmodel.compile_counts — tier-1
    tests assert every instrumented function's count is exactly 1, so a
    silent recompile regression (which would multiply compile time into
    the 870 s suite budget) fails loudly.

    ``counts.gate(path)`` additionally runs the CI gate itself —
    ``tools/cost_report.py PATH --fail-on-recompile`` — over the stream
    (ISSUE 8: the serve path rides the same gate as the train path), so
    the tests police the exact command CI scripts key on, not just the
    underlying counter."""
    import importlib.util

    from apex_example_tpu.obs import costmodel
    from apex_example_tpu.obs.metrics import read_jsonl

    def counts(path_or_records):
        records = path_or_records
        if isinstance(path_or_records, str):
            records = read_jsonl(path_or_records)
        return costmodel.compile_counts(records)

    def gate(path):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "cost_report", os.path.join(repo, "tools", "cost_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main([path, "--fail-on-recompile"])

    counts.gate = gate
    return counts


@pytest.fixture
def step_traced_with():
    """``with step_traced_with(xla=...)``: the serve engine's step traced
    afresh inside the block, on the kernels' XLA forms (``xla=True``) or on
    the kernels.  FORCE_XLA is read when a step is traced and is not part
    of any cache key, so the engine's cached steps are dropped on both
    sides of the pinned stretch."""
    import contextlib

    from apex_example_tpu.ops import _config
    from apex_example_tpu.serve import engine

    @contextlib.contextmanager
    def pinned(xla: bool):
        steps = (engine._slot_step, engine._draft_step)
        for step in steps:
            step.cache_clear()
        try:
            with _config.force_xla(xla):
                yield
        finally:
            for step in steps:
                step.cache_clear()

    return pinned
