"""Auxiliary-subsystem tests (SURVEY.md §6):

- psum determinism: the race-detection analog.  XLA/jit is data-race-free
  by construction; the observable contract is bitwise-identical results for
  identical (seed, data, devices) — which the reference's
  ddp_race_condition_test can only probe stochastically.
- fault injection: kill a training process mid-run (SIGKILL, no cleanup),
  resume from its checkpoint, assert step continuity — the reference
  family's recovery contract is exactly relaunch+resume (no elastic).
"""

import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_example_tpu import amp
from apex_example_tpu.data import image_batch
from apex_example_tpu.engine import (create_train_state,
                                     make_sharded_train_step)
from apex_example_tpu.models import resnet18
from apex_example_tpu.optim import FusedSGD
from apex_example_tpu.parallel import make_data_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_steps(devices, n_steps=5, seed=0):
    policy, scaler = amp.initialize("O2")
    model = resnet18(num_classes=8, small_stem=True, num_filters=8,
                     bn_axis_name="data")
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    mesh = make_data_mesh(devices=devices)
    x, y = image_batch(jnp.asarray(0), batch_size=16, image_size=16,
                       channels=3, num_classes=8, seed=seed)
    state = create_train_state(jax.random.PRNGKey(seed), model, opt, x[:1],
                               policy, scaler)
    step = make_sharded_train_step(mesh, model, opt, policy, donate=False)
    losses = []
    for i in range(n_steps):
        batch = image_batch(jnp.asarray(i), batch_size=16, image_size=16,
                            channels=3, num_classes=8, seed=seed)
        state, metrics = step(state, batch)
        losses.append(np.asarray(metrics["loss"]))
    return np.stack(losses), state


def test_psum_determinism_bitwise(devices8):
    """Same seed, same 8-device mesh, two runs → bitwise-equal losses and
    params (SURVEY.md §6 race-detection row)."""
    l1, s1 = _run_steps(devices8)
    l2, s2 = _run_steps(devices8)
    np.testing.assert_array_equal(l1, l2)      # bitwise, not allclose
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s1.params, s2.params)


def _spawn_trainer(ckpt, extra, env):
    # bert_tiny, not resnet18: the kill/resume contract under test is
    # arch-agnostic (checkpoint step continuity + AMP O2 state survival),
    # and the tiny-LM step compiles several times faster — this test is
    # two cold subprocess trainers, the suite's single largest cost.
    return subprocess.Popen(
        [sys.executable, "train.py", "--arch", "bert_tiny", "--seq-len",
         "16", "--opt", "adam", "--opt-level", "O2", "--epochs", "3",
         "--steps-per-epoch", "3", "--batch-size", "8", "--print-freq",
         "1", "--checkpoint-dir", ckpt] + extra,
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, bufsize=1)


def test_fault_injection_kill_and_resume(tmp_path):
    """SIGKILL mid-run, then resume: training continues from the saved
    step with loss continuity (SURVEY.md §6 failure-detection row)."""
    ckpt = str(tmp_path / "ck")
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})

    # Phase 1: run until the first checkpoint lands, then SIGKILL (the
    # harshest failure mode: no atexit, no finally blocks).
    p = _spawn_trainer(ckpt, [], env)
    saw_save, out1 = False, []
    deadline = time.time() + 540
    for line in p.stdout:
        out1.append(line)
        if "saved checkpoint at step" in line:
            saw_save = True
            break
        if time.time() > deadline:
            break
    assert saw_save, "".join(out1)
    os.kill(p.pid, signal.SIGKILL)
    p.wait(timeout=60)

    # Phase 2: resume from the murdered run's checkpoint.
    p2 = _spawn_trainer(ckpt, ["--resume", ckpt], env)
    out2, _ = p2.communicate(timeout=540)
    assert p2.returncode == 0, out2
    assert "resumed from step 3 (epoch 1)" in out2, out2
    # It continued (epoch 1 and 2 ran, a later checkpoint was written).
    assert "saved checkpoint at step 9" in out2, out2


def test_async_checkpoint_save_restore(tmp_path):
    """--async-checkpoint semantics: save(wait=False) returns immediately,
    wait_until_finished joins the background write, restore round-trips."""
    import jax
    import jax.numpy as jnp
    from apex_example_tpu import amp
    from apex_example_tpu.engine import create_train_state, make_train_step
    from apex_example_tpu.models.resnet import BasicBlock, ResNet
    from apex_example_tpu.optim import FusedSGD
    from apex_example_tpu.utils.checkpoint import CheckpointManager

    policy, scaler = amp.initialize("O0")
    model = ResNet(stage_sizes=[1], block_cls=BasicBlock, num_classes=4,
                   num_filters=8, small_stem=True)
    opt = FusedSGD(lr=0.1)
    x = jnp.ones((4, 16, 16, 3))
    y = jnp.zeros((4,), jnp.int32)
    state = create_train_state(jax.random.PRNGKey(0), model, opt, x[:1],
                               policy, scaler)
    step = jax.jit(make_train_step(model, opt, policy))
    state, _ = step(state, (x, y))

    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(state, wait=False)          # async: returns before IO lands
    state, _ = step(state, (x, y))       # training continues meanwhile
    mgr.wait_until_finished()
    assert mgr.latest_step() == 1

    fresh = create_train_state(jax.random.PRNGKey(1), model, opt, x[:1],
                               policy, scaler)
    restored = mgr.restore(fresh)
    assert int(restored.step) == 1
    mgr.close()


def test_ddp_resume_through_train_cli(tmp_path, devices8):
    """Resume under a mesh: orbax restores INTO the template's shardings, so
    a single-device-committed template used to make the sharded step raise
    'incompatible devices' on the first post-resume step (found by driving
    train.py end to end; utils.checkpoint.restore_under_mesh is the fix)."""
    import train as train_mod
    ck = str(tmp_path / "ck")
    base = ["--arch", "resnet18", "--opt-level", "O2", "--sync_bn",
            "--steps-per-epoch", "2", "--batch-size", "16",
            "--print-freq", "1"]
    assert train_mod.main(base + ["--epochs", "1",
                                  "--checkpoint-dir", ck]) == 0
    assert train_mod.main(base + ["--epochs", "2", "--resume", ck]) == 0


def test_zero_resume_through_train_cli(tmp_path, devices8):
    """ZeRO resume: restore_under_mesh places the optimizer state per the
    ZeRO optimizer's own state_spec (data-sharded), so the restored shards
    land where the sharded step expects them."""
    import train as train_mod
    ck = str(tmp_path / "ck")
    base = ["--arch", "bert_tiny", "--zero", "--opt", "adam",
            "--opt-level", "O0", "--steps-per-epoch", "2",
            "--batch-size", "8", "--seq-len", "16", "--print-freq", "1"]
    assert train_mod.main(base + ["--epochs", "1",
                                  "--checkpoint-dir", ck]) == 0
    assert train_mod.main(base + ["--epochs", "2", "--resume", ck]) == 0


def test_cp_resume_through_train_cli(tmp_path, devices8):
    """Context-parallel resume: CP state is replicated, so the replicated
    restore_under_mesh template is its restore target too."""
    import train as train_mod
    from apex_example_tpu.transformer import parallel_state
    ck = str(tmp_path / "ck")
    base = ["--arch", "bert_tiny", "--context-parallel", "4",
            "--opt", "adam", "--opt-level", "O0", "--steps-per-epoch", "2",
            "--batch-size", "8", "--seq-len", "16", "--print-freq", "1"]
    try:
        assert train_mod.main(base + ["--epochs", "1",
                                      "--checkpoint-dir", ck]) == 0
        assert train_mod.main(base + ["--epochs", "2", "--resume", ck]) == 0
    finally:
        parallel_state.set_mesh(None)


# ---------------------------------------------------------------------------
# MFU accounting (utils/flops.py): the analytic FLOPs
# models bench.py's mfu_pct field is computed from.
# ---------------------------------------------------------------------------

def test_resnet50_flops_matches_literature():
    """torchvision ResNet-50 @224 is 4.09 GMACs forward — the per-conv
    enumeration must land on 2x that (±2% for fc/stem conventions)."""
    from apex_example_tpu.utils.flops import resnet_train_flops_per_image
    train = resnet_train_flops_per_image("resnet50", 224, 1000)
    fwd = train / 3.0
    assert abs(fwd - 8.2e9) / 8.2e9 < 0.02
    # resnet18 @224: 1.82 GMACs forward
    fwd18 = resnet_train_flops_per_image("resnet18", 224, 1000) / 3.0
    assert abs(fwd18 - 3.64e9) / 3.64e9 < 0.02


def test_transformer_flops_model():
    from apex_example_tpu.models.bert import bert_base
    from apex_example_tpu.models.gpt import gpt_base
    from apex_example_tpu.models.transformer_xl import transformer_xl_base
    from apex_example_tpu.utils.flops import model_train_flops_per_token

    # BERT-base: 6*N_matmul dominates; N_matmul = 12*(4*768^2 + 2*768*3072)
    # + 768*30522 head = 108.4M -> ~650 MFLOPs/token + attention term.
    bert = model_train_flops_per_token(bert_base(), 128)
    assert 6.3e8 < bert < 7.0e8
    # GPT-base shares the geometry; same ballpark.
    gpt = model_train_flops_per_token(gpt_base(), 128)
    assert abs(gpt - bert) / bert < 0.05
    # attention quadratic: span doubles => flops strictly increase
    assert model_train_flops_per_token(bert_base(), 512) > bert
    # TXL: recurrence widens the attention span by mem_len
    txl = model_train_flops_per_token(transformer_xl_base(), 192)
    assert txl > 0
    # MoE top-2 routes each token through two expert FFNs
    m1 = model_train_flops_per_token(
        bert_base(moe_experts=8, moe_top_k=1), 128)
    m2 = model_train_flops_per_token(
        bert_base(moe_experts=8, moe_top_k=2), 128)
    assert m2 > m1


def test_mfu_pct_and_bench_emit(monkeypatch):
    import io
    import json
    from contextlib import redirect_stdout

    from apex_example_tpu.utils.flops import V5E, device_peaks, mfu_pct
    v5e = device_peaks(V5E)
    # rate * flops == peak => 100%
    assert mfu_pct(1000.0, v5e.bf16_flops / 1000.0, v5e.bf16_flops) == 100.0

    import bench
    # main() takes the row from the device it finds (and refuses the CPU);
    # _emit only divides by it.
    monkeypatch.setattr(bench, "_PEAKS", v5e)
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench._emit("m", 2000.0, "images/sec/chip", 0.5,
                    flops_per_item=24.5e9)
    rec = json.loads(buf.getvalue())
    assert rec["mfu_pct"] == round(100.0 * 2000 * 24.5e9 / 197e12, 2)
    # without a flops model the field is absent, not null
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench._emit("m", 1.0, "u", None)
    assert "mfu_pct" not in json.loads(buf.getvalue())


def test_device_peaks_table():
    """One table keyed by device_kind: the v5e row carries the published
    figures (Google Cloud 'TPU v5e': 197 TFLOP/s bf16, 16 GB HBM at
    819 GB/s) with their source, and a device that is not in the table is
    an error naming it — never a default peak."""
    from apex_example_tpu.utils.flops import (DEVICE_PEAKS, V5E,
                                              device_peaks)
    v5e = device_peaks(V5E)
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s, v5e.hbm_bytes) \
        == (197e12, 819e9, 16e9)
    assert all(row.source for row in DEVICE_PEAKS.values())
    with pytest.raises(KeyError, match="TPU v99"):
        device_peaks("TPU v99")
    with pytest.raises(KeyError, match="'cpu'"):
        device_peaks("cpu")


def test_bench_refuses_non_tpu_and_unknown_device(monkeypatch, capsys):
    """bench.main's gate: a non-TPU backend, or a TPU whose device_kind is
    not in the peaks table, is a non-zero exit naming the cause — after the
    one stderr header, before any JSON line."""
    import types

    import bench

    def found(platform, kind):
        monkeypatch.setattr(bench.jax, "devices", lambda: [
            types.SimpleNamespace(platform=platform, device_kind=kind)])

    found("cpu", "cpu")
    with pytest.raises(SystemExit, match="'cpu'"):
        bench._require_tpu()
    found("tpu", "TPU v99")
    with pytest.raises(SystemExit, match="TPU v99"):
        bench._require_tpu()
    found("tpu", "TPU v5 lite")
    assert bench._require_tpu().bf16_flops == 197e12
    out, err = capsys.readouterr()
    assert out == "" and err.count("bench: jax ") == 3
    assert "device_kind 'TPU v5 lite'" in err
