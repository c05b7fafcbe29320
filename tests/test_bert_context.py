"""Context-parallel BERT training (workloads.make_bert_cp_train_step;
train.py --context-parallel): ring attention over a ('data', 'context')
mesh driving the full MLM train step — the long-context training path (no
reference analog; SURVEY.md §3.2 CP row).

The CP model's param tree is identical to the dense one (the ring branch
reuses the same query/key/value/output projections), so tests initialize
via the dense twin and pin trajectory equality.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from apex_example_tpu import amp
from apex_example_tpu.data import mlm_batch
from apex_example_tpu.engine import create_train_state, make_train_step
from apex_example_tpu.models.bert import bert_tiny
from apex_example_tpu.optim import FusedAdam, FusedSGD
from apex_example_tpu.workloads import make_bert_cp_train_step, mlm_loss

B, L = 4, 32      # context=4 -> local seq 8


def _batch(i, vocab):
    ids, lab, w = mlm_batch(jnp.asarray(i, jnp.int32), batch_size=B,
                            seq_len=L, vocab_size=vocab,
                            mask_token_id=vocab - 1, seed=0)
    return ids, (lab, w)


def test_cp_train_matches_dense(devices8):
    """30-step LOCKSTEP run on a (data=2, context=4) mesh vs dense
    single-device (VERDICT r3 item 7: 3 steps was a smoke test, not a
    trajectory): the ring attention, the shard-offset position embeddings,
    and the globally normalized MLM loss must agree at every step, with
    tolerances that only absorb fp32 reduction-order noise (growing
    mildly as the trajectories compound)."""
    mesh = Mesh(np.asarray(devices8).reshape(2, 4), ("data", "context"))
    policy, scaler = amp.initialize("O0")
    dense = bert_tiny()
    cp_model = bert_tiny(context_parallel=True)
    V = dense.vocab_size
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)

    sample = _batch(0, V)[0][:1]
    state_d = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                 sample, policy, scaler)
    step_d = jax.jit(make_train_step(dense, opt(), policy, loss_fn=mlm_loss,
                                     compute_accuracy=False))
    state_c = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                 sample, policy, scaler)
    step_c = make_bert_cp_train_step(mesh, cp_model, opt(), policy,
                                     donate=False)
    for i in range(30):
        b = _batch(i, V)
        state_d, m_d = step_d(state_d, b)
        state_c, m_c = step_c(state_c, b)
        np.testing.assert_allclose(
            float(m_d["loss"]), float(m_c["loss"]),
            rtol=3e-5 * (1 + i / 3),
            err_msg=f"loss diverged at step {i}")
    for a, b in zip(jax.tree_util.tree_leaves(state_d.params),
                    jax.tree_util.tree_leaves(state_c.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=3e-5)


def test_cp_ulysses_train_matches_dense(devices8):
    """BERT CP with the all-to-all (Ulysses) attention program == dense:
    full sequence per device on H/N head shards, exact attention — the
    bidirectional counterpart of the GPT ulysses test."""
    mesh = Mesh(np.asarray(devices8).reshape(2, 4), ("data", "context"))
    policy, scaler = amp.initialize("O0")
    dense = bert_tiny()
    cp_model = bert_tiny(context_parallel=True, cp_mode="ulysses")
    V = dense.vocab_size
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)
    sample = _batch(0, V)[0][:1]
    state_d = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                 sample, policy, scaler)
    step_d = jax.jit(make_train_step(dense, opt(), policy,
                                     loss_fn=mlm_loss,
                                     compute_accuracy=False))
    state_c = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                 sample, policy, scaler)
    step_c = make_bert_cp_train_step(mesh, cp_model, opt(), policy,
                                     donate=False)
    for i in range(30):
        b = _batch(i, V)
        state_d, m_d = step_d(state_d, b)
        state_c, m_c = step_c(state_c, b)
        np.testing.assert_allclose(float(m_d["loss"]), float(m_c["loss"]),
                                   rtol=3e-5 * (1 + i / 3))
    for a, b in zip(jax.tree_util.tree_leaves(state_d.params),
                    jax.tree_util.tree_leaves(state_c.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=3e-5)


def test_cp_eval_matches_dense(devices8):
    """Sequence-sharded eval (workloads.make_bert_cp_eval_step) returns the
    dense eval's loss AND masked accuracy on the same params — the ring
    forward and the psum-normalized metrics are exact restatements."""
    from apex_example_tpu.workloads import (make_bert_cp_eval_step,
                                            make_bert_eval_step)
    mesh = Mesh(np.asarray(devices8).reshape(2, 4), ("data", "context"))
    policy, scaler = amp.initialize("O0")
    dense = bert_tiny()
    cp_model = bert_tiny(context_parallel=True)
    V = dense.vocab_size
    state = create_train_state(jax.random.PRNGKey(0), dense,
                               FusedAdam(lr=1e-3), _batch(0, V)[0][:1],
                               policy, scaler)
    ev_d = jax.jit(make_bert_eval_step(dense))
    ev_c = make_bert_cp_eval_step(mesh, cp_model)
    for i in range(2):
        b = _batch(100 + i, V)
        md, mc = ev_d(state.params, b), ev_c(state.params, b)
        np.testing.assert_allclose(float(md["loss"]), float(mc["loss"]),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(md["masked_acc"]),
                                   float(mc["masked_acc"]), rtol=1e-5)


def test_cp_grad_accum_matches_dense(devices8):
    """--grad-accum under CP: K local microbatches with per-microbatch
    psum-normalized losses equal dense K-microbatch accumulation on the
    SAME example grouping.  CP's microbatch m holds each data-shard's m-th
    local slice (examples {m, local+m, ...}) while the dense engine takes
    contiguous blocks, so the dense side gets the batch permuted into CP's
    grouping — grad accumulation is a mean over microbatch losses, which
    depends on the grouping whenever per-example masked counts differ."""
    mesh = Mesh(np.asarray(devices8).reshape(2, 4), ("data", "context"))
    policy, scaler = amp.initialize("O0")
    dense = bert_tiny()
    cp_model = bert_tiny(context_parallel=True)
    V = dense.vocab_size
    K, data = 2, 2
    local = B // data
    perm = np.array([s * local + m * (local // K) + j
                     for m in range(K) for s in range(data)
                     for j in range(local // K)])
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)
    sample = _batch(0, V)[0][:1]
    state_d = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                 sample, policy, scaler)
    step_d = jax.jit(make_train_step(dense, opt(), policy, loss_fn=mlm_loss,
                                     compute_accuracy=False, grad_accum=K))
    state_c = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                 sample, policy, scaler)
    step_c = make_bert_cp_train_step(mesh, cp_model, opt(), policy,
                                     donate=False, grad_accum=K)
    for i in range(10):
        ids, (lab, w) = _batch(i, V)
        state_d, m_d = step_d(state_d, (ids[perm], (lab[perm], w[perm])))
        state_c, m_c = step_c(state_c, (ids, (lab, w)))
        np.testing.assert_allclose(float(m_d["loss"]), float(m_c["loss"]),
                                   rtol=3e-5)
    for a, b in zip(jax.tree_util.tree_leaves(state_d.params),
                    jax.tree_util.tree_leaves(state_c.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_cp_o2_bf16_trains(devices8):
    mesh = Mesh(np.asarray(devices8).reshape(2, 4), ("data", "context"))
    policy, scaler = amp.initialize("O2")
    md = amp.module_dtypes(policy)
    kw = dict(dtype=md.compute, param_dtype=md.param, ln_dtype=md.ln_io,
              softmax_dtype=md.softmax)
    dense = bert_tiny(**kw)
    cp_model = bert_tiny(context_parallel=True, **kw)
    V = dense.vocab_size
    opt = FusedAdam(lr=3e-3)
    state = create_train_state(jax.random.PRNGKey(0), dense, opt,
                               _batch(0, V)[0][:1], policy, scaler)
    step = make_bert_cp_train_step(mesh, cp_model, opt, policy,
                                   donate=False)
    # Overfit ONE batch: per-step losses on fresh random batches are too
    # noisy at this tiny scale for a monotonicity check.
    b = _batch(0, V)
    losses = []
    for _ in range(6):
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.7 * losses[0], losses


def test_cp_tp_train_matches_dense(devices8):
    """CP×TP composition: ring attention over 'context' with the GSPMD TP
    layers on a still-automatic 'model' axis (the same partially-manual
    shard_map form as TP×PP) — trajectory matches dense and the params
    keep their model-axis sharding across steps (the step pins its output
    shardings; without that the compiler may hand updated params back
    replicated)."""
    from apex_example_tpu.engine import gspmd_state_shardings
    from apex_example_tpu.transformer import parallel_state
    mesh = parallel_state.initialize_model_parallel(
        tensor_parallel=2, context_parallel=2, devices=devices8)
    try:
        policy, scaler = amp.initialize("O0")
        dense = bert_tiny()
        tp_model = bert_tiny(tensor_parallel=True)
        cp_tp_model = bert_tiny(tensor_parallel=True, context_parallel=True)
        V = dense.vocab_size
        opt = lambda: FusedSGD(lr=0.05, momentum=0.9)
        sample = _batch(0, V)[0][:1]
        state_d = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                     sample, policy, scaler)
        step_d = jax.jit(make_train_step(dense, opt(), policy,
                                         loss_fn=mlm_loss,
                                         compute_accuracy=False))
        # Dense init, placed into the TP metadata shardings.
        state_c = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                     sample, policy, scaler)
        sh = gspmd_state_shardings(mesh, tp_model, opt(), sample, policy)
        state_c = jax.device_put(state_c, sh)
        step_c = make_bert_cp_train_step(mesh, cp_tp_model, opt(), policy,
                                         donate=False, state_shardings=sh)
        for i in range(30):
            b = _batch(i, V)
            state_d, m_d = step_d(state_d, b)
            state_c, m_c = step_c(state_c, b)
            np.testing.assert_allclose(float(m_d["loss"]),
                                       float(m_c["loss"]), rtol=3e-5 * (1 + i / 3))
        for a, b in zip(jax.tree_util.tree_leaves(state_d.params),
                        jax.tree_util.tree_leaves(state_c.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=3e-5)
        qk = state_c.params["layer_0"]["attention"]["query"]["kernel"]
        assert qk.addressable_shards[0].data.shape == (64, 32), \
            "query kernel lost its model-axis sharding"
    finally:
        parallel_state.set_mesh(None)


def test_train_py_cli_cp_tp(tmp_path, devices8, capsys):
    """--context-parallel 2 --tensor-parallel 2 trains, evals
    (sequence-sharded ring eval on the TP model), accumulates gradients,
    checkpoints, and resumes (the tp>1 template is gspmd-placed, so the
    direct-restore branch must land the shards back where the step expects
    them)."""
    import train as train_mod
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.transformer import parallel_state
    ck = str(tmp_path / "ck")
    base = ["--arch", "bert_tiny", "--context-parallel", "2",
            "--tensor-parallel", "2", "--batch-size", str(B),
            "--seq-len", str(L), "--steps-per-epoch", "2",
            "--opt", "adam", "--opt-level", "O0", "--print-freq", "1",
            "--grad-accum", "2", "--eval", "--eval-batches", "2"]
    try:
        assert train_mod.main(base + ["--epochs", "1",
                                      "--checkpoint-dir", ck]) == 0
        assert "masked_acc" in capsys.readouterr().out
        assert train_mod.main(base + ["--epochs", "2",
                                      "--resume", ck]) == 0
        assert "resumed from step 2" in capsys.readouterr().out
    finally:
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)


def test_cp_model_rejects_mask():
    m = bert_tiny(context_parallel=True)
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError):
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), ids,
                                      attention_mask=jnp.ones((1, 8))))


def test_train_py_cli_context_parallel(devices8):
    import train as train_mod
    from apex_example_tpu.transformer import parallel_state
    argv = ["--arch", "bert_tiny", "--context-parallel", "4",
            "--batch-size", str(B), "--seq-len", str(L), "--epochs", "1",
            "--steps-per-epoch", "3", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1"]
    try:
        assert train_mod.main(argv) == 0
    finally:
        parallel_state.set_mesh(None)


def test_train_py_cli_cp_eval_and_grad_accum(devices8, capsys):
    """--eval and --grad-accum now compose with --context-parallel
    (VERDICT r3 item 6): the eval pass runs sequence-sharded on the ring."""
    import train as train_mod
    from apex_example_tpu.transformer import parallel_state
    argv = ["--arch", "bert_tiny", "--context-parallel", "4",
            "--batch-size", str(B), "--seq-len", str(L), "--epochs", "1",
            "--steps-per-epoch", "2", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1", "--grad-accum", "2",
            "--eval", "--eval-batches", "2"]
    try:
        assert train_mod.main(argv) == 0
    finally:
        parallel_state.set_mesh(None)
    assert "masked_acc" in capsys.readouterr().out


def test_train_py_cp_rejections():
    import train as train_mod
    with pytest.raises(SystemExit):
        train_mod.main(["--arch", "resnet18", "--context-parallel", "2"])
    with pytest.raises(SystemExit):
        train_mod.main(["--arch", "transformer_xl_tiny",
                        "--context-parallel", "2"])
    with pytest.raises(SystemExit):
        # (CP x PP composes since round 5; the ZeRO x CP x TP triple
        # does not)
        train_mod.main(["--arch", "bert_tiny", "--context-parallel", "2",
                        "--tensor-parallel", "2", "--zero"])
    with pytest.raises(SystemExit):
        # SP's sequence sharding conflicts with the context axis.
        train_mod.main(["--arch", "bert_tiny", "--context-parallel", "2",
                        "--tensor-parallel", "2", "--sequence-parallel"])
    with pytest.raises(SystemExit):
        train_mod.main(["--arch", "bert_tiny", "--context-parallel", "3",
                        "--seq-len", "16"])
    with pytest.raises(SystemExit):
        # O3's half-softmax contract: rejected at the CLI (the model-level
        # ValueError would otherwise only fire at trace time).
        train_mod.main(["--arch", "bert_tiny", "--context-parallel", "2",
                        "--opt-level", "O3"])
