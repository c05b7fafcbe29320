"""Expert-parallel (switch-MoE) BERT training from the harness
(workloads.make_bert_moe_train_step; train.py --moe-experts).

The golden is the BLOCKED DENSE construction: routing/capacity are
per-device by design (the same contract the layer-level EP tests pin), so
the reference trajectory applies the dense-reference MoE model to each
shard's batch block independently, combines the blocks' losses with the
same globally-normalized weighted CE + mean aux objective, and takes the
same fused-optimizer step on the full [E, ...] stacks.  The EP step must
reproduce it exactly — all_to_all dispatch, shard-local expert grads,
implicit psum of replicated grads and all."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_example_tpu import amp
from apex_example_tpu.data import mlm_batch
from apex_example_tpu.engine import create_train_state
from apex_example_tpu.models.bert import bert_tiny
from apex_example_tpu.optim import FusedAdam, FusedSGD
from apex_example_tpu.ops.xentropy import softmax_cross_entropy
from apex_example_tpu.workloads import (bert_moe_state_shardings,
                                        make_bert_moe_train_step)

BATCH, SEQ, E = 16, 16, 8
AUX_W = 1e-2


def _moe_model(**kw):
    kw.setdefault("moe_experts", E)
    kw.setdefault("moe_axis_name", "data")
    return bert_tiny(**kw)


def _batch(i, vocab):
    ids, lab, w = mlm_batch(jnp.asarray(i, jnp.int32), batch_size=BATCH,
                            seq_len=SEQ, vocab_size=vocab,
                            mask_token_id=vocab - 1, seed=0)
    return ids, (lab, w)


def _golden_step(model, optimizer, state, n_blocks=E):
    """Blocked dense-reference step: n_blocks batch blocks through the
    full-stack dense MoE path, one global objective, one optimizer step."""
    from apex_example_tpu.engine import TrainState, _wrap_optimizer
    opt = _wrap_optimizer(optimizer)
    E_ = n_blocks
    b = BATCH // E_

    def loss_fn(params, batch):
        ids, (labels, weights) = batch
        num = jnp.zeros((), jnp.float32)
        aux_sum = jnp.zeros((), jnp.float32)
        for s in range(E_):
            sl = slice(s * b, (s + 1) * b)
            logits, aux = model.apply({"params": params}, ids[sl],
                                      train=True)
            ce = softmax_cross_entropy(logits, labels[sl])
            num = num + (ce * weights[sl]).sum()
            aux_sum = aux_sum + aux
        den = jnp.maximum(weights.sum(), 1.0)
        return num / den + AUX_W * aux_sum / E_

    @jax.jit
    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        new_params, new_opt = opt.apply(grads, state.opt_state, state.params)
        return TrainState(step=state.step + 1, params=new_params,
                          batch_stats=state.batch_stats, opt_state=new_opt,
                          scaler=state.scaler), loss

    return step


@pytest.mark.parametrize("n_experts", [E, 2 * E])
def test_moe_train_matches_blocked_dense_golden(devices8, n_experts):
    """n_experts = 2*E runs TWO experts per device: the grouped
    all_to_all's backward (reshape/transpose pairs), the shard-local
    [k, ...] expert grads, and the optimizer on the k-stacked shards are
    the parts only this variant exercises."""
    mesh = Mesh(np.asarray(devices8), ("data",))
    policy, scaler = amp.initialize("O0")
    model = _moe_model(moe_experts=n_experts)
    V = model.vocab_size
    # SGD+momentum, not adam: attention's key bias takes a mathematically
    # ~zero gradient, and adam's m/sqrt(v) normalization would amplify the
    # all_to_all-vs-einsum rounding noise on it to lr-scale updates —
    # a tolerance problem, not a semantics one (adam is exercised by the
    # CLI/scaling tests).
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)
    state_g = create_train_state(jax.random.PRNGKey(0), model, opt(),
                                 _batch(0, V)[0][:1], policy, scaler)
    golden = _golden_step(model, opt(), state_g, n_blocks=E)

    zopt = opt()
    state_e = create_train_state(jax.random.PRNGKey(0), model, zopt,
                                 _batch(0, V)[0][:1], policy, scaler)
    state_e = jax.device_put(state_e,
                             bert_moe_state_shardings(mesh, state_e, zopt))
    step_e = make_bert_moe_train_step(mesh, model, zopt, policy,
                                      state_template=state_e,
                                      aux_weight=AUX_W, donate=False)

    for i in range(30):
        batch = _batch(i, V)
        state_g, loss_g = golden(state_g, batch)
        state_e, m_e = step_e(state_e, batch)
        np.testing.assert_allclose(float(loss_g), float(m_e["loss"]),
                                   rtol=2e-5 * (1 + i / 3))
    for (ka, a), (kb, b2) in zip(
            jax.tree_util.tree_leaves_with_path(state_g.params),
            jax.tree_util.tree_leaves_with_path(state_e.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=1e-3, atol=1e-5, err_msg=str(ka))


def test_moe_tp_train_matches_blocked_dense_golden(devices8):
    """MoE x TP (partially-manual shard_map: experts over manual 'data',
    GSPMD TP attention/embeddings/head on automatic 'model') == the same
    blocked dense golden, fed identical params — and the state is provably
    sharded on BOTH axes."""
    from apex_example_tpu.engine import create_gspmd_train_state
    from apex_example_tpu.ops import _config as ops_config
    mesh = Mesh(np.asarray(devices8).reshape(4, 2), ("data", "model"))
    policy, scaler = amp.initialize("O0")
    dense = _moe_model(moe_experts=4)
    tp_model = _moe_model(moe_experts=4, tensor_parallel=True)
    V = dense.vocab_size
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)
    state_g = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                 _batch(0, V)[0][:1], policy, scaler)
    golden = _golden_step(dense, opt(), state_g, n_blocks=4)

    ops_config.set_force_xla(True)
    try:
        zopt = opt()
        state_e, gsh = create_gspmd_train_state(
            jax.random.PRNGKey(0), mesh, tp_model, zopt,
            _batch(0, V)[0][:1], policy, scaler)
        sh = bert_moe_state_shardings(mesh, state_e, zopt,
                                      base_shardings=gsh)
        # same starting point as the golden (identical param tree)
        state_e = jax.device_put(state_g.replace(
            opt_state=state_e.opt_state), sh)
        step_e = make_bert_moe_train_step(mesh, tp_model, zopt, policy,
                                          state_template=state_e,
                                          aux_weight=AUX_W, donate=False,
                                          state_shardings=sh)
        for i in range(30):
            batch = _batch(i, V)
            state_g, loss_g = golden(state_g, batch)
            state_e, m_e = step_e(state_e, batch)
            np.testing.assert_allclose(float(loss_g), float(m_e["loss"]),
                                       rtol=3e-5 * (1 + i / 3))
        p0 = state_e.params["layer_0"]
        assert p0["moe"]["w_in"].sharding.spec == P("data")
        q_spec = p0["attention"]["query"]["kernel"].sharding.spec
        assert "model" in jax.tree_util.tree_leaves(tuple(q_spec)), q_spec
        for (ka, a), (kb, b2) in zip(
                jax.tree_util.tree_leaves_with_path(state_g.params),
                jax.tree_util.tree_leaves_with_path(state_e.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=2e-4, atol=1e-5,
                                       err_msg=str(ka))
    finally:
        ops_config.set_force_xla(False)


def test_train_py_cli_moe_tp(devices8, capsys):
    """MoE x TP from the CLI (both families' routing already covered; this
    pins the composed path end-to-end)."""
    import train as train_mod
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.transformer import parallel_state
    argv = ["--arch", "bert_tiny", "--moe-experts", "4",
            "--tensor-parallel", "2", "--batch-size", str(BATCH),
            "--seq-len", str(SEQ), "--epochs", "1", "--steps-per-epoch",
            "2", "--opt", "adam", "--lr", "1e-3", "--opt-level", "O0",
            "--print-freq", "1", "--eval", "--eval-batches", "2"]
    try:
        assert train_mod.main(argv) == 0
    finally:
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)
    assert "masked_acc" in capsys.readouterr().out


def test_moe_state_actually_sharded(devices8):
    """The expert stacks shard one-per-device over 'data'; the router and
    everything else replicate."""
    mesh = Mesh(np.asarray(devices8), ("data",))
    policy, scaler = amp.initialize("O0")
    model = _moe_model()
    opt = FusedAdam(lr=1e-3)
    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               _batch(0, model.vocab_size)[0][:1], policy,
                               scaler)
    state = jax.device_put(state, bert_moe_state_shardings(mesh, state, opt))
    p0 = state.params["layer_0"]["moe"]
    assert p0["w_in"].sharding.spec == P("data")
    local = p0["w_in"].addressable_shards[0].data
    assert local.shape[0] == 1 and p0["w_in"].shape[0] == E
    assert p0["router"].sharding.spec == P()


def test_moe_fp16_dynamic_scaling_skips_globally(devices8):
    """An overflow landing in ONE shard's expert grads must skip the update
    and halve the scale on EVERY shard (the finite_reduce_axes pmean) —
    without it the replicated scaler state diverges across the mesh."""
    mesh = Mesh(np.asarray(devices8), ("data",))
    policy, scaler = amp.initialize("O2", loss_scale="dynamic",
                                    half_dtype=jnp.float16,
                                    init_scale=2.0 ** 4)
    model = _moe_model(dtype=jnp.float16)
    V = model.vocab_size
    opt = FusedAdam(lr=1e-3)
    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               _batch(0, V)[0][:1], policy, scaler)
    state = jax.device_put(state, bert_moe_state_shardings(mesh, state, opt))
    step = make_bert_moe_train_step(mesh, model, opt, policy,
                                    state_template=state, aux_weight=AUX_W,
                                    donate=False)
    ids, (labels, w) = _batch(0, V)
    w_bad = w.at[0, 0].set(jnp.inf)        # lands in shard 0 only
    p_before = jax.tree_util.tree_map(lambda p: np.asarray(p), state.params)
    state, m = step(state, (ids, (labels, w_bad)))
    assert float(m["grads_finite"]) == 0.0
    assert float(state.scaler.scale) == 2.0 ** 3
    for a, b in zip(jax.tree_util.tree_leaves(p_before),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    state, m = step(state, (ids, (labels, w)))
    assert float(m["grads_finite"]) == 1.0


def test_train_py_cli_moe(devices8, capsys):
    import train as train_mod
    argv = ["--arch", "bert_tiny", "--moe-experts", "8",
            "--batch-size", str(BATCH), "--seq-len", str(SEQ),
            "--epochs", "1", "--steps-per-epoch", "3", "--opt", "adam",
            "--opt-level", "O0", "--print-freq", "1",
            "--eval", "--eval-batches", "2"]
    assert train_mod.main(argv) == 0
    assert "masked_acc" in capsys.readouterr().out


def test_train_py_moe_rejections(devices8):
    import train as train_mod
    base = ["--arch", "bert_tiny", "--batch-size", "16", "--seq-len", "16",
            "--epochs", "1", "--steps-per-epoch", "1"]
    with pytest.raises(SystemExit):       # lamb collapses on expert stacks
        train_mod.main(base + ["--moe-experts", "8", "--opt", "lamb"])
    with pytest.raises(SystemExit):       # no ZeRO composition
        train_mod.main(base + ["--moe-experts", "8", "--zero"])
    with pytest.raises(SystemExit):       # no SP composition
        train_mod.main(base + ["--moe-experts", "4",
                               "--tensor-parallel", "2",
                               "--sequence-parallel"])
    with pytest.raises(SystemExit):       # experts != device count
        train_mod.main(base + ["--moe-experts", "3"])
    with pytest.raises(SystemExit):       # image archs have no FFN to swap
        train_mod.main(["--arch", "resnet18", "--moe-experts", "8",
                        "--epochs", "1", "--steps-per-epoch", "1"])


# ---------------------------------------------------------------------------
# EP x CP (VERDICT r4 item 4): experts over 'data', KV ring over 'context'
# — two manual axes, two independent collectives in one step (train.py
# --moe-experts --context-parallel).  The golden is EXACT: the same
# (data, context) shard_map and the same CP attention program, but MoEMLP
# bound to an UNBOUND axis name ('expert' is not a mesh axis), so every
# shard runs the dense-reference expert compute on the replicated full
# [E, ...] stacks with the SAME per-(data, context)-shard routing/capacity
# the EP dispatch uses.  The EP x CP step must reproduce it exactly —
# aux loss and capacity drops included.
# ---------------------------------------------------------------------------

def _golden_moe_cp_step(mesh, model_gold, optimizer, policy, mode):
    from apex_example_tpu.engine import make_train_step
    from apex_example_tpu.workloads import (_cp_layout_wrap,
                                            _global_lm_loss)
    from jax import shard_map as smap

    def gold_loss(out, y):
        logits, aux = out
        aux = jax.lax.pmean(aux, ("data", "context"))
        return _global_lm_loss(logits, y, ("data", "context")) + AUX_W * aux

    per_shard = make_train_step(model_gold, optimizer, policy,
                                axis_name=None, loss_fn=gold_loss,
                                compute_accuracy=False)
    b = P("data", "context")
    sharded = smap(per_shard, mesh=mesh, in_specs=(P(), (b, b)),
                   out_specs=(P(), P()))
    return jax.jit(_cp_layout_wrap(sharded, mesh, model_gold, mode),
                   donate_argnums=())


def _lm_batch(i, vocab, batch=8, seq=16):
    from apex_example_tpu.data import lm_batch
    toks = lm_batch(jnp.asarray(i, jnp.int32), batch_size=batch,
                    seq_len=seq, vocab_size=vocab, seed=0)
    return toks[:, :-1], toks[:, 1:]


@pytest.mark.parametrize("mode", ["ring", "zigzag", "ulysses"])
def test_moe_cp_train_matches_dense_ref_golden(devices8, mode):
    """30 lockstep steps of GPT EP x CP (dp=4, cp=2) == the dense-reference
    golden under the identical mesh/attention/routing — exact semantics,
    not tolerance hand-waving (SGD+momentum per the suite's parity
    convention; adam's near-zero-grad sign flips are a tolerance artifact,
    not semantics)."""
    from apex_example_tpu.models.gpt import gpt_tiny
    from apex_example_tpu.workloads import make_bert_moe_train_step

    mesh = Mesh(np.asarray(devices8).reshape(4, 2), ("data", "context"))
    policy, scaler = amp.initialize("O0")
    kw = dict(moe_experts=4, context_parallel=True, cp_mode=mode)
    ep_model = gpt_tiny(**kw, moe_axis_name="data")
    gold_model = gpt_tiny(**kw, moe_axis_name="expert")   # unbound => dense
    dense_init = gpt_tiny(moe_experts=4, moe_axis_name="data")
    V = dense_init.vocab_size
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)

    # 17-token stream => x,y are [8, 16]; seq 16 = 2 context shards x 8
    sample = _lm_batch(0, V)[0][:1]
    state_g = create_train_state(jax.random.PRNGKey(0), dense_init, opt(),
                                 sample, policy, scaler)
    golden = _golden_moe_cp_step(mesh, gold_model, opt(), policy, mode)

    zopt = opt()
    state_e = create_train_state(jax.random.PRNGKey(0), dense_init, zopt,
                                 sample, policy, scaler)
    state_e = jax.device_put(state_e,
                             bert_moe_state_shardings(mesh, state_e, zopt))
    step_e = make_bert_moe_train_step(mesh, ep_model, zopt, policy,
                                      state_template=state_e,
                                      aux_weight=AUX_W, donate=False,
                                      objective="lm",
                                      context_parallel=True, mode=mode)

    for i in range(30):
        batch = _lm_batch(i, V)
        state_g, m_g = golden(state_g, batch)
        state_e, m_e = step_e(state_e, batch)
        np.testing.assert_allclose(float(m_g["loss"]), float(m_e["loss"]),
                                   rtol=2e-5)
    for (ka, a), (kb, b2) in zip(
            jax.tree_util.tree_leaves_with_path(state_g.params),
            jax.tree_util.tree_leaves_with_path(state_e.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=2e-4, atol=1e-6, err_msg=str(ka))


def test_moe_cp_expert_state_sharded(devices8):
    """The EP x CP state really is placed expert-per-data-device and
    replicated over 'context' (1/dp expert bytes per device)."""
    from apex_example_tpu.models.gpt import gpt_tiny
    mesh = Mesh(np.asarray(devices8).reshape(4, 2), ("data", "context"))
    policy, scaler = amp.initialize("O0")
    model = gpt_tiny(moe_experts=4, moe_axis_name="data")
    V = model.vocab_size
    opt = FusedSGD(lr=0.05, momentum=0.9)
    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               _lm_batch(0, V)[0][:1], policy, scaler)
    state = jax.device_put(state,
                           bert_moe_state_shardings(mesh, state, opt))
    w_in = state.params["layer_0"]["moe"]["w_in"]
    assert w_in.shape[0] == 4
    assert w_in.addressable_shards[0].data.shape[0] == 1   # 1 expert/device
    assert "data" in w_in.sharding.spec


def test_train_py_moe_cp_rejections():
    import train as train_mod
    base = ["--batch-size", "16", "--seq-len", "16", "--opt", "adam"]
    with pytest.raises(SystemExit):   # PP still rejected with MoE
        train_mod.main(["--arch", "gpt_tiny", "--moe-experts", "4",
                        "--context-parallel", "2", "--pipeline-parallel",
                        "2", "--microbatches", "2"] + base)
    with pytest.raises(SystemExit):   # SP still rejected with MoE
        train_mod.main(["--arch", "bert_tiny", "--moe-experts", "8",
                        "--sequence-parallel"] + base)


def test_train_py_cli_moe_context_parallel(devices8):
    """CLI end to end: GPT EP x CP (zigzag) and BERT EP x CP with eval."""
    import train as train_mod
    base = ["--batch-size", "16", "--seq-len", "16", "--epochs", "1",
            "--steps-per-epoch", "2", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1"]
    assert train_mod.main(
        ["--arch", "gpt_tiny", "--moe-experts", "4",
         "--context-parallel", "2", "--cp-mode", "zigzag"] + base) == 0
    assert train_mod.main(
        ["--arch", "bert_tiny", "--moe-experts", "4",
         "--context-parallel", "2", "--eval", "--eval-batches", "2"]
        + base) == 0


def test_moe_cp_tp_triple_matches_dense_ref_golden(devices8):
    """EP x CP x TP (round 5): expert all_to_all over manual 'data', KV
    ring over manual 'context', GSPMD TP over automatic 'model' — 10
    lockstep steps against the same EXACT dense-reference golden the
    EP x CP test uses (on its own (data=2, context=2) 4-device mesh,
    identical init and batches), expert stacks AND attention provably
    sharded."""
    from apex_example_tpu.engine import create_gspmd_train_state
    from apex_example_tpu.models.gpt import gpt_tiny
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.transformer import parallel_state
    from apex_example_tpu.workloads import make_bert_moe_train_step

    gold_mesh = Mesh(np.asarray(devices8[:4]).reshape(2, 2),
                     ("data", "context"))
    mesh = Mesh(np.asarray(devices8).reshape(2, 2, 2),
                ("data", "context", "model"))
    policy, scaler = amp.initialize("O0")
    kw = dict(moe_experts=2, moe_axis_name="data")
    dense_init = gpt_tiny(**kw)
    gold_model = gpt_tiny(moe_experts=2, moe_axis_name="expert",
                          context_parallel=True, cp_mode="ring")
    triple = gpt_tiny(**kw, tensor_parallel=True, context_parallel=True,
                      cp_mode="ring")
    V = dense_init.vocab_size
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)

    sample = _lm_batch(0, V)[0][:1]
    state_g = create_train_state(jax.random.PRNGKey(0), dense_init, opt(),
                                 sample, policy, scaler)
    golden = _golden_moe_cp_step(gold_mesh, gold_model, opt(), policy,
                                 "ring")

    parallel_state.set_mesh(mesh)
    ops_config.set_force_xla(True)
    try:
        zopt = opt()
        state_e, gsh = create_gspmd_train_state(
            jax.random.PRNGKey(0), mesh,
            gpt_tiny(**kw, tensor_parallel=True), zopt, sample, policy,
            scaler)
        sh = bert_moe_state_shardings(mesh, state_e, zopt,
                                      base_shardings=gsh)
        # same starting point as the golden (identical param tree)
        state_e = jax.device_put(
            state_g.replace(opt_state=state_e.opt_state), sh)
        step_e = make_bert_moe_train_step(mesh, triple, zopt, policy,
                                          state_template=state_e,
                                          aux_weight=AUX_W, donate=False,
                                          objective="lm",
                                          context_parallel=True,
                                          mode="ring", state_shardings=sh)
        for i in range(10):
            batch = _lm_batch(i, V)
            state_g, m_g = golden(state_g, batch)
            state_e, m_e = step_e(state_e, batch)
            np.testing.assert_allclose(float(m_g["loss"]),
                                       float(m_e["loss"]),
                                       rtol=3e-5 * (1 + i / 3))
        for (ka, a), (kb, b2) in zip(
                jax.tree_util.tree_leaves_with_path(state_g.params),
                jax.tree_util.tree_leaves_with_path(state_e.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=1e-3, atol=1e-5,
                                       err_msg=str(ka))
        w_in = state_e.params["layer_0"]["moe"]["w_in"]
        qk = state_e.params["layer_0"]["attention"]["query"]["kernel"]
        assert w_in.addressable_shards[0].data.shape[0] == \
            w_in.shape[0] // 2                       # experts over data
        assert qk.addressable_shards[0].data.shape[-1] == \
            qk.shape[-1] // 2                        # heads over model
    finally:
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)


def test_train_py_cli_moe_cp_tp(devices8):
    """The EP x CP x TP triple from the CLI."""
    import train as train_mod
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.transformer import parallel_state
    argv = ["--arch", "gpt_tiny", "--moe-experts", "2",
            "--context-parallel", "2", "--tensor-parallel", "2",
            "--batch-size", "8", "--seq-len", "16", "--epochs", "1",
            "--steps-per-epoch", "2", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1"]
    try:
        assert train_mod.main(argv) == 0
    finally:
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)


# ---------------------------------------------------------------------------
# EP x PP (round 5): switch-MoE experts INSIDE the ring pipeline schedule —
# expert stacks shard [layers->pipe, experts->data], the per-(stage,
# microbatch) aux loss rides the schedule carry (spmd_pipeline with_aux).
# ---------------------------------------------------------------------------

def test_moe_pp_matches_blocked_dense_golden(devices8):
    """10 lockstep EP x PP steps on a (pipe=2, data=4) mesh == an
    INDEPENDENT blocked-dense golden (no schedule code shared): the dense
    MoE model applied per (data-shard, microbatch) row block — the
    per-device routing contract — with CE globally normalized and the aux
    term the mean over blocks of aux_total/L.  Independence matters: a
    bug in the schedule's aux normalization would cancel in a golden
    built from the same factory."""
    from apex_example_tpu.engine import TrainState, _wrap_optimizer
    from apex_example_tpu.models.gpt import gpt_tiny
    from apex_example_tpu.transformer.bert_pipeline import (
        bert_pp_state_shardings, make_bert_pp_train_step, pack_params,
        unpack_params)

    B, L, M, DP = 8, 16, 2, 4
    mesh = Mesh(np.asarray(devices8).reshape(2, DP), ("pipe", "data"))
    policy, scaler = amp.initialize("O0")
    ep_model = gpt_tiny(moe_experts=4, moe_axis_name="data")
    dense = gpt_tiny(moe_experts=4, moe_axis_name="expert")  # dense ref
    V = dense.vocab_size
    opt = lambda: FusedSGD(lr=0.05, momentum=0.9)

    def batch(i):
        return _lm_batch(i, V, batch=B, seq=L)

    state0 = create_train_state(jax.random.PRNGKey(0), dense, opt(),
                                batch(0)[0][:1], policy, scaler)

    # ---- independent golden: dense model per row block (B blocks of 1
    # row: data shard d owns rows [2d, 2d+1], microbatch m takes row m of
    # the shard => block index 2d+m runs row 2d+m).
    gopt = _wrap_optimizer(opt())

    def gold_loss(params, b):
        x, y = b
        num = jnp.zeros((), jnp.float32)
        aux_sum = jnp.zeros((), jnp.float32)
        for r in range(B):
            logits, aux = dense.apply({"params": params}, x[r:r + 1],
                                      train=True)
            ce = softmax_cross_entropy(logits, y[r:r + 1])
            num = num + ce.sum()
            aux_sum = aux_sum + aux           # model returns aux_total/L
        return num / (B * L) + AUX_W * aux_sum / B

    @jax.jit
    def gold_step(state, b):
        loss, grads = jax.value_and_grad(gold_loss)(state.params, b)
        new_p, new_o = gopt.apply(grads, state.opt_state, state.params)
        return TrainState(step=state.step + 1, params=new_p,
                          batch_stats=state.batch_stats, opt_state=new_o,
                          scaler=state.scaler), {"loss": loss}

    state_g = state0

    # ---- the EP x PP step under test
    eopt = opt()
    packed = pack_params(state0.params, dense.num_layers)
    state_e = TrainState(step=jnp.zeros((), jnp.int32), params=packed,
                         batch_stats={}, opt_state=eopt.init(packed),
                         scaler=state0.scaler)
    state_e = jax.device_put(
        state_e, bert_pp_state_shardings(mesh, state_e, eopt,
                                         model=ep_model))
    step_e = make_bert_pp_train_step(mesh, ep_model, eopt, policy,
                                     microbatches=M, donate=False,
                                     moe_aux_weight=AUX_W)

    for i in range(10):
        b = batch(i)
        state_g, m_g = gold_step(state_g, b)
        state_e, m_e = step_e(state_e, b)
        np.testing.assert_allclose(float(m_g["loss"]), float(m_e["loss"]),
                                   rtol=3e-5 * (1 + i / 3))
    un = unpack_params(state_e.params, dense.num_layers)
    key = lambda kv: str(kv[0])
    for (ka, a), (kb, b2) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(state_g.params),
                   key=key),
            sorted(jax.tree_util.tree_leaves_with_path(un), key=key)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                   rtol=1e-3, atol=1e-5, err_msg=str(ka))
    # expert stacks jointly sharded [layers->pipe, experts->data]
    w_in = state_e.params["layers"]["moe"]["w_in"]
    assert w_in.addressable_shards[0].data.shape[0] == w_in.shape[0] // 2
    assert w_in.addressable_shards[0].data.shape[1] == w_in.shape[1] // DP


def test_train_py_cli_moe_pp(devices8):
    """EP x PP from the CLI (+ the rejection bounds)."""
    import train as train_mod
    from apex_example_tpu.transformer import parallel_state
    base = ["--batch-size", "8", "--seq-len", "16", "--epochs", "1",
            "--steps-per-epoch", "2", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1"]
    try:
        assert train_mod.main(
            ["--arch", "gpt_tiny", "--moe-experts", "4",
             "--pipeline-parallel", "2", "--microbatches", "2"]
            + base) == 0
    finally:
        parallel_state.set_mesh(None)
    with pytest.raises(SystemExit):      # 1f1b has no aux channel
        train_mod.main(["--arch", "gpt_tiny", "--moe-experts", "4",
                        "--pipeline-parallel", "2", "--microbatches", "2",
                        "--pipeline-schedule", "1f1b"] + base)
    with pytest.raises(SystemExit):      # no MoE x PP x TP triple
        train_mod.main(["--arch", "gpt_tiny", "--moe-experts", "4",
                        "--pipeline-parallel", "2", "--microbatches", "2",
                        "--tensor-parallel", "2"] + base)
