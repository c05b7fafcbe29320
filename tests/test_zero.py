"""DistributedFusedAdam (ZeRO-1 state sharding): equivalence with the
replicated FusedAdam DDP step on the 8-device rig, and the 1/N state-memory
contract (SURVEY.md §3.4 contrib row / §3.3 weight-update sharding)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_example_tpu import amp
from apex_example_tpu.data import image_batch
from apex_example_tpu.engine import (create_train_state,
                                     make_sharded_train_step)
from apex_example_tpu.models import resnet18
from apex_example_tpu.optim import FusedAdam
from apex_example_tpu.optim.distributed import (DistributedFusedAdam,
                                                ZeroAdamState, _flat_size,
                                                _padded_size,
                                                make_zero_train_step)
from apex_example_tpu.parallel.mesh import make_data_mesh


def _setup(devices8, opt):
    policy, scaler = amp.initialize("O0")
    model = resnet18(num_classes=10, bn_axis_name="data")
    batch = image_batch(jnp.asarray(0), batch_size=16, image_size=32,
                        channels=3, num_classes=10, seed=0)
    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               batch[0][:1], policy, scaler)
    return policy, model, batch, state


def test_zero_matches_replicated_adam(devices8):
    mesh = make_data_mesh(devices=devices8)
    hp = dict(lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=1e-2)

    policy, model, batch, state_ref = _setup(devices8, FusedAdam(**hp))
    ref_step = make_sharded_train_step(mesh, model, FusedAdam(**hp), policy,
                                       donate=False)

    zopt = DistributedFusedAdam(**hp, world=8, axis_name="data")
    _, _, _, state_z = _setup(devices8, zopt)
    zero_step = make_zero_train_step(mesh, model, zopt, policy, donate=False)

    for i in range(3):
        b = image_batch(jnp.asarray(i), batch_size=16, image_size=32,
                        channels=3, num_classes=10, seed=0)
        state_ref, m_ref = ref_step(state_ref, b)
        state_z, m_z = zero_step(state_z, b)

    # fp32 reduction-order noise only (flatten-then-slice vs per-leaf psum):
    # the earlier double-reduction bug showed up here as a 5e-3 loss drift.
    # Params get an absolute-only bound: Adam behaves like sign(g)·lr where
    # grads are near zero, so order-of-reduction noise can flip individual
    # updates (bounded by ~lr per step) without the trajectories diverging —
    # exact elementwise agreement is checked by the fixed-grads test below.
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_z["loss"]),
                               rtol=1e-4)
    diffs = np.concatenate([
        np.abs(np.asarray(a) - np.asarray(b)).ravel()
        for a, b in zip(jax.tree_util.tree_leaves(state_ref.params),
                        jax.tree_util.tree_leaves(state_z.params))])
    # A handful of near-zero-grad elements may differ by up to ~lr per step
    # (sign flip); everything else must agree tightly.
    assert float((diffs < 5e-3).mean()) > 0.999
    assert float(diffs.max()) < 3 * 1e-2        # 3 steps x lr


def test_zero_apply_matches_fused_adam_fixed_grads(devices8):
    """One sharded apply on fixed (params, grads) == replicated FusedAdam
    elementwise — no model in the loop, so no sign-flip amplification."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map as smap

    mesh = make_data_mesh(devices=devices8)
    hp = dict(lr=3e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=1e-2)
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(40, 37), jnp.float32),
              "b": jnp.asarray(rng.randn(33), jnp.float32)}
    grads = {"w": jnp.asarray(rng.randn(40, 37), jnp.float32),
             "b": jnp.asarray(rng.randn(33), jnp.float32)}

    ref = FusedAdam(**hp)
    st_ref = ref.init(params)
    p_ref, _ = ref.apply(grads, st_ref, params)

    zopt = DistributedFusedAdam(**hp, world=8, axis_name="data")
    st_z = zopt.init(params)

    def step(params, grads, st):
        # replicated grads stand in for the engine's already-psum-ed grads;
        # pre-multiply by world so the /world averaging is a no-op.
        g = jax.tree_util.tree_map(
            lambda g: g * jax.lax.axis_size("data"), grads)
        return zopt.apply(g, st, params)

    p_z, _ = jax.jit(smap(
        step, mesh=mesh,
        in_specs=(P(), P(), zopt.state_spec()),
        out_specs=(P(), zopt.state_spec())))(params, grads, st_z)

    for k in params:
        np.testing.assert_allclose(np.asarray(p_ref[k]), np.asarray(p_z[k]),
                                   atol=1e-6, rtol=1e-6)


def test_zero_state_is_one_nth(devices8):
    zopt = DistributedFusedAdam(lr=1e-3, world=8)
    params = {"a": jnp.zeros((1000, 37)), "b": jnp.zeros((13,))}
    st = zopt.init(params)
    padded = _padded_size(_flat_size(params), 8)
    assert st.mu.shape == (padded,) and padded % (8 * 128) == 0
    # Global buffer sharded over 8 devices => per-device bytes are 1/8 of
    # FusedAdam's per-device replicated state.
    mesh = make_data_mesh(devices=devices8)
    from jax.sharding import NamedSharding, PartitionSpec as P
    mu = jax.device_put(st.mu, NamedSharding(mesh, P("data")))
    shard_bytes = mu.addressable_shards[0].data.nbytes
    assert shard_bytes == st.mu.nbytes // 8


def test_zero_fp16_dynamic_scaling_skips_in_lockstep(devices8):
    """fp16 + dynamic scaling + ZeRO: a nonfinite grad originating on ONE
    replica's microbatch must skip the step identically on all replicas —
    params, sharded (m, v) and the scaler all roll back together, and the
    next clean step trains normally.  (The finite check runs after the
    reduce; the flag is psum-ed so no replica can step alone.)"""
    mesh = make_data_mesh(devices=devices8)
    # Modest init scale: 2**10 keeps the CLEAN follow-up step overflowing in
    # fp16 (the scale must walk down first), which is correct scaler behavior
    # but not what this test pins — the lockstep skip is.  BN-free model: an
    # inf input permanently poisons BN *running stats* (apex semantics keep
    # forward-pass stat updates even on skipped steps), which would make
    # every later step nonfinite regardless of the optimizer's behavior.
    from flax import linen as fnn

    class _Mlp(fnn.Module):
        @fnn.compact
        def __call__(self, x, train: bool = True):
            x = x.reshape(x.shape[0], -1).astype(jnp.float16)
            x = fnn.relu(fnn.Dense(32, dtype=jnp.float16)(x))
            return fnn.Dense(10, dtype=jnp.float16)(x).astype(jnp.float32)

    policy, scaler = amp.initialize("O2", loss_scale="dynamic",
                                    half_dtype=jnp.float16,
                                    init_scale=2.0 ** 4)
    zopt = DistributedFusedAdam(lr=1e-2, world=8, axis_name="data")
    model = _Mlp()
    batch = image_batch(jnp.asarray(0), batch_size=16, image_size=32,
                        channels=3, num_classes=10, seed=0)
    state = create_train_state(jax.random.PRNGKey(0), model, zopt,
                               batch[0][:1], policy, scaler)
    step = make_zero_train_step(mesh, model, zopt, policy, donate=False)

    # Poison one element of shard 0's slice: only that replica's local grads
    # go nonfinite before the reduce.
    x, y = batch
    x_bad = x.at[0, 0, 0, 0].set(jnp.inf)
    p_before = jax.tree_util.tree_map(lambda p: np.asarray(p), state.params)
    mu_before = np.asarray(state.opt_state.mu)
    state, metrics = step(state, (x_bad, y))

    assert float(metrics["grads_finite"]) == 0.0
    for a, b in zip(jax.tree_util.tree_leaves(p_before),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(mu_before, np.asarray(state.opt_state.mu))
    assert int(state.opt_state.step) == 0
    assert float(state.scaler.scale) == 2.0 ** 3

    # Clean step afterwards: must actually train (params move, step counts).
    state, metrics = step(state, batch)
    assert float(metrics["grads_finite"]) == 1.0
    assert int(state.opt_state.step) == 1
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(p_before),
                        jax.tree_util.tree_leaves(state.params)))
    assert moved


def test_train_py_cli_bert_zero(devices8):
    """CLI end to end: BERT MLM under ZeRO-1 state sharding."""
    import train as train_mod
    assert train_mod.main(
        ["--arch", "bert_tiny", "--zero", "--opt", "adam",
         "--batch-size", "16", "--seq-len", "16", "--epochs", "1",
         "--steps-per-epoch", "3", "--opt-level", "O0",
         "--print-freq", "1"]) == 0


def test_train_py_zero_rejections():
    import train as train_mod
    with pytest.raises(SystemExit):
        train_mod.main(["--arch", "transformer_xl_tiny", "--zero",
                        "--opt", "adam"])
    with pytest.raises(SystemExit):
        train_mod.main(["--arch", "bert_tiny", "--zero", "--opt", "lamb"])
    with pytest.raises(SystemExit):
        train_mod.main(["--arch", "bert_tiny", "--zero", "--opt", "adam",
                        "--grad-accum", "2", "--batch-size", "16"])


# ---------------------------------------------------------------------------
# ZeRO-1 x tensor parallelism (VERDICT r4 item 2): under GSPMD the ZeRO
# contract is pure annotation — params keep their 'model'-axis TP specs,
# optimizer state (mu/nu) additionally shards over 'data'
# (engine.gspmd_state_shardings zero_axis) — and the partitioner derives
# reduce-scatter(grads) + data-sliced Adam + all-gather(params) from the
# sharding lattice, composed with the TP collectives in one jit program.
# ---------------------------------------------------------------------------

TP, SEQ, BATCH = 4, 16, 8


def _mlm(i, vocab):
    from apex_example_tpu.data import mlm_batch
    ids, labels, w = mlm_batch(jnp.asarray(i, jnp.int32), batch_size=BATCH,
                               seq_len=SEQ, vocab_size=vocab,
                               mask_token_id=vocab - 1, seed=0)
    return ids, (labels, w)


@pytest.fixture()
def tp_mesh(devices8):
    from apex_example_tpu.transformer import parallel_state
    mesh = parallel_state.initialize_model_parallel(tensor_parallel=TP,
                                                    devices=devices8)
    yield mesh
    parallel_state.set_mesh(None)


def test_zero_tp_matches_dense_trajectory(tp_mesh):
    """30 Adam steps of ZeRO-1 x TP BERT on the (data=2, model=4) mesh ==
    30 single-device dense steps from the same init and batches.  Same
    tolerance design as test_zero_matches_replicated_adam: Adam near zero
    grads behaves like sign(g)*lr, so partitioning-order noise can flip
    individual elements by ~lr/step without the trajectories diverging."""
    from apex_example_tpu.engine import (create_gspmd_train_state,
                                         create_train_state as mk_state,
                                         make_gspmd_train_step,
                                         make_train_step)
    from apex_example_tpu.models.bert import bert_tiny
    from apex_example_tpu.parallel.mesh import DATA_AXIS
    from apex_example_tpu.workloads import mlm_loss

    steps, lr = 30, 1e-3
    policy, scaler = amp.initialize("O0")
    dense = bert_tiny()
    tp_model = bert_tiny(tensor_parallel=True)
    V = dense.vocab_size
    opt = lambda: FusedAdam(lr=lr, weight_decay=1e-2)

    sample = _mlm(0, V)[0][:1]
    state_d = mk_state(jax.random.PRNGKey(0), dense, opt(), sample, policy,
                       scaler)
    step_d = jax.jit(make_train_step(dense, opt(), policy, loss_fn=mlm_loss,
                                     compute_accuracy=False))

    state_z, shardings = create_gspmd_train_state(
        jax.random.PRNGKey(0), tp_mesh, tp_model, opt(), sample, policy,
        scaler, zero_axis=DATA_AXIS)
    state_z = state_z.replace(
        params=jax.device_put(state_d.params, shardings.params))
    step_z = make_gspmd_train_step(tp_mesh, tp_model, opt(), policy,
                                   shardings, loss_fn=mlm_loss,
                                   compute_accuracy=False, donate=False)

    for i in range(steps):
        b = _mlm(i, V)
        state_d, m_d = step_d(state_d, b)
        state_z, m_z = step_z(state_z, b)
        np.testing.assert_allclose(float(m_d["loss"]), float(m_z["loss"]),
                                   rtol=1e-4)

    diffs = np.concatenate([
        np.abs(np.asarray(a) - np.asarray(b)).ravel()
        for a, b in zip(jax.tree_util.tree_leaves(state_d.params),
                        jax.tree_util.tree_leaves(state_z.params))])
    assert float((diffs < 5e-3).mean()) > 0.999
    assert float(diffs.max()) < steps * lr * 3


def test_zero_tp_state_shards_both_axes(tp_mesh):
    """Params provably shard over 'model' AND opt state over 'data': the
    live buffers carry 1/TP param bytes and 1/(DP*TP) mu/nu bytes per
    device — the ZeRO-1 memory contract on top of TP's."""
    from jax.sharding import PartitionSpec as P

    from apex_example_tpu.engine import create_gspmd_train_state
    from apex_example_tpu.models.bert import bert_tiny
    from apex_example_tpu.parallel.mesh import DATA_AXIS

    dp = 8 // TP
    policy, scaler = amp.initialize("O0")
    model = bert_tiny(tensor_parallel=True)
    sample = _mlm(0, model.vocab_size)[0][:1]
    state, shardings = create_gspmd_train_state(
        jax.random.PRNGKey(0), tp_mesh, model, FusedAdam(lr=1e-3), sample,
        policy, scaler, zero_axis=DATA_AXIS)

    k = state.params["layer_0"]["intermediate"]["kernel"]
    mu = state.opt_state.mu["layer_0"]["intermediate"]["kernel"]
    nu = state.opt_state.nu["layer_0"]["intermediate"]["kernel"]
    # param: TP only (replicated over data — ZeRO-1, not ZeRO-3)
    assert k.addressable_shards[0].data.shape[1] == k.shape[1] // TP
    assert k.addressable_shards[0].data.nbytes == k.nbytes // TP
    # mu/nu: data x model
    for s in (mu, nu):
        assert s.addressable_shards[0].data.nbytes == s.nbytes // (dp * TP)
        assert DATA_AXIS in s.sharding.spec
    # the sharding spec tree says the same thing statically
    mu_spec = shardings.opt_state.mu["layer_0"]["intermediate"]["kernel"].spec
    assert DATA_AXIS in mu_spec and "model" in mu_spec
    # scalar step stays replicated
    assert state.opt_state.step.sharding.spec == P()


def test_zero_tp_fp16_dynamic_scaling_skips_globally(tp_mesh):
    """fp16 dynamic scaling under ZeRO-1 x TP: one jit program, so the
    finite flag is global by construction — a poisoned batch rolls back
    params AND the data-sharded (mu, nu) everywhere and halves the scale;
    a clean step then trains."""
    from apex_example_tpu.engine import (create_gspmd_train_state,
                                         make_gspmd_train_step)
    from apex_example_tpu.models.bert import bert_tiny
    from apex_example_tpu.parallel.mesh import DATA_AXIS
    from apex_example_tpu.workloads import mlm_loss

    policy, scaler = amp.initialize("O2", loss_scale="dynamic",
                                    half_dtype=jnp.float16,
                                    init_scale=2.0 ** 4)
    model = bert_tiny(tensor_parallel=True, dtype=jnp.float16)
    V = model.vocab_size
    opt = FusedAdam(lr=1e-3)
    sample = _mlm(0, V)[0][:1]
    state, shardings = create_gspmd_train_state(
        jax.random.PRNGKey(0), tp_mesh, model, opt, sample, policy, scaler,
        zero_axis=DATA_AXIS)
    step = make_gspmd_train_step(tp_mesh, model, opt, policy, shardings,
                                 loss_fn=mlm_loss, compute_accuracy=False,
                                 donate=False)

    ids, (labels, w) = _mlm(0, V)
    w_bad = w.at[0, 0].set(jnp.inf)
    p_before = jax.tree_util.tree_map(lambda p: np.asarray(p), state.params)
    o_before = jax.tree_util.tree_map(lambda p: np.asarray(p),
                                      state.opt_state)
    state, m = step(state, (ids, (labels, w_bad)))
    assert float(m["grads_finite"]) == 0.0
    assert float(state.scaler.scale) == 2.0 ** 3
    for a, b in zip(jax.tree_util.tree_leaves(p_before),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(o_before),
                    jax.tree_util.tree_leaves(state.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    state, m = step(state, (ids, (labels, w)))
    assert float(m["grads_finite"]) == 1.0
    assert int(state.opt_state.step) == 1
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(p_before),
                        jax.tree_util.tree_leaves(state.params)))
    assert moved


def test_train_py_cli_bert_zero_tensor_parallel(devices8):
    """The VERDICT contract: --zero --tensor-parallel 2 accepted and trains
    through the CLI on the (data=4, model=2) CPU mesh."""
    import train as train_mod
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.transformer import parallel_state
    argv = ["--arch", "bert_tiny", "--zero", "--tensor-parallel", "2",
            "--batch-size", "16", "--seq-len", "16", "--epochs", "1",
            "--steps-per-epoch", "3", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1"]
    try:
        assert train_mod.main(argv) == 0
    finally:
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)


def test_train_py_cli_gpt_zero_tensor_parallel(devices8):
    """Same cell for the GPT causal-LM family (shared GSPMD path)."""
    import train as train_mod
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.transformer import parallel_state
    argv = ["--arch", "gpt_tiny", "--zero", "--tensor-parallel", "2",
            "--batch-size", "16", "--seq-len", "16", "--epochs", "1",
            "--steps-per-epoch", "3", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1"]
    try:
        assert train_mod.main(argv) == 0
    finally:
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)


# ---------------------------------------------------------------------------
# ZeRO-1 x context parallelism (round 5): the flat (mu, nu) buffers shard
# over 'data' INSIDE the CP shard_map (workloads._cp_state_spec) while
# params replicate over (data, context) — long context with 1/N optimizer
# state.
# ---------------------------------------------------------------------------

def test_zero_cp_matches_cp_adam(devices8):
    """5 ZeRO x CP steps == 5 plain-FusedAdam CP steps from the same init
    (same tolerance design as test_zero_matches_replicated_adam: Adam's
    near-zero-grad sign flips bound elementwise diffs by ~lr/step), and
    the sharded (mu, nu) really live 1/data-axis per device."""
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_example_tpu.data import lm_batch
    from apex_example_tpu.models.gpt import gpt_tiny
    from apex_example_tpu.workloads import make_gpt_cp_train_step

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("data", "context"))
    hp = dict(lr=1e-3, weight_decay=1e-2)
    dense = gpt_tiny()
    cp_model = gpt_tiny(context_parallel=True)
    V = dense.vocab_size
    policy, scaler = amp.initialize("O0")

    def batch(i):
        toks = lm_batch(jnp.asarray(i, jnp.int32), batch_size=8,
                        seq_len=16, vocab_size=V, seed=0)
        return toks[:, :-1], toks[:, 1:]

    sample = batch(0)[0][:1]
    state_a = create_train_state(jax.random.PRNGKey(0), dense,
                                 FusedAdam(**hp), sample, policy, scaler)
    step_a = make_gpt_cp_train_step(mesh, cp_model, FusedAdam(**hp),
                                    policy, donate=False)

    # grads_global_mean: the CP losses psum-normalize GLOBALLY, so the
    # implicitly psum-ed grads arrive as the true global mean — without
    # the flag the optimizer would divide by world again (Adam's scale
    # invariance would hide it from the loss/param comparison; the mu
    # norm check below would not).
    zopt = DistributedFusedAdam(**hp, world=2, axis_name="data",
                                grads_global_mean=True)
    state_z = create_train_state(jax.random.PRNGKey(0), dense, zopt,
                                 sample, policy, scaler)
    state_z = state_z.replace(params=state_a.params)
    step_z = make_gpt_cp_train_step(mesh, cp_model, zopt, policy,
                                    donate=False)

    for i in range(5):
        b = batch(i)
        state_a, m_a = step_a(state_a, b)
        state_z, m_z = step_z(state_z, b)
        np.testing.assert_allclose(float(m_a["loss"]), float(m_z["loss"]),
                                   rtol=1e-4)
    diffs = np.concatenate([
        np.abs(np.asarray(a) - np.asarray(b)).ravel()
        for a, b in zip(jax.tree_util.tree_leaves(state_a.params),
                        jax.tree_util.tree_leaves(state_z.params))])
    assert float((diffs < 5e-3).mean()) > 0.999
    assert float(diffs.max()) < 5 * 1e-3 * 3
    # The first-moment buffers must agree in NORM with the reference
    # adam's tree (Adam's update is scale-invariant, so a silently
    # rescaled gradient would pass the param comparison but not this).
    mu_ref = np.sqrt(sum(
        float(jnp.sum(m.astype(jnp.float32) ** 2))
        for m in jax.tree_util.tree_leaves(state_a.opt_state.mu)))
    mu_z = np.sqrt(float(jnp.sum(state_z.opt_state.mu ** 2)))
    np.testing.assert_allclose(mu_ref, mu_z, rtol=1e-3)
    # 1/N state: mu sharded over 'data', replicated over 'context'
    mu = state_z.opt_state.mu
    assert mu.addressable_shards[0].data.size * 2 == mu.size
    assert "data" in mu.sharding.spec


def test_train_py_cli_zero_context_parallel(devices8):
    import train as train_mod
    from apex_example_tpu.transformer import parallel_state
    argv = ["--arch", "gpt_tiny", "--zero", "--context-parallel", "2",
            "--batch-size", "8", "--seq-len", "16", "--epochs", "1",
            "--steps-per-epoch", "3", "--opt", "adam", "--opt-level", "O0",
            "--print-freq", "1"]
    try:
        assert train_mod.main(argv) == 0
    finally:
        parallel_state.set_mesh(None)
