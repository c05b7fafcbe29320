"""Expert parallelism (switch-MoE over the expert axis): sharded all_to_all
dispatch == dense per-shard golden, gradients, capacity-overflow semantics.

EP is a beyond-reference extension (SURVEY.md §3.2 marks it absent there);
these tests define and pin its semantics the way the CP tests do."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_example_tpu.transformer.expert_parallel import (
    EXPERT_AXIS, MoEParams, _dispatch_masks, init_moe_params,
    moe_forward, moe_forward_dense_reference)

from jax import shard_map


def _mesh(devices8):
    return Mesh(np.asarray(devices8), (EXPERT_AXIS,))


def test_sharded_matches_dense_reference(devices8):
    mesh = _mesh(devices8)
    E, T, d, h = 8, 16, 32, 64          # T per device
    params = init_moe_params(jax.random.PRNGKey(0), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (E * T, d), jnp.float32)

    sharded = jax.jit(shard_map(
        lambda p, x: moe_forward(p, x),
        mesh=mesh,
        in_specs=(MoEParams(P(), P(EXPERT_AXIS), P(EXPERT_AXIS)),
                  P(EXPERT_AXIS)),
        out_specs=(P(EXPERT_AXIS), P())))
    y, aux = sharded(params, x)

    # dense golden, shard by shard (routing/capacity are per-device)
    ys, auxs = [], []
    for s in range(E):
        ref_y, ref_aux = moe_forward_dense_reference(
            params, x[s * T:(s + 1) * T])
        ys.append(ref_y)
        auxs.append(ref_aux)
    np.testing.assert_allclose(np.asarray(y), np.concatenate(ys),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux), np.mean(auxs), rtol=1e-6)


def test_gradients_match_dense_reference(devices8):
    mesh = _mesh(devices8)
    E, T, d, h = 8, 8, 16, 32
    params = init_moe_params(jax.random.PRNGKey(2), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(3), (E * T, d), jnp.float32)

    def sharded_loss(p, x):
        def inner(p, xs):
            y, aux = moe_forward(p, xs)
            return lax.psum(jnp.sum(y.astype(jnp.float32) ** 2),
                            EXPERT_AXIS) + 0.01 * aux
        return shard_map(
            inner, mesh=mesh,
            in_specs=(MoEParams(P(), P(EXPERT_AXIS), P(EXPERT_AXIS)),
                      P(EXPERT_AXIS)),
            out_specs=P())(p, x)

    def dense_loss(p, x):
        total = 0.0
        auxs = []
        for s in range(E):
            y, aux = moe_forward_dense_reference(p, x[s * T:(s + 1) * T])
            total = total + jnp.sum(y.astype(jnp.float32) ** 2)
            auxs.append(aux)
        return total + 0.01 * jnp.mean(jnp.stack(auxs))

    g_sh = jax.grad(sharded_loss)(params, x)
    g_ref = jax.grad(dense_loss)(params, x)
    for a, b in zip(g_sh, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_capacity_overflow_drops_tokens():
    """Tokens beyond an expert's capacity get zero dispatch AND zero combine
    weight (the switch static-shape drop)."""
    T, E, C = 12, 2, 4
    # all tokens prefer expert 0
    logits = jnp.stack([jnp.ones(T), -jnp.ones(T)], axis=1)
    dispatch, combine, _ = _dispatch_masks(logits, C)
    # first C tokens occupy expert 0 slots 0..C-1; rest dropped
    total_dispatched = float(dispatch.sum())
    assert total_dispatched == C
    assert float(dispatch[C:].sum()) == 0.0
    assert float(combine[C:].sum()) == 0.0
    # kept tokens land in distinct slots
    slots = np.asarray(dispatch[:C, 0]).argmax(axis=1)
    assert sorted(slots.tolist()) == list(range(C))


def test_dropped_tokens_output_zero(devices8):
    """A dropped token's MoE output is exactly zero (identity residual adds
    happen outside the block)."""
    mesh = _mesh(devices8)
    E, T, d, h = 8, 32, 16, 32
    params = init_moe_params(jax.random.PRNGKey(4), d, h, E)
    # identical tokens all pick the same expert; capacity_factor 0.25 over
    # 32 tokens -> 8 slots (after lane rounding) -> 24 of 32 dropped.
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(5), (1, d)), (E * T, 1))

    sharded = jax.jit(shard_map(
        lambda p, xs: moe_forward(p, xs, capacity_factor=0.25)[0],
        mesh=mesh,
        in_specs=(MoEParams(P(), P(EXPERT_AXIS), P(EXPERT_AXIS)),
                  P(EXPERT_AXIS)),
        out_specs=P(EXPERT_AXIS)))
    y = np.asarray(sharded(params, x))
    # identical tokens all route to one expert; capacity 8*0.25/8 -> 8 slots
    # min => some rows kept, the rest exactly zero
    nonzero = np.abs(y).sum(axis=1) > 0
    assert nonzero.any() and (~nonzero).any()
    np.testing.assert_array_equal(y[~nonzero], 0.0)


def test_top2_sharded_matches_dense_reference(devices8):
    """GShard-style top-2: the all_to_all dispatch must equal the dense
    per-shard golden with the same (two-slot) masks."""
    mesh = _mesh(devices8)
    E, T, d, h = 8, 16, 32, 64
    params = init_moe_params(jax.random.PRNGKey(4), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(5), (E * T, d), jnp.float32)

    sharded = jax.jit(shard_map(
        lambda p, x: moe_forward(p, x, top_k=2),
        mesh=mesh,
        in_specs=(MoEParams(P(), P(EXPERT_AXIS), P(EXPERT_AXIS)),
                  P(EXPERT_AXIS)),
        out_specs=(P(EXPERT_AXIS), P())))
    y, aux = sharded(params, x)
    ys, auxs = [], []
    for s in range(E):
        ref_y, ref_aux = moe_forward_dense_reference(
            params, x[s * T:(s + 1) * T], top_k=2)
        ys.append(ref_y)
        auxs.append(ref_aux)
    np.testing.assert_allclose(np.asarray(y), np.concatenate(ys),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux), np.mean(auxs), rtol=1e-6)


def test_top2_semantics():
    """Top-2 invariants on the masks directly: every un-dropped token is
    dispatched to its two distinct top experts with renormalized gates
    summing to 1; at generous capacity nothing is dropped."""
    from apex_example_tpu.transformer.expert_parallel import _dispatch_masks
    T, E, C = 16, 4, 16                       # capacity >> T: no drops
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    dispatch, combine, _aux = _dispatch_masks(logits, C, top_k=2)
    d_np, c_np = np.asarray(dispatch), np.asarray(combine)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    order = np.argsort(-probs, axis=-1)
    for t in range(T):
        experts = set(np.argwhere(d_np[t].sum(-1) > 0)[:, 0])
        assert experts == {order[t, 0], order[t, 1]}, t
        np.testing.assert_allclose(c_np[t].sum(), 1.0, rtol=1e-6)
    # each expert's capacity slots hold at most one token
    assert (d_np.sum(axis=0) <= 1.0 + 1e-6).all()


def test_top2_capacity_drops_second_choices_first():
    """Under capacity pressure the second opinions are dropped before any
    kept first choice (the GShard queueing convention)."""
    from apex_example_tpu.transformer.expert_parallel import _dispatch_masks
    T, E = 8, 2
    # every token's first choice is expert 0, second expert 1
    logits = jnp.tile(jnp.asarray([[2.0, 1.0]]), (T, 1))
    C = 4
    dispatch, combine, _ = _dispatch_masks(logits, C, top_k=2)
    d_np = np.asarray(dispatch)
    # expert 0: exactly C first-choice tokens kept (tokens 0..C-1)
    assert d_np[:C, 0].sum() == C and d_np[C:, 0].sum() == 0
    # expert 1: its queue is all second choices, first C kept
    assert d_np[:C, 1].sum() == C and d_np[C:, 1].sum() == 0


def test_multi_expert_per_device_matches_dense(devices8):
    """E = 2 experts per device x 8 devices = 16 experts: the grouped
    all_to_all (sender-major <-> expert-major transposes around the
    batched local FFN) must equal the dense per-shard golden."""
    mesh = _mesh(devices8)
    E, T, d, h = 16, 16, 32, 64          # T per device
    params = init_moe_params(jax.random.PRNGKey(6), d, h, E)
    x = jax.random.normal(jax.random.PRNGKey(7), (8 * T, d), jnp.float32)

    sharded = jax.jit(shard_map(
        lambda p, x: moe_forward(p, x),
        mesh=mesh,
        in_specs=(MoEParams(P(), P(EXPERT_AXIS), P(EXPERT_AXIS)),
                  P(EXPERT_AXIS)),
        out_specs=(P(EXPERT_AXIS), P())))
    y, aux = sharded(params, x)
    ys, auxs = [], []
    for s in range(8):
        ref_y, ref_aux = moe_forward_dense_reference(
            params, x[s * T:(s + 1) * T])
        ys.append(ref_y)
        auxs.append(ref_aux)
    np.testing.assert_allclose(np.asarray(y), np.concatenate(ys),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux), np.mean(auxs), rtol=1e-6)
