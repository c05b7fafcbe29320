"""Fused custom-VJP BatchNorm kernels (ops/batch_norm.py) vs the XLA
composite path — value, gradient, and running-stat equivalence, single-device
and cross-replica (SURVEY.md §5 syncbn test strategy)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_example_tpu.ops.batch_norm import _pick_block
from apex_example_tpu.parallel.mesh import make_data_mesh
from apex_example_tpu.parallel.sync_batchnorm import SyncBatchNorm

from jax import shard_map as shard_map_fn
from jax.sharding import PartitionSpec as P


def _run(fused, x, key, axis_name=None):
    bn = SyncBatchNorm(use_running_average=False, axis_name=axis_name,
                       stats_dtype=jnp.float32, fused_kernel=fused)
    variables = bn.init(key, x)

    def loss_fn(params, stats, x):
        y, mut = bn.apply({"params": params, "batch_stats": stats}, x,
                          mutable=["batch_stats"])
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, mut["batch_stats"])

    (val, (y, new_stats)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(variables["params"], variables["batch_stats"],
                               x)
    dx = jax.grad(lambda x: loss_fn(variables["params"],
                                    variables["batch_stats"], x)[0])(x)
    return y, new_stats, grads, dx


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_kernel_matches_xla(dtype):
    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (16, 8, 8, 64)) * 2.0 + 1.5).astype(dtype)
    y0, st0, g0, dx0 = _run(False, x, key)
    y1, st1, g1, dx1 = _run(True, x, key)

    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y1, np.float32), atol=tol, rtol=tol)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=tol, rtol=tol), st0, st1)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=5e-2, rtol=5e-2), g0, g1)
    np.testing.assert_allclose(np.asarray(dx0, np.float32),
                               np.asarray(dx1, np.float32),
                               atol=tol * 10, rtol=tol * 10)


def test_fused_kernel_sync_matches_full_batch(devices8):
    """N-shard fused-kernel SyncBN == full-batch XLA BN (values + dx)."""
    mesh = make_data_mesh(devices=devices8)
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (32, 4, 4, 16), jnp.float32) * 3.0 - 0.7

    y_full, _, _, dx_full = _run(False, x, key)

    bn = SyncBatchNorm(use_running_average=False, axis_name="data",
                       stats_dtype=jnp.float32, fused_kernel=True)
    variables = bn.init(key, x[:4])

    def shard_fn(params, stats, xs):
        def loss_fn(xs):
            y, _ = bn.apply({"params": params, "batch_stats": stats}, xs,
                            mutable=["batch_stats"])
            # global sum so the cotangent matches the full-batch loss
            return jax.lax.psum(jnp.sum(y.astype(jnp.float32) ** 2), "data")
        dx = jax.grad(loss_fn)(xs)
        y, mut = bn.apply({"params": params, "batch_stats": stats}, xs,
                          mutable=["batch_stats"])
        return y, dx, mut["batch_stats"]

    sharded = shard_map_fn(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(), P("data")),
        out_specs=(P("data"), P("data"), P()))
    y_sh, dx_sh, stats_sh = jax.jit(sharded)(
        variables["params"], variables["batch_stats"], x)

    np.testing.assert_allclose(np.asarray(y_full, np.float32),
                               np.asarray(y_sh, np.float32),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dx_full, np.float32),
                               np.asarray(dx_sh, np.float32),
                               atol=1e-3, rtol=1e-3)


def test_pick_block_divides():
    for rows in (802816, 200704, 50176, 12544, 256 * 32 * 32, 8, 16):
        for C in (64, 256, 1024, 2048):
            blk = _pick_block(rows, C)
            assert blk is not None and rows % blk == 0 and blk % 8 == 0
            assert blk * C <= (1 << 19) or blk == 8
    assert _pick_block(12, 64) is None   # not a multiple of 8
