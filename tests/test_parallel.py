"""Distributed-semantics tests on 8 real XLA CPU devices (SURVEY.md §5:
the actual psum/shard_map code path, not a mock — exceeds the reference's
two-physical-GPU test gap).

Covers: SyncBN invariant (N-shard == full-batch BN, the upstream two_gpu
test), DDP grad-averaging semantics, predivide/fp32 options, and torch
BatchNorm goldens for the single-device path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from apex_example_tpu.parallel import (
    DDPConfig, SyncBatchNorm, allreduce_grads, convert_syncbn_model,
    make_data_mesh)
from jax.sharding import PartitionSpec as P

from jax import shard_map


def _bn_apply(axis_name=None, train=True):
    mod = SyncBatchNorm(use_running_average=not train, axis_name=axis_name)
    return mod


class TestSyncBatchNormLocal:
    def test_matches_torch_batchnorm_train(self):
        x = np.random.RandomState(0).randn(8, 4, 4, 3).astype(np.float32)
        mod = SyncBatchNorm(use_running_average=False)
        vars_ = mod.init(jax.random.PRNGKey(0), jnp.asarray(x))
        y, mut = mod.apply(vars_, jnp.asarray(x), mutable=["batch_stats"])

        tbn = torch.nn.BatchNorm2d(3, eps=1e-5, momentum=0.1)
        tx = torch.from_numpy(x.transpose(0, 3, 1, 2))
        ty = tbn(tx).detach().numpy().transpose(0, 2, 3, 1)
        np.testing.assert_allclose(np.asarray(y), ty, atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(mut["batch_stats"]["mean"]),
            tbn.running_mean.numpy(), atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(mut["batch_stats"]["var"]),
            tbn.running_var.numpy(), atol=1e-5, rtol=1e-4)

    def test_eval_uses_running_stats(self):
        x = np.random.RandomState(1).randn(4, 2, 2, 5).astype(np.float32)
        mod = SyncBatchNorm(use_running_average=True)
        vars_ = mod.init(jax.random.PRNGKey(0), jnp.asarray(x))
        y = mod.apply(vars_, jnp.asarray(x))
        # Fresh stats: mean 0, var 1 → identity up to affine (scale=1,bias=0).
        np.testing.assert_allclose(np.asarray(y),
                                   x / np.sqrt(1 + 1e-5), atol=1e-5)


class TestSyncBatchNormCrossReplica:
    def test_sharded_equals_full_batch(self, devices8):
        """The SyncBN invariant: 8-shard SyncBN == 1-device big-batch BN."""
        mesh = make_data_mesh(devices=devices8)
        n, h, w, c = 16, 4, 4, 6
        x = np.random.RandomState(2).randn(n, h, w, c).astype(np.float32)

        mod_sync = SyncBatchNorm(use_running_average=False, axis_name="data")
        mod_local = SyncBatchNorm(use_running_average=False)
        vars_ = mod_local.init(jax.random.PRNGKey(0), jnp.asarray(x))

        def shard_fn(xs):
            y, mut = mod_sync.apply(vars_, xs, mutable=["batch_stats"])
            return y, mut["batch_stats"]

        sharded = jax.jit(shard_map(
            shard_fn, mesh=mesh, in_specs=P("data"),
            out_specs=(P("data"), P())))
        y_sh, stats_sh = sharded(jnp.asarray(x))

        y_full, mut_full = mod_local.apply(vars_, jnp.asarray(x),
                                           mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_full),
                                   atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(stats_sh["mean"]),
            np.asarray(mut_full["batch_stats"]["mean"]), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(stats_sh["var"]),
            np.asarray(mut_full["batch_stats"]["var"]), atol=1e-4,
            rtol=1e-4)

    def test_backward_crosses_replicas(self, devices8):
        """Grad of per-shard loss wrt shared params must include every
        shard's contribution (psum transpose)."""
        mesh = make_data_mesh(devices=devices8)
        x = np.random.RandomState(3).randn(8, 2, 2, 3).astype(np.float32)
        mod = SyncBatchNorm(use_running_average=False, axis_name="data")
        # init outside shard_map must not touch the axis: use the local twin
        # (identical param structure).
        vars_ = SyncBatchNorm(use_running_average=False).init(
            jax.random.PRNGKey(0), jnp.asarray(x[:1]))
        params = vars_["params"]

        def shard_loss(params, xs):
            y, _ = mod.apply({"params": params}, xs,
                             mutable=["batch_stats"])
            return jnp.sum(y ** 2)

        def total_loss(params, xs):
            l = shard_loss(params, xs)
            return jax.lax.psum(l, "data")

        g = jax.jit(shard_map(
            jax.grad(total_loss), mesh=mesh,
            in_specs=(P(), P("data")), out_specs=P()))(params,
                                                       jnp.asarray(x))
        # Golden: same computation single-device (full batch, local BN).
        mod_l = SyncBatchNorm(use_running_average=False)

        def full_loss(params):
            y, _ = mod_l.apply({"params": params}, jnp.asarray(x),
                               mutable=["batch_stats"])
            return jnp.sum(y ** 2)

        g_full = jax.grad(full_loss)(params)
        for k in ("scale", "bias"):
            np.testing.assert_allclose(np.asarray(g[k]),
                                       np.asarray(g_full[k]),
                                       atol=1e-3, rtol=1e-4)


class TestDDP:
    def test_allreduce_grads_mean(self, devices8):
        mesh = make_data_mesh(devices=devices8)
        g = np.arange(8, dtype=np.float32).reshape(8, 1)

        def f(gs):
            return allreduce_grads({"w": gs}, DDPConfig(),
                                   already_reduced=False)["w"]

        out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"), check_vma=False))(
            jnp.asarray(g))
        # gradient_average=True → every shard holds the mean.
        np.testing.assert_allclose(np.asarray(out),
                                   np.full((8, 1), g.mean()), rtol=1e-6)

    def test_allreduce_sum_when_average_off(self, devices8):
        mesh = make_data_mesh(devices=devices8)
        g = np.ones((8, 1), np.float32)
        cfg = DDPConfig(gradient_average=False)

        def f(gs):
            return allreduce_grads({"w": gs}, cfg,
                                   already_reduced=False)["w"]

        out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"), check_vma=False))(
            jnp.asarray(g))
        np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 8.0))

    def test_predivide_matches_plain_average(self, devices8):
        mesh = make_data_mesh(devices=devices8)
        g = np.random.RandomState(4).randn(8, 4).astype(np.float32)

        def f(cfg):
            def inner(gs):
                return allreduce_grads({"w": gs}, cfg,
                                       already_reduced=False)["w"]
            return jax.jit(shard_map(inner, mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data"), check_vma=False))(
                jnp.asarray(g))

        plain = f(DDPConfig())
        pre = f(DDPConfig(gradient_predivide_factor=8.0))
        np.testing.assert_allclose(np.asarray(plain), np.asarray(pre),
                                   rtol=1e-5, atol=1e-6)

    def test_allreduce_always_fp32_preserves_dtype(self, devices8):
        mesh = make_data_mesh(devices=devices8)
        g = jnp.ones((8, 4), jnp.bfloat16)
        cfg = DDPConfig(allreduce_always_fp32=True)

        def f(gs):
            return allreduce_grads({"w": gs}, cfg,
                                   already_reduced=False)["w"]

        out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"), check_vma=False))(g)
        assert out.dtype == jnp.bfloat16


def _shard_map_unchecked(f, mesh, in_specs, out_specs):
    """shard_map without variance checking."""
    return shard_map(f, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


class TestDDPPrecision:
    """allreduce_always_fp32 semantics (the ISSUE 13 satellite pin) and
    the quantized-allreduce mode, on a 2-device DDP fixture."""

    def _mesh2(self, devices8):
        return make_data_mesh(devices=devices8[:2])

    def _reduce(self, mesh, cfg, g):
        def f(gs):
            return allreduce_grads({"w": gs}, cfg,
                                   already_reduced=False)["w"]
        return np.asarray(jax.jit(_shard_map_unchecked(
            f, mesh, P("data"), P("data")))(g))

    def test_allreduce_always_fp32_upcasts_before_psum(self, devices8):
        """The direct semantics pin: upcast BEFORE psum, downcast
        after.  Two fp16 shards of 40000.0 sum to 80000 — past fp16's
        65504 max — so a reduction performed in fp16 is inf by the time
        the average brings it back in range, while the fp32-upcast path
        averages to a finite 40000 and only then downcasts.  The output
        dtype stays fp16 either way (the downcast half of the
        contract)."""
        mesh = self._mesh2(devices8)
        g = jnp.full((2, 4), 40000.0, jnp.float16)
        plain = self._reduce(mesh, DDPConfig(), g)
        up = self._reduce(mesh, DDPConfig(allreduce_always_fp32=True), g)
        assert plain.dtype == np.float16 and up.dtype == np.float16
        assert not np.isfinite(plain).any()      # fp16 psum overflowed
        np.testing.assert_array_equal(
            up, np.full((2, 4), 40000.0, np.float16))

    def test_quantized_allreduce_bound_and_identities(self, devices8):
        """One quantized reduction: per-element error within the
        documented world*scale/2 bound (scale = pmax chunk max-abs /
        127; averaging divides both sides by world), the off switch
        bit-identical to the unquantized path, and composition with
        allreduce_always_fp32 exact (the quantized path already
        accumulates in f32)."""
        mesh = self._mesh2(devices8)
        chunk = 256
        g = np.random.RandomState(0).randn(2, 4096).astype(np.float32)
        exact = self._reduce(mesh, DDPConfig(), jnp.asarray(g))
        cfg = DDPConfig(quantized_allreduce=True, quant_chunk=chunk)
        quant = self._reduce(mesh, cfg, jnp.asarray(g))
        # shared scale per chunk: pmax over the 2 shards of max-abs/127
        scale = np.abs(g).reshape(2, -1, chunk).max(axis=(0, 2)) / 127.0
        err = np.abs(quant - exact).reshape(2, -1, chunk).max(axis=2)
        bound = np.broadcast_to(scale[None, :] / 2 * 1.001 + 1e-8,
                                err.shape)
        np.testing.assert_array_less(err, bound)
        assert (err > 0).any()                   # it really quantized
        off = self._reduce(mesh, DDPConfig(quantized_allreduce=False),
                           jnp.asarray(g))
        np.testing.assert_array_equal(off, exact)
        both = self._reduce(mesh, DDPConfig(
            quantized_allreduce=True, quant_chunk=chunk,
            allreduce_always_fp32=True), jnp.asarray(g))
        np.testing.assert_array_equal(both, quant)
        # grad dtype preserved through the int8 exchange
        gb = jnp.asarray(g, jnp.bfloat16)
        qb = self._reduce(mesh, cfg, gb)
        assert qb.dtype == jnp.bfloat16

    def test_quantized_allreduce_30step_lockstep_trail(self, devices8):
        """The gate the ISSUE names: 30 lockstep SGD steps on the
        2-device DDP fixture, quantized exchange vs the fp32 reduction.
        Per step the reduced-gradient error is bounded by scale/2
        (averaged), so the parameter trails stay within the summed
        per-step bounds — asserted exactly, step by step, against the
        accumulated bound rather than a vibes tolerance."""
        mesh = self._mesh2(devices8)
        chunk = 128
        rs = np.random.RandomState(7)
        w_exact = np.zeros((2, chunk), np.float32)
        w_quant = np.zeros((2, chunk), np.float32)
        budget = 0.0
        lr = 0.1
        cfg_q = DDPConfig(quantized_allreduce=True, quant_chunk=chunk)
        # ONE jitted program per config for the whole trail (the loop
        # re-invokes, never re-traces).
        mk = lambda cfg: jax.jit(_shard_map_unchecked(
            lambda gs: allreduce_grads({"w": gs}, cfg,
                                       already_reduced=False)["w"],
            mesh, P("data"), P("data")))
        red_exact, red_quant = mk(DDPConfig()), mk(cfg_q)
        for step in range(30):
            # synthetic per-shard grads: a drifting quadratic pull plus
            # shard-dependent noise (what DDP exists to average away)
            base = rs.randn(1, chunk).astype(np.float32)
            noise = rs.randn(2, chunk).astype(np.float32)
            g_exact = base + 0.3 * noise + 0.05 * w_exact
            g_quant = base + 0.3 * noise + 0.05 * w_quant
            r_exact = np.asarray(red_exact(jnp.asarray(g_exact)))
            r_quant = np.asarray(red_quant(jnp.asarray(g_quant)))
            # this step's quantization bound at the quant trail's grads
            scale = np.abs(g_quant).reshape(2, -1, chunk) \
                .max(axis=(0, 2)) / 127.0
            budget = budget * (1 + lr * 0.05) \
                + lr * (float(scale.max()) / 2 + 1e-7)
            w_exact = w_exact - lr * r_exact
            w_quant = w_quant - lr * r_quant
            assert np.abs(w_quant - w_exact).max() <= budget * 1.01, \
                f"trail diverged past the accumulated bound at {step}"
        # and the trails really are different computations
        assert np.abs(w_quant - w_exact).max() > 0


def test_convert_syncbn_model():
    from apex_example_tpu.models import resnet18
    m = resnet18(num_classes=10)
    assert m.bn_axis_name is None
    m2 = convert_syncbn_model(m)
    assert m2.bn_axis_name == "data"


def test_reducer_manual_allreduce(devices8):
    """apex.parallel.Reducer analog: manual reduction == pmean."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from apex_example_tpu.parallel import Reducer
    mesh = Mesh(np.asarray(devices8), ("data",))
    x = jnp.arange(16.0).reshape(8, 2)

    red = Reducer()
    out = shard_map(lambda t: red.reduce({"g": t})["g"],
                    mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)
    expect = np.broadcast_to(np.asarray(x).reshape(8, 2).mean(0), (8, 2))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)
