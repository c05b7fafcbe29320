"""DistributedFusedAdam: ZeRO-1 optimizer-state sharding over the data axis.

Reference: apex/contrib/optimizers/distributed_fused_adam.py (SURVEY.md §3.4
contrib row) — Adam whose optimizer state and parameter update are sharded
across the data-parallel group: gradients reduce-scatter instead of
all-reduce, each rank updates only its 1/N shard of the flattened parameter
space, and the new parameters all-gather back.  SURVEY.md §3.3 notes the same
idea for TPU as "cross-replica weight-update sharding".

TPU-native design: the flattened parameter space is ONE fp32 buffer padded to
``world × 128`` lanes.  Optimizer state (m, v) lives as global (padded,)
arrays that shard over the mesh's data axis — inside ``shard_map`` each
replica holds exactly its (padded/world,) slice, so per-device state memory
is 1/N of FusedAdam's.  One step, inside the same jitted program as
forward/backward:

    flat_g   = flatten(grads)                      # per-replica, shard-varying
    g_shard  = psum_scatter(flat_g, 'data')        # the reduce-scatter
    p_shard  = dynamic_slice(flatten(params), axis_index * shard)
    p_shard' = fused adam kernel (p, g, m, v shards — ops/fused_optim.py)
    flat_p'  = all_gather(p_shard', 'data', tiled)  # replicated again
    params'  = unflatten(flat_p')

reduce_scatter + all_gather move the same bytes as the plain psum, so the
step trades nothing on the wire for an N-fold cut in optimizer-state memory
and update FLOPs — the ZeRO-1 contract.

``make_zero_train_step`` wires this into the engine's DDP step: the only
difference from ``make_sharded_train_step`` is that the optimizer-state
in/out specs shard over the data axis (P("data")) instead of replicating.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_example_tpu.ops.fused_optim import adam_update_leaf
from apex_example_tpu.optim.fused import Schedule, _lr_at

_LANES = 128


class ZeroAdamState(NamedTuple):
    step: jnp.ndarray
    mu: jnp.ndarray        # (padded,) fp32 — shards over the data axis
    nu: jnp.ndarray        # (padded,) fp32 — shards over the data axis


def _flat_size(params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def _padded_size(n: int, world: int) -> int:
    quantum = world * _LANES
    return n + (-n) % quantum


def _flatten(tree, padded: int, dtype=jnp.float32) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    flat = jnp.concatenate([l.astype(dtype).reshape(-1) for l in leaves])
    return jnp.pad(flat, (0, padded - flat.shape[0]))


def _unflatten(flat: jnp.ndarray, like) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return treedef.unflatten(out)


class DistributedFusedAdam:
    """ZeRO-1 Adam/AdamW over a data-parallel mesh axis.

    Ctor mirrors FusedAdam plus the sharding contract: ``world`` (the data-
    axis size, static) and ``axis_name``.  ``apply`` must run inside
    ``shard_map`` with ``axis_name`` bound and state sharded P(axis) (see
    ``make_zero_train_step``); ``init`` runs anywhere and returns the
    global-shaped state.
    """

    def __init__(self, lr: Schedule = 1e-3, betas=(0.9, 0.999),
                 eps: float = 1e-8, weight_decay: float = 0.0,
                 adam_w_mode: bool = True, *, world: int,
                 axis_name: str = "data",
                 grads_global_mean: bool = False):
        self.lr, self.betas, self.eps = lr, betas, eps
        self.weight_decay, self.adam_w_mode = weight_decay, adam_w_mode
        self.world, self.axis_name = world, axis_name
        # Reduction contract: False (the DDP engine path) = incoming
        # grads are per-shard LOCAL means whose implicit psum sums to
        # world x the global mean — apply() divides by world.  True (the
        # CP path, whose losses are psum-normalized GLOBALLY) = grads
        # arrive as the true global mean already — dividing again would
        # hand Adam g/world and silently inflate the effective epsilon.
        self.grads_global_mean = grads_global_mean

    def init(self, params) -> ZeroAdamState:
        padded = _padded_size(_flat_size(params), self.world)
        return ZeroAdamState(step=jnp.zeros((), jnp.int32),
                             mu=jnp.zeros((padded,), jnp.float32),
                             nu=jnp.zeros((padded,), jnp.float32))

    def state_spec(self) -> ZeroAdamState:
        """shard_map PartitionSpecs for the state (m/v shard over data)."""
        return ZeroAdamState(step=P(), mu=P(self.axis_name),
                             nu=P(self.axis_name))

    def apply(self, grads, state: ZeroAdamState, params
              ) -> Tuple[Any, ZeroAdamState]:
        """Sharded update; inside shard_map state.mu/nu are the LOCAL shard.

        ``grads`` are the per-replica (unreduced) gradients — the reduce
        happens here, as a reduce-scatter, so the engine must NOT have
        psum-ed them already (make_zero_train_step passes ddp-less grads).
        """
        step = state.step + 1
        b1, b2 = self.betas
        t = step.astype(jnp.float32)
        c1 = 1.0 / (1.0 - jnp.power(b1, t))
        c2 = 1.0 / (1.0 - jnp.power(b2, t))
        lr = _lr_at(self.lr, step)

        world = lax.axis_size(self.axis_name)
        padded = _padded_size(_flat_size(params), world)
        shard = padded // world
        idx = lax.axis_index(self.axis_name)

        flat_g = _flatten(grads, padded)
        if not self.grads_global_mean:
            flat_g = flat_g / world                  # mean-reduction contract
        vma = getattr(jax.typeof(flat_g), "vma", None)
        if vma is None:
            # Without vma typing (pre-vma JAX / check_vma=False) we cannot
            # tell already-psummed engine grads from raw per-replica grads;
            # guessing wrong silently trains each shard on 1/N of the data.
            raise RuntimeError(
                "DistributedFusedAdam requires vma-typed shard_map "
                "(jax.shard_map with check_vma=True, the default) so the "
                "gradient-reduction state is visible; got an aval without "
                "vma typing")
        if self.axis_name in vma:
            if self.grads_global_mean:
                raise RuntimeError(
                    "grads_global_mean=True expects implicitly psum-ed "
                    "(shard-invariant) grads — the CP-loss contract; got "
                    "shard-varying grads, whose reduce-scatter would need "
                    "the /world mean the flag disables")
            # Raw per-replica grads: the reduction IS the reduce-scatter.
            g_shard = lax.psum_scatter(flat_g, self.axis_name,
                                       scatter_dimension=0, tiled=True)
        else:
            # Engine-path grads: jax.grad w.r.t. replicated params already
            # psum-ed them inside backward (see parallel/distributed.py) —
            # XLA owns that collective's schedule; only the slice remains.
            # The ZeRO-1 memory contract (1/N optimizer state + update) is
            # unchanged; the reduce-scatter wire saving applies only to the
            # varying-grads path.
            g_shard = lax.dynamic_slice(flat_g, (idx * shard,), (shard,))
        p_shard = lax.dynamic_slice(_flatten(params, padded),
                                    (idx * shard,), (shard,))

        # Finite check AFTER the reduce — the fp16 dynamic-scaling contract.
        # A nonfinite grad element lands in exactly one replica's shard after
        # psum_scatter, so the per-shard flag alone would diverge across
        # replicas (each skipping or stepping on its own) and de-synchronize
        # the gathered params.  psum-ing the flag makes the skip decision
        # identical everywhere: every replica steps, or none does.  The psum
        # output is mesh-invariant, so the select below provably keeps the
        # replicated-params out-spec.
        shard_ok = jnp.all(jnp.isfinite(g_shard)).astype(jnp.float32)
        finite = lax.psum(shard_ok, self.axis_name) == world

        po, mo, vo = adam_update_leaf(
            p_shard, g_shard, state.mu, state.nu, lr=lr, beta1=b1, beta2=b2,
            eps=self.eps, weight_decay=self.weight_decay, bias_c1=c1,
            bias_c2=c2, adam_w_mode=self.adam_w_mode)

        # Overflow ⇒ the whole sharded update is dropped (params, m, v and
        # the bias-correction step all keep their old values) — the same
        # "skip optimizer.step()" select the engine applies for replicated
        # optimizers, enforced here where the shard structure is known.
        po = jnp.where(finite, po, p_shard)
        mo = jnp.where(finite, mo, state.mu)
        vo = jnp.where(finite, vo, state.nu)
        step = jnp.where(finite, step, state.step)

        # Gather the updated shards back to replicated parameters.  The psum
        # of per-replica scattered writes is the vma-typed form of the
        # all_gather (shard_map's replication checker can prove psum outputs
        # invariant; lax.all_gather stays 'varying' and would be rejected at
        # the P() out_spec) — XLA lowers this select-free sum-of-disjoint
        # slices to the same collective traffic class.
        contrib = lax.dynamic_update_slice(
            jnp.zeros((padded,), jnp.float32), po, (idx * shard,))
        flat_p = lax.psum(contrib, self.axis_name)
        return _unflatten(flat_p, params), ZeroAdamState(step, mo, vo)


def make_zero_train_step(mesh: Mesh, model, optimizer: DistributedFusedAdam,
                         policy, loss_fn=None, compute_accuracy: bool = True,
                         donate: bool = True):
    """DDP train step with ZeRO-1 state sharding.

    Identical contract to ``engine.make_sharded_train_step`` except the
    optimizer-state leaves shard over the data axis (P("data")) and gradient
    reduction happens inside the optimizer (reduce-scatter), not as a psum.
    """
    from apex_example_tpu import amp as amp_lib
    from apex_example_tpu.engine import (TrainState, cross_entropy_loss,
                                         make_train_step, _replicate_mean)

    axis = optimizer.axis_name
    loss_fn = loss_fn or cross_entropy_loss
    # Dynamic loss scaling composes safely here on two grounds:
    #  - On this engine path grads reach the optimizer already implicitly
    #    psum-ed (jax.grad w.r.t. replicated params inside shard_map), so the
    #    engine's unscale/finite flag is mesh-invariant — every replica makes
    #    the same skip decision and updates the scaler identically.
    #  - Independently, DistributedFusedAdam.apply re-checks finiteness on
    #    the post-reduce shard and psums the flag, so even the raw
    #    reduce-scatter path (varying grads) skips in lockstep.  A skipped
    #    step is therefore a no-op on params AND on the sharded (m, v, step).
    # axis_name=None: the inner step must NOT psum grads (the optimizer's
    # reduce-scatter is the reduction); loss/metrics get pmean-ed below.
    per_shard = make_train_step(model, optimizer, policy, axis_name=None,
                                loss_fn=loss_fn,
                                compute_accuracy=compute_accuracy)

    def step_and_sync(state, batch):
        new_state, metrics = per_shard(state, batch)
        metrics = {k: lax.pmean(v, axis) for k, v in metrics.items()}
        synced = _replicate_mean(new_state.batch_stats, axis)
        return new_state.replace(batch_stats=synced), metrics

    # Prefix specs: a single P() stands for a whole replicated subtree.
    spec = TrainState(step=P(), params=P(), batch_stats=P(),
                      opt_state=optimizer.state_spec(), scaler=P())
    sharded = jax.shard_map(
        step_and_sync, mesh=mesh,
        in_specs=(spec, (P(axis), P(axis))),
        out_specs=(spec, P()))
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())
