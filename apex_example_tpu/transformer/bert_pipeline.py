"""Pipeline-parallel BERT/GPT training step (train.py --pipeline-parallel).

Reference: apex.transformer's pipeline_parallel package drives Megatron-LM
models through its schedules; the in-tree schedules here
(pipeline_parallel/schedules.py) were previously exercised on synthetic
stage functions only.  This module closes the integration gap for real
workloads: BERT-for-MLM and GPT causal LM (one schedule body serves both —
the GPT (x, y) batch becomes the MLM shape with all-ones weights, a
causal layer stack, and its own head cell), stages = contiguous blocks of
encoder layers, driven through the SPMD ring schedule over a
('pipe', 'data') mesh.

Design (TPU-native, *uniform-schedule* form):

- The encoder layers — where the FLOPs and params live — are stacked into
  one [num_layers, ...] pytree and sharded P('pipe') on the stacked dim:
  each stage owns num_layers/S contiguous layers and scans over them.
- Embedding and MLM head are REPLICATED-COMPUTE: every stage evaluates
  them, but only stage 0 consumes the embedded activations (the ring
  schedule's injection mask) and only the last stage consumes the head
  (the loss mask), so the masked cotangents + the automatic psum of
  invariant-param grads yield exactly the right gradients — including the
  tied decoder, whose table grad is the psum of the stage-0 embedding
  contribution and the last-stage decode contribution.  This trades a
  little redundant forward compute for a schedule with NO special-cased
  first/last stage (Megatron instead places the embedding on stage 0 and
  shares it with the last stage via a dedicated all-reduce).
- Data parallelism composes on the 'data' mesh axis: the global batch
  shards over it, per-shard microbatches feed the ring, grads of
  replicated params psum over both axes automatically.
- Tensor parallelism composes on the 'model' mesh axis (reference:
  apex.transformer.parallel_state exists precisely to run TP+PP+DP
  jointly, SURVEY.md:149-151).  TPU-native form: the shard_map is manual
  over ('pipe', 'data') ONLY (``axis_names``), leaving 'model' an
  *automatic* axis inside the body — so the stage function runs the same
  GSPMD TP layers (column/row-parallel, ``tensor_parallel=True``) as the
  pure-TP path, with their sharding constraints binding to the still-auto
  model axis and GSPMD inserting the Megatron collectives inside each
  ring tick.  Stacked layer params shard over BOTH axes: P('pipe') on the
  stacked dim via in_specs, column/row metadata over 'model' riding along
  as the arrays' auto-axis sharding.  Embedding and MLM head stay
  replicated-compute over 'model' (their FLOPs are a rounding error at
  BERT scale; the encoder is where TP pays).

The param tree is IDENTICAL in content to the dense
``models.bert.BertForMaskedLM`` tree (``pack_params``/``unpack_params``
convert), so checkpoints interchange and tests compare trajectories
against the single-device model directly.

Dynamic loss scaling (fp16 O1/O2) composes with the schedule without any
per-microbatch plumbing: an overflow anywhere in the schedule poisons the
ACCUMULATED grads (inf/nan propagates through the scan and the psums), so
the post-schedule finite check sees it; rest-param grads are psum'd over
pipe+data (making their flag mesh-invariant already) and the stage-local
layer-grad flags are pmean'd over 'pipe', so every stage takes the same
all-or-none skip — the same protocol the TP and ZeRO paths use.  This goes
beyond the reference, whose pipeline schedules do not compose with apex
AMP's dynamic scaler (Megatron uses its own grad scaler).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_example_tpu import amp as amp_lib
from apex_example_tpu.amp.policy import Policy
from apex_example_tpu.engine import TrainState, _wrap_optimizer
from apex_example_tpu.models.bert import BertForMaskedLM, BertLayer
from apex_example_tpu.ops.layer_norm import layer_norm
from apex_example_tpu.ops.xentropy import softmax_cross_entropy
from apex_example_tpu.parallel.mesh import DATA_AXIS, PIPE_AXIS
from apex_example_tpu.transformer.pipeline_parallel.schedules import (
    pipeline_1f1b, spmd_pipeline)

def _rest_keys(dense_params) -> Tuple[str, ...]:
    """Everything that is not a stacked encoder layer — embedding + head
    params.  Derived from the tree itself so one pack/unpack pair serves
    both BertForMaskedLM (mlm_dense/mlm_ln/mlm_bias) and GPTForCausalLM
    (final_ln/lm_bias)."""
    return tuple(k for k in dense_params if not k.startswith("layer_"))


def pack_params(dense_params: Dict[str, Any], num_layers: int
                ) -> Dict[str, Any]:
    """Dense BertForMaskedLM/GPTForCausalLM tree ->
    {'rest': ..., 'layers': stacked}."""
    layers = [dense_params[f"layer_{i}"] for i in range(num_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {"rest": {k: dense_params[k] for k in _rest_keys(dense_params)},
            "layers": stacked}


def unpack_params(packed: Dict[str, Any], num_layers: int) -> Dict[str, Any]:
    out = dict(packed["rest"])
    for i in range(num_layers):
        out[f"layer_{i}"] = jax.tree_util.tree_map(
            lambda x: x[i], packed["layers"])
    return out


def _1f1b_order(num_layers: int, stages: int, num_chunks: int):
    """Dense-layer index for each (stage, chunk, slot): global stage
    v·S+s owns the contiguous dense block [(v·S+s)·per, +per) — the
    interleaved-virtual-stage assignment (device s holds chunks {v·S+s})."""
    if num_layers % (stages * num_chunks):
        raise ValueError(
            f"num_layers {num_layers} not divisible by stages {stages} x "
            f"chunks {num_chunks} — layers would be silently dropped")
    per = num_layers // (stages * num_chunks)
    return [[(v * stages + s) * per + i
             for v in range(num_chunks) for i in range(per)]
            for s in range(stages)], per


def pack_params_1f1b(dense_params: Dict[str, Any], num_layers: int,
                     stages: int, num_chunks: int = 1) -> Dict[str, Any]:
    """Dense tree -> {'rest', 'layers'} ARRANGED for the 1F1B schedules:
    layer leaves are [S, V, per, ...] with [s, v, i] holding dense layer
    (v·S+s)·per + i, so a P('pipe') shard hands device s exactly its
    chunks.  (The ring pack's contiguous [num_layers, ...] stack cannot
    express the interleaved assignment — chunk v·S+s for v>0 is not a
    contiguous slice of device s's shard.)"""
    order, per = _1f1b_order(num_layers, stages, num_chunks)
    rows = [jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs).reshape(num_chunks, per, *xs[0].shape),
        *[dense_params[f"layer_{j}"] for j in row]) for row in order]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)
    return {"rest": {k: dense_params[k] for k in _rest_keys(dense_params)},
            "layers": stacked}


def unpack_params_1f1b(packed: Dict[str, Any], num_layers: int,
                       stages: int, num_chunks: int = 1) -> Dict[str, Any]:
    out = dict(packed["rest"])
    order, per = _1f1b_order(num_layers, stages, num_chunks)
    for s, row in enumerate(order):
        for slot, j in enumerate(row):
            v, i = divmod(slot, per)
            out[f"layer_{j}"] = jax.tree_util.tree_map(
                lambda x, s=s, v=v, i=i: x[s, v, i], packed["layers"])
    return out


def _embed(rest, ids, model):
    """Embedding + post-embedding LN, matching BertForMaskedLM.__call__
    (GPTForCausalLM uses the identical names and math).

    Under CP x PP (``model.context_parallel``) ``ids`` is this shard's
    contiguous sequence chunk: positions offset by the context-shard
    index, exactly like the models' own CP branch (contiguous/ring
    layout; the zigzag layout is rejected at the factory)."""
    dtype = model.dtype
    ln_io = model.ln_dtype or dtype
    L = ids.shape[-1]
    x = jnp.take(rest["word_embeddings"]["embedding"], ids,
                 axis=0).astype(dtype)
    pos_tbl = rest["position_embeddings"]["embedding"]
    if getattr(model, "context_parallel", False):
        from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
        i = lax.axis_index(CONTEXT_AXIS)
        if getattr(model, "cp_mode", "ring") == "zigzag":
            # zigzag layout: this shard's halves are global chunks i and
            # 2n-1-i (the models' own CP branch algebra; the factory's
            # zigzag_shard pre-pass reordered the tokens to match)
            n = lax.axis_size(CONTEXT_AXIS)
            c = L // 2
            pos = jnp.concatenate([jnp.arange(c) + i * c,
                                   jnp.arange(c) + (2 * n - 1 - i) * c])
        else:
            pos = jnp.arange(L) + i * L
        x = x + jnp.take(pos_tbl, pos, axis=0)[None].astype(dtype)
    else:
        x = x + pos_tbl[:L][None].astype(dtype)
    x = layer_norm(x.astype(ln_io), rest["embeddings_ln"]["scale"],
                   rest["embeddings_ln"]["bias"])
    return x.astype(dtype)


def _head_loss_sum(rest, y, labels, weights, model: BertForMaskedLM):
    """MLM head (dense+gelu+LN, tied decoder) + weighted CE *sum*, matching
    BertForMaskedLM.__call__.  Returns the un-normalized Σ ce·w: the global
    masked-position denominator is applied outside the pipeline so the loss
    equals workloads.mlm_loss on the full batch exactly (a per-microbatch
    mean-of-means would weight microbatches with different masked counts
    unequally)."""
    dtype = model.dtype
    ln_io = model.ln_dtype or dtype
    x = y.astype(dtype) @ rest["mlm_dense"]["kernel"].astype(dtype) \
        + rest["mlm_dense"]["bias"].astype(dtype)
    x = jax.nn.gelu(x, approximate=False)
    x = layer_norm(x.astype(ln_io), rest["mlm_ln"]["scale"],
                   rest["mlm_ln"]["bias"]).astype(dtype)
    logits = x @ rest["word_embeddings"]["embedding"].astype(dtype).T
    logits = logits.astype(jnp.float32) + rest["mlm_bias"]
    ce = softmax_cross_entropy(logits, labels)
    return (ce * weights).sum()


def _gpt_head_loss_sum(rest, y, labels, weights, model):
    """GPT head (final LN + tied decoder) + CE *sum*, matching
    GPTForCausalLM.__call__.  ``weights`` is all-ones from the factory, so
    the shared global denominator turns the sum into exactly
    workloads.lm_loss's mean over the full batch."""
    dtype = model.dtype
    ln_io = model.ln_dtype or dtype
    x = layer_norm(y.astype(ln_io), rest["final_ln"]["scale"],
                   rest["final_ln"]["bias"]).astype(dtype)
    logits = x @ rest["word_embeddings"]["embedding"].astype(dtype).T
    logits = logits.astype(jnp.float32) + rest["lm_bias"]
    ce = softmax_cross_entropy(logits, labels)
    return (ce * weights).sum()


def _tp_layer_specs(model):
    """Per-leaf PartitionSpecs of ONE encoder layer under TP (the flax
    with_partitioning metadata of the column/row-parallel layers), shaped
    like an entry of the packed ``layers`` subtree minus the stacked dim."""
    import flax.linen as nn
    layer_mod = BertLayer(model.hidden_size, model.num_heads,
                          model.intermediate_size, model.dtype,
                          model.param_dtype, model.ln_dtype,
                          model.softmax_dtype,
                          fused_attention=model.fused_attention,
                          tensor_parallel=True,
                          sequence_parallel=model.sequence_parallel)
    abs_x = jax.ShapeDtypeStruct((1, 8, model.hidden_size), model.dtype)
    abs_vars = jax.eval_shape(
        lambda r, x: layer_mod.init(r, x, None),
        jax.random.PRNGKey(0), abs_x)
    return nn.get_partition_spec(abs_vars)["params"]


def _moe_pp_layers_spec(layers_tree):
    """Per-leaf specs for an EP x PP packed ``layers`` subtree: expert
    stacks (workloads._is_expert_leaf) shard [stacked->pipe,
    experts->data], everything else P('pipe') on the stacked dim only.
    ONE definition shared by the in_specs and the placement shardings."""
    from apex_example_tpu.workloads import _is_expert_leaf
    return jax.tree_util.tree_map_with_path(
        lambda path, _leaf: P(PIPE_AXIS, DATA_AXIS)
        if _is_expert_leaf(path) else P(PIPE_AXIS), layers_tree)


def _is_moe_ep(model) -> bool:
    return bool(getattr(model, "moe_experts", 0)) and \
        getattr(model, "moe_axis_name", "") == DATA_AXIS


def bert_pp_state_shardings(mesh: Mesh, state: TrainState, optimizer,
                            model: Optional[BertForMaskedLM] = None
                            ) -> TrainState:
    """NamedSharding pytree for a packed-params TrainState: layers shard
    their stacked dim over 'pipe', everything else replicates, optimizer
    state mirrors its params-shaped fields.  Used both to place the initial
    state and as the orbax restore template (cf.
    utils.checkpoint.restore_under_mesh for the DP/ZeRO/CP paths).

    With a ``tensor_parallel`` model, layer leaves additionally shard over
    'model' per the TP layers' column/row partitioning metadata —
    P('pipe', …, 'model', …) — the jointly-sharded placement of the TP×PP
    composition (rest/embedding/head still replicate)."""
    from apex_example_tpu.engine import _opt_state_specs
    tmap = jax.tree_util.tree_map
    if model is not None and model.tensor_parallel:
        # Pad between the 'pipe'-sharded stacked dim and the layer's own
        # TP spec: the ring pack has ONE leading index dim ([L, ...]), the
        # 1F1B arranged pack has THREE ([S, V, per, ...]) — the TP axes
        # always name the trailing (per-layer) dims.
        layer_specs = tmap(
            lambda s, leaf: P(PIPE_AXIS,
                              *([None] * (leaf.ndim - 1 - len(tuple(s)))),
                              *tuple(s)),
            _tp_layer_specs(model), state.params["layers"],
            is_leaf=lambda v: isinstance(v, P))
    elif model is not None and _is_moe_ep(model):
        layer_specs = _moe_pp_layers_spec(state.params["layers"])
    else:
        layer_specs = tmap(lambda _: P(PIPE_AXIS), state.params["layers"])
    params_specs = {
        "rest": tmap(lambda _: P(), state.params["rest"]),
        "layers": layer_specs,
    }
    abs_params = tmap(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                      state.params)
    # PipelineZeroAdam's flat [S, padded] buffers do not mirror the params
    # tree — they carry their own spec (P(pipe, data)).
    opt_specs = optimizer.state_spec() \
        if isinstance(optimizer, PipelineZeroAdam) \
        else _opt_state_specs(optimizer, abs_params, params_specs)
    spec_state = TrainState(
        step=P(), params=params_specs,
        batch_stats=tmap(lambda _: P(), state.batch_stats),
        opt_state=opt_specs,
        scaler=tmap(lambda _: P(), state.scaler))
    from jax.sharding import NamedSharding
    return tmap(lambda s: NamedSharding(mesh, s), spec_state,
                is_leaf=lambda v: isinstance(v, P))


class PipelineZeroState(NamedTuple):
    """ZeRO x PP optimizer state: one flat fp32 buffer pair for the
    (pipe-invariant, replicated-compute) embedding/head params, sharded
    P('data'), and one per-stage pair for the layer blocks, sharded
    P('pipe', 'data')."""
    step: jnp.ndarray
    rest_mu: jnp.ndarray
    rest_nu: jnp.ndarray
    layer_mu: jnp.ndarray
    layer_nu: jnp.ndarray


class PipelineZeroAdam:
    """ZeRO-1 Adam for the packed ``{'rest', 'layers'}`` pipeline tree —
    the ZeRO x PP pairing (the reference's distributed_fused_adam is run
    with Megatron PP in practice; DeepSpeed's "3D" stacks the same way).

    Each pipe stage flattens ITS local packed slice (rest + its layer
    block) into one fp32 buffer whose (m, v) shard over 'data' via the
    inner :class:`DistributedFusedAdam` — per-device optimizer state is
    1/data-axis of the STAGE-local params.  (The 'rest' state is
    per-stage duplicated, mirroring the schedule's replicated-compute
    embedding/head: still 1/dp of the non-ZeRO form per device.)

    ``init`` runs OUTSIDE the mesh on the global packed tree and returns
    ``[S, padded_local]`` buffers; ``state_spec`` shards them
    P('pipe', 'data'); ``apply`` runs INSIDE the shard_map on the local
    slice (the inner optimizer sees exactly its per-stage tree, whose
    flat size matches init's arithmetic because every ``layers`` leaf
    splits its stacked dim 0 S-ways).  The inner optimizer must carry
    ``grads_global_mean=True``: the PP losses are psum-normalized
    globally, so grads arrive as the true global mean (see
    optim/distributed.py).
    """

    def __init__(self, zadam, stages: int):
        from apex_example_tpu.optim.distributed import DistributedFusedAdam
        if not isinstance(zadam, DistributedFusedAdam):
            raise TypeError(f"PipelineZeroAdam wraps DistributedFusedAdam, "
                            f"got {type(zadam).__name__}")
        if not zadam.grads_global_mean:
            raise ValueError(
                "PipelineZeroAdam needs DistributedFusedAdam("
                "grads_global_mean=True): the PP losses are globally "
                "psum-normalized, so dividing by world again would hand "
                "Adam g/world")
        self.z = zadam
        self.stages = stages

    def _padded_sizes(self, packed):
        from apex_example_tpu.optim.distributed import (_flat_size,
                                                        _padded_size)
        S = self.stages
        rest = _padded_size(_flat_size(packed["rest"]), self.z.world)
        layers = _padded_size(
            sum(int(l.size) // S
                for l in jax.tree_util.tree_leaves(packed["layers"])),
            self.z.world)
        return rest, layers

    def init(self, packed):
        if not (isinstance(packed, dict) and "rest" in packed):
            # The harness bootstraps a dense state first (its opt state is
            # discarded and rebuilt from the packed tree) — mirror
            # PipelineFusedLAMB's any-tree tolerance with a throwaway
            # inner-form state.
            return self.z.init(packed)
        pr, pl = self._padded_sizes(packed)
        return PipelineZeroState(
            step=jnp.zeros((), jnp.int32),
            rest_mu=jnp.zeros((pr,), jnp.float32),
            rest_nu=jnp.zeros((pr,), jnp.float32),
            layer_mu=jnp.zeros((self.stages, pl), jnp.float32),
            layer_nu=jnp.zeros((self.stages, pl), jnp.float32))

    def state_spec(self):
        d = self.z.axis_name
        return PipelineZeroState(step=P(), rest_mu=P(d), rest_nu=P(d),
                                 layer_mu=P(PIPE_AXIS, d),
                                 layer_nu=P(PIPE_AXIS, d))

    def apply(self, grads, state, params):
        from apex_example_tpu.optim.distributed import ZeroAdamState
        # Two independent flat buffers so the vma typing stays exact:
        # 'rest' (pipe-INVARIANT inputs -> invariant outputs, no extra
        # collective) and this stage's layer block (pipe-varying, the
        # [S, padded] buffers arrive as this (stage, data) cell's
        # [1, padded/dp] slice; the inner contract is the bare local
        # shard of a P(data) buffer).
        new_rest, st_r = self.z.apply(
            grads["rest"],
            ZeroAdamState(step=state.step, mu=state.rest_mu,
                          nu=state.rest_nu),
            params["rest"])
        new_layers, st_l = self.z.apply(
            grads["layers"],
            ZeroAdamState(step=state.step, mu=state.layer_mu[0],
                          nu=state.layer_nu[0]),
            params["layers"])
        # One step counter: both inner applies take the same skip decision
        # whenever the engine's global finite flag lets the update stand
        # (a partially-finite step is rolled back wholesale by the
        # engine's select_tree), so st_r.step is THE step.
        return ({"rest": new_rest, "layers": new_layers},
                PipelineZeroState(step=st_r.step, rest_mu=st_r.mu,
                                  rest_nu=st_r.nu,
                                  layer_mu=st_l.mu[None],
                                  layer_nu=st_l.nu[None]))


class PipelineFusedLAMB:
    """FusedLAMB for the packed ``{'rest', 'layers'}`` pipeline tree.

    Plain FusedLAMB on the packed tree would be silently wrong twice over
    (which is why :func:`make_bert_pp_train_step` rejects it): a stacked
    ``[num_layers, …]`` leaf would get ONE cross-layer trust ratio where
    the dense model computes one per layer's tensor, and the global
    gradient-norm clip would see only THIS stage's layer grads.  This
    wrapper restores the dense semantics exactly:

    - stacked leaves run LAMB stage 1/2 per layer slice (a static unrolled
      loop over the stage's ``per_stage`` layers — the same per-leaf fused
      kernels the dense path runs, so trust ratios match it bitwise);
    - the clip norm is assembled globally: Σ‖g‖² of the (pipe-invariant)
      rest leaves plus a psum over 'pipe' of the stage-local layer Σ‖g‖².

    ``apply`` must run inside shard_map with ``axis_name`` bound (the PP
    per-shard step); ``init`` works on any tree and simply mirrors it.
    Under TP×PP the model axis stays automatic, so the per-layer norms are
    full logical reductions — GSPMD inserts the model-axis psums.
    """

    def __init__(self, lamb, axis_name: str = PIPE_AXIS,
                 stacked_dims: int = 1):
        from apex_example_tpu.optim.fused import FusedLAMB
        if not isinstance(lamb, FusedLAMB):
            raise TypeError(f"PipelineFusedLAMB wraps FusedLAMB, got "
                            f"{type(lamb).__name__}")
        self.lamb = lamb
        self.axis_name = axis_name
        # Leading per-layer index dims on each stacked leaf: 1 for the ring
        # pack ([num_layers, ...]), 3 for the 1F1B arranged pack
        # ([S, V, per, ...]) — every one of them must be unrolled or a
        # whole [V, per] block would share one trust ratio.
        self.stacked_dims = stacked_dims

    def init(self, params):
        return self.lamb.init(params)

    def apply(self, grads, state, params):
        from apex_example_tpu.ops.multi_tensor import sqsum_leaf
        from apex_example_tpu.optim.fused import (LambState, lamb_clip_scale,
                                                  lamb_step_scalars,
                                                  lamb_update_leaf)
        L = self.lamb
        step = state.step + 1
        c1, c2, lr = lamb_step_scalars(L, step)

        tleaves = jax.tree_util.tree_leaves
        if L.max_grad_norm and L.max_grad_norm > 0:
            rest_sq = sum(sqsum_leaf(g) for g in tleaves(grads["rest"]))
            layer_sq = sum(sqsum_leaf(g) for g in tleaves(grads["layers"]))
            # psum → pipe-invariant, so the shared clip scale (and with it
            # every rest-leaf update) stays invariant too.
            gscale = lamb_clip_scale(
                L, jnp.sqrt(rest_sq + lax.psum(layer_sq, self.axis_name)))
        else:
            gscale = jnp.asarray(1.0, jnp.float32)

        def one(p, g, m, v):
            return lamb_update_leaf(L, p, g, m, v, c1, c2, lr, gscale)

        def stacked(p, g, m, v):
            lead = p.shape[:self.stacked_dims]
            n = 1
            for s in lead:
                n *= s
            rs = lambda t: t.reshape((n,) + p.shape[self.stacked_dims:])
            pf, gf, mf, vf = rs(p), rs(g), rs(m), rs(v)
            outs = [one(pf[l], gf[l], mf[l], vf[l]) for l in range(n)]
            return tuple(
                jnp.stack([o[i] for o in outs]).reshape(p.shape)
                for i in range(3))

        def sweep(fn, sub):
            flat_p, treedef = jax.tree_util.tree_flatten(params[sub])
            flat = [treedef.flatten_up_to(t[sub])
                    for t in (grads, state.mu, state.nu)]
            outs = [fn(p, g, m, v) for p, g, m, v in zip(flat_p, *flat)]
            return tuple(treedef.unflatten([o[i] for o in outs])
                         for i in range(3))

        rp, rm, rv = sweep(one, "rest")
        sp, sm, sv = sweep(stacked, "layers")
        return ({"rest": rp, "layers": sp},
                LambState(step, {"rest": rm, "layers": sm},
                          {"rest": rv, "layers": sv}))


def make_bert_pp_train_step(mesh: Mesh, model: BertForMaskedLM, optimizer,
                            policy: Policy, microbatches: int,
                            donate: bool = True, schedule: str = "ring",
                            num_chunks: int = 1,
                            moe_aux_weight: float = 1e-2):
    """Jitted (state, (ids, (labels, weights))) -> (state, metrics) over a
    ('pipe', 'data') mesh.  ``state.params`` is the packed tree with
    ``layers`` leaves carrying a leading stacked-stage dim (shard
    P('pipe')); batch shards over 'data' and is split into ``microbatches``
    ring slots per shard.

    ``schedule`` picks the pipeline program (all three trajectory-match
    the dense model; reference: the three apex schedule entry points):

    - "ring" (default): the SPMD ring (:func:`schedules.spmd_pipeline`),
      backward derived by autodiff.  State layout: ``pack_params``'s
      [num_layers, ...] stack.  The only schedule that composes with
      tensor parallelism.
    - "1f1b": TRUE 1F1B (:func:`schedules.pipeline_1f1b`) — bounded
      in-flight activations independent of the microbatch count.
      Embedding runs batched OUTSIDE the schedule (its backward completes
      through the returned input cotangents); the parametrized head rides
      the loss cell via ``head_params``.  State layout:
      ``pack_params_1f1b``'s arranged [S, V, per, ...] stack.
    - "interleaved": 1F1B with ``num_chunks`` virtual stages per device
      (the reference's interleaved variant; needs microbatches % S == 0
      and num_layers % (S·num_chunks) == 0).
    """
    S = mesh.shape[PIPE_AXIS]
    if schedule not in ("ring", "1f1b", "interleaved"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "interleaved":
        if num_chunks < 2:
            raise ValueError("interleaved schedule needs num_chunks >= 2")
    elif num_chunks != 1:
        # Reject rather than ignore (the same contract train.py states for
        # --virtual-stages): a caller asking for virtual stages on a
        # non-interleaved schedule would otherwise silently get none.
        raise ValueError(f"num_chunks={num_chunks} only applies to the "
                         f"interleaved schedule, not {schedule!r}")
    V = num_chunks if schedule == "interleaved" else 1
    if model.num_layers % (S * V):
        raise ValueError(f"num_layers {model.num_layers} not divisible by "
                         f"pipeline size {S} x chunks {V}")
    from apex_example_tpu.parallel.mesh import (CONTEXT_AXIS,
                                                require_model_axis_match)
    tp = require_model_axis_match(mesh, model.tensor_parallel)
    # TP composes with ALL THREE schedules (round 5; r4 allowed ring
    # only).  NOT via the plain cond dispatch: TP collectives inside the
    # per-stage lax.cond COMPILE fine but DEADLOCK at runtime — devices
    # disagree on the global cross-program collective order (PERF.md
    # round-5 note).  The 1F1B/interleaved cells therefore require the
    # branch-free uniform_collectives form, passed below; any new TP call
    # site of pipeline_1f1b must pass it too.
    # CP x PP (round 5): the sequence additionally shards over 'context'
    # as another manual axis — the KV ring runs INSIDE each stage cell,
    # positions offset in _embed, losses psum over (data, context).  The
    # same uniform-collectives requirement applies on 1F1B/interleaved
    # (the KV ring's manual ppermutes inside a cond would diverge the
    # collective order exactly like the TP case).
    cp = mesh.shape.get(CONTEXT_AXIS, 1)
    model_is_cp = bool(getattr(model, "context_parallel", False))
    if cp > 1 and not model_is_cp:
        raise ValueError(f"mesh has '{CONTEXT_AXIS}' size {cp} but the "
                         "model was built without context_parallel=True")
    if model_is_cp and cp <= 1:
        raise ValueError("context_parallel model needs a mesh with a "
                         f"nontrivial '{CONTEXT_AXIS}' axis")
    if cp > 1 and getattr(model, "cp_mode", "ring") == "zigzag":
        from apex_example_tpu.models.gpt import GPTForCausalLM as _GPT
        if not isinstance(model, _GPT):
            raise ValueError(
                "CP x PP zigzag is the load-balanced CAUSAL layout (gpt "
                "archs); bidirectional BERT does uniform ring work "
                "already")
    # EP x PP (round 5): switch-MoE FFNs inside the ring schedule's
    # stages — the expert all_to_all rides the manual 'data' axis inside
    # each tick, the per-(stage, microbatch) Switch aux loss rides the
    # schedule's carry (spmd_pipeline with_aux).  Expert stacks shard
    # [layers->pipe, experts->data] jointly.  moe_axis_name='data' is the
    # EP form; any UNBOUND axis name (e.g. 'expert') runs the dense-
    # reference experts on replicated stacks — the exact golden the tests
    # compare against, through this same factory.
    moe = int(getattr(model, "moe_experts", 0) or 0)
    moe_ep = moe > 0 and getattr(model, "moe_axis_name", "") == DATA_AXIS
    if moe:
        if schedule != "ring":
            raise ValueError(
                "MoE composes with the ring schedule only: the 1F1B value "
                "program would need the aux loss threaded through its "
                "masked cells and the expert all_to_all runs per tick "
                "either way (no memory win to buy)")
        if cp > 1 or tp > 1:
            raise ValueError("MoE x PP composes pairwise only (no "
                             "MoE x PP x TP/CP triple yet)")
        if moe_ep and moe % mesh.shape[DATA_AXIS]:
            raise ValueError(
                f"moe_experts={moe} must be a multiple of the data-axis "
                f"size {mesh.shape[DATA_AXIS]}")
        if moe_ep and isinstance(optimizer, PipelineFusedLAMB):
            raise ValueError(
                "PipelineFusedLAMB does not compose with EP x PP: its "
                "clip norm psums over 'pipe' only, but EP expert-stack "
                "grads vary over 'data' too — every replicated leaf would "
                "silently receive a different update per data shard")
    from apex_example_tpu.optim.fused import FusedLAMB, FusedNovoGrad
    if isinstance(optimizer, FusedLAMB):
        raise ValueError(
            "bare FusedLAMB under PP would collapse each stacked "
            "[num_layers, ...] leaf into ONE cross-layer trust ratio and "
            "clip on a stage-local grad norm; wrap it in PipelineFusedLAMB")
    if isinstance(optimizer, FusedNovoGrad):
        raise ValueError(
            "FusedNovoGrad under PP would collapse its per-TENSOR second "
            "moment (EMA of ||g||²) across each stage's stacked layers; "
            "no pipeline form exists yet")
    if isinstance(optimizer, PipelineFusedLAMB):
        # The wrapper's leading-index-dim count must match this schedule's
        # param layout: the ring pack stacks [num_layers, ...] (1 dim), the
        # 1F1B/interleaved arranged pack stacks [S, V, per, ...] (3 dims).
        # A mismatch trains silently wrong — either one trust ratio per
        # whole [V, per] block, or per-row ratios of a layout that does
        # not exist.
        want = 1 if schedule == "ring" else 3
        if optimizer.stacked_dims != want:
            raise ValueError(
                f"PipelineFusedLAMB(stacked_dims={optimizer.stacked_dims}) "
                f"does not match the {schedule!r} schedule's param layout "
                f"(needs stacked_dims={want})")
    opt = _wrap_optimizer(optimizer)
    from apex_example_tpu.models.gpt import GPTForCausalLM
    is_gpt = isinstance(model, GPTForCausalLM)
    head_sum = _gpt_head_loss_sum if is_gpt else _head_loss_sum
    layer_mod = BertLayer(model.hidden_size, model.num_heads,
                          model.intermediate_size, model.dtype,
                          model.param_dtype, model.ln_dtype,
                          model.softmax_dtype,
                          fused_attention=model.fused_attention,
                          tensor_parallel=model.tensor_parallel,
                          sequence_parallel=model.sequence_parallel,
                          context_parallel=model_is_cp,
                          cp_mode=getattr(model, "cp_mode", "ring"),
                          moe_experts=moe,
                          moe_capacity_factor=getattr(
                              model, "moe_capacity_factor", 1.25),
                          moe_axis_name=getattr(model, "moe_axis_name",
                                                "expert"),
                          moe_top_k=getattr(model, "moe_top_k", 1),
                          causal=is_gpt)
    red_axes = (DATA_AXIS, CONTEXT_AXIS) if cp > 1 else DATA_AXIS

    def _unpack(batch):
        """One schedule body serves both objectives: GPT's (x, y) pair
        becomes the MLM shape with all-ones weights, under which the
        global weighted-CE normalization IS the next-token mean."""
        if is_gpt:
            ids, labels = batch
            return ids, labels, jnp.ones(labels.shape, jnp.float32)
        ids, (labels, weights) = batch
        return ids, labels, weights

    def stage_fn(stage_layers, x):
        # stage_layers leaves: [per_stage, ...] — scan applies them in
        # order (this stage's contiguous block of encoder layers).  The
        # injected activation is pipe-invariant while the layer params
        # vary over pipe; align the scan carry's vma typing up front.
        if PIPE_AXIS not in getattr(jax.typeof(x), "vma", frozenset()):
            x = lax.pcast(x, PIPE_AXIS, to="varying")

        if moe:
            # MoE layers return (h, aux); the stage emits the SUM of its
            # layers' Switch balance losses alongside the activation
            # (spmd_pipeline with_aux accumulates it across the ring).
            def body_aux(carry, p):
                h, a = carry
                h, aux = layer_mod.apply({"params": p}, h, None)
                return (h, a + aux.astype(jnp.float32)), None
            # the aux carry must enter with the activation's shard-
            # variance type (pipe + data) or the scan carry typing trips
            a0 = lax.pcast(
                jnp.zeros((), jnp.float32),
                tuple(sorted(getattr(jax.typeof(x), "vma", frozenset()))),
                to="varying")
            (y, aux_sum), _ = lax.scan(body_aux, (x, a0), stage_layers)
            return y, aux_sum

        def body(h, p):
            return layer_mod.apply({"params": p}, h, None), None
        y, _ = lax.scan(body, x, stage_layers)
        return y

    def _split(ids):
        M = microbatches
        b = ids.shape[0]
        if b % M:
            raise ValueError(f"per-shard batch {b} not divisible by "
                             f"microbatches {M}")
        return M, b, lambda a: a.reshape(M, b // M, *a.shape[1:])

    def finish(state: TrainState, grads, loss):
        """Unscale → (all-or-none) update → scaler bookkeeping — shared by
        every schedule's per-shard step."""
        grads, grads_finite = amp_lib.unscale_grads(grads, state.scaler)
        # layers grads vary over 'pipe' (each stage owns its block), so the
        # all-leaves finite flag does too; under EP the expert-stack grads
        # additionally vary over 'data' (each shard owns its experts).
        # Make the flag mesh-invariant for the replicated metrics/scaler.
        finite_axes = (PIPE_AXIS, DATA_AXIS) if moe_ep else PIPE_AXIS
        grads_finite = lax.pmean(
            grads_finite.astype(jnp.float32), finite_axes) == 1.0
        new_params, new_opt_state = opt.apply(grads, state.opt_state,
                                              state.params)
        if policy.uses_dynamic_scaling:
            # Overflow => all-or-none skip on every stage: the flag is
            # mesh-invariant (pmean above), so each stage's where-select
            # takes the same branch and the sharded state stays consistent.
            new_params = amp_lib.select_tree(grads_finite, new_params,
                                             state.params)
            new_opt_state = amp_lib.select_tree(grads_finite, new_opt_state,
                                                state.opt_state)
        scaler = amp_lib.update_scaler(state.scaler, grads_finite)
        metrics = {"loss": loss, "scale": scaler.scale,
                   "grads_finite": grads_finite.astype(jnp.float32)}
        return TrainState(step=state.step + 1, params=new_params,
                          batch_stats=state.batch_stats,
                          opt_state=new_opt_state, scaler=scaler), metrics

    def per_shard_ring(state: TrainState, batch):
        ids, labels, weights = _unpack(batch)
        M, b, mb = _split(ids)

        def scaled_loss_fn(params):
            rest = params["rest"]
            x = _embed(rest, ids, model)          # replicated compute
            # Global masked-position denominator: per-microbatch SUMS ride
            # the schedule (scaled by M to cancel its mean), the psum stitches
            # the shards — the result equals mlm_loss on the full batch.
            denom = jnp.maximum(lax.psum(weights.sum(), red_axes), 1.0)
            out = spmd_pipeline(
                stage_fn,
                lambda y, tgt: head_sum(rest, y, tgt[0], tgt[1],
                                        model) * M / denom,
                params["layers"], mb(x), (mb(labels), mb(weights)),
                with_aux=bool(moe))
            if moe:
                loss, aux = out
                # aux: psum-over-pipe of per-(stage, microbatch) Switch
                # sums / M (spmd_pipeline) -> per-layer mean, then the
                # data-shard mean — the dense model's aux_total/L averaged
                # over routing blocks (the blocked-dense golden contract).
                aux = lax.pmean(aux / model.num_layers, DATA_AXIS)
                loss = lax.psum(loss, red_axes) \
                    + jnp.asarray(moe_aux_weight, jnp.float32) * aux
            else:
                loss = lax.psum(out, red_axes)
            return amp_lib.scale_loss(loss, state.scaler), loss

        grads, loss = jax.grad(scaled_loss_fn, has_aux=True)(state.params)
        return finish(state, grads, loss)

    def per_shard_1f1b(state: TrainState, batch):
        """True-1F1B/interleaved cell: the schedule is a VALUE program
        (manual vjp per tick), so the embedding/head backward is assembled
        around it — embed batched outside with its vjp saved, head params
        ride the loss cell, and the schedule's returned input cotangents
        close the embedding chain.  Data-axis grad reduction is implicit:
        params enter data-INVARIANT, so each vjp's AD inserts the data
        psum (safe inside the schedule's cond — the action tables vary
        over 'pipe' only, every data shard takes the same branch); the
        pipe axis, over which the predicates DO vary, is kept local and
        reduced with the two explicit psums below."""
        ids, labels, weights = _unpack(batch)
        M, b, mb = _split(ids)
        rest = state.params["rest"]
        x, vjp_embed = jax.vjp(lambda r: _embed(r, ids, model), rest)
        denom = jnp.maximum(lax.psum(weights.sum(), red_axes), 1.0)

        def last_fn(hp, y, tgt):
            raw = head_sum(hp, y, tgt[0], tgt[1], model) * M / denom
            return amp_lib.scale_loss(raw, state.scaler)

        layers = jax.tree_util.tree_map(lambda l: l[0],
                                        state.params["layers"])  # [V, …]
        if V == 1:
            layers = jax.tree_util.tree_map(lambda l: l[0], layers)
        sloss, glayers, ghead, dxa = pipeline_1f1b(
            stage_fn, last_fn, layers, mb(x),
            (mb(labels), mb(weights)), num_chunks=V, head_params=rest,
            # TP: the stage/head cells contain GSPMD model-axis collectives
            # — the cond dispatch would give devices divergent collective
            # orders and deadlock; the branch-free masked form keeps one
            # uniform order (see pipeline_1f1b docstring).  The CP KV
            # ring's manual ppermutes have the same requirement.
            uniform_collectives=tp > 1 or cp > 1)
        if V == 1:
            glayers = jax.tree_util.tree_map(lambda g: g[None], glayers)
        glayers = jax.tree_util.tree_map(lambda g: g[None], glayers)
        # Cross-pipe collection: head grads live on the last stage, input
        # cotangents on stage 0 — exact zeros elsewhere.
        ghead = jax.tree_util.tree_map(lambda g: lax.psum(g, PIPE_AXIS),
                                       ghead)
        dxa = lax.psum(dxa, PIPE_AXIS)
        (g_embed,) = vjp_embed(
            dxa.reshape(b, *x.shape[1:]).astype(x.dtype))
        grads = {"rest": jax.tree_util.tree_map(
                    lambda a, c: a + c.astype(a.dtype), ghead, g_embed),
                 "layers": glayers}
        sloss = lax.psum(sloss, red_axes)
        loss = sloss if state.scaler.identity \
            else sloss / state.scaler.scale
        return finish(state, grads, loss)

    per_shard = per_shard_ring if schedule == "ring" else per_shard_1f1b

    # Prefix specs: layers shard their stacked dim over 'pipe'; everything
    # else (embedding/head params, optimizer scalars) replicates.  The
    # optimizer state mirrors the params tree per-field
    # (engine._opt_state_specs), so the same {'rest': P(), 'layers':
    # P('pipe')} prefix applies inside each of its (mu, nu, ...) fields.
    from apex_example_tpu.engine import _opt_state_specs
    if isinstance(optimizer, PipelineZeroAdam):
        # ZeRO x PP bounds: the flat-buffer slice assumes replicated-over-
        # data, non-model-sharded stage params.
        if tp > 1 or cp > 1 or moe:
            raise ValueError("PipelineZeroAdam (ZeRO x PP) composes "
                             "pairwise only — no TP/CP/MoE triple yet")
        if optimizer.stages != S:
            raise ValueError(f"PipelineZeroAdam(stages={optimizer.stages}) "
                             f"does not match the mesh's pipe size {S}")
    if moe_ep:
        # Per-leaf specs (the prefix trick cannot single out the expert
        # stacks): abstract-init the model, pack, and mark expert leaves
        # [stacked->pipe, experts->data].
        abs_params = jax.eval_shape(
            lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))["params"]
        abs_packed = jax.tree_util.tree_map(
            lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype),
            jax.eval_shape(lambda p: pack_params(p, model.num_layers),
                           abs_params))
        params_spec = {"rest": jax.tree_util.tree_map(
                           lambda _: P(), abs_packed["rest"]),
                       "layers": _moe_pp_layers_spec(abs_packed["layers"])}
        opt_spec = _opt_state_specs(optimizer, abs_packed, params_spec)
    elif isinstance(optimizer, PipelineZeroAdam):
        params_spec = {"rest": P(), "layers": P(PIPE_AXIS)}
        opt_spec = optimizer.state_spec()     # flat [S, padded] buffers
    else:
        params_spec = {"rest": P(), "layers": P(PIPE_AXIS)}
        probe = {"rest": jax.ShapeDtypeStruct((), jnp.float32),
                 "layers": jax.ShapeDtypeStruct((), jnp.float32)}
        opt_spec = _opt_state_specs(optimizer, probe, params_spec)
    state_spec = TrainState(step=P(), params=params_spec, batch_stats=P(),
                            opt_state=opt_spec, scaler=P())
    # TP×PP: manual over (pipe, data) — 'model' stays automatic so the TP
    # layers' GSPMD constraints inside the body bind to it.  CP×PP adds
    # 'context' to the MANUAL set (the KV ring's ppermutes are manual-axis
    # collectives).  The specs name manual axes; the layer leaves'
    # model-axis sharding rides along from the arrays' placement
    # (bert_pp_state_shardings).
    from apex_example_tpu.workloads import partial_manual_axis_names
    manual = frozenset({PIPE_AXIS, DATA_AXIS}
                       | ({CONTEXT_AXIS} if cp > 1 else set()))
    kw = partial_manual_axis_names(mesh, model, manual)
    b = P(DATA_AXIS, CONTEXT_AXIS) if cp > 1 else P(DATA_AXIS)
    bspec = (b, b) if is_gpt else (b, (b, b))
    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(state_spec, bspec),
        out_specs=(state_spec, P()), **kw)
    if cp > 1 and getattr(model, "cp_mode", "ring") == "zigzag":
        # zigzag x PP: reorder the (x, y) LM pair into the zigzag layout
        # before the shard_map, so P('context') hands device i its
        # (i, 2n-1-i) chunk pair — the same pre-pass the pure-CP GPT
        # factory applies; _embed's zigzag position ids follow.
        from apex_example_tpu.parallel.context_parallel import zigzag_shard
        inner = sharded

        def sharded(state, batch):
            x, y = batch
            return inner(state, (zigzag_shard(x, cp), zigzag_shard(y, cp)))
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())
