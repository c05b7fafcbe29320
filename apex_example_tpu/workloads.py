"""Workload-specific losses and step builders (C4 BERT-MLM, C5 TXL-LM).

The classification engine (engine.py) covers C1–C3.  BERT reuses it with an
MLM loss (the label pytree is (labels, weights)); Transformer-XL needs its
own step because segment recurrence threads a memory carry alongside the
train state — the memory is per-replica activation state (batch-sharded under
DDP, P(None, "data") on its (layers, B, mem, d) layout), unlike the
replicated TrainState.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_example_tpu import amp as amp_lib
from apex_example_tpu.amp.policy import Policy
from apex_example_tpu.engine import TrainState, _wrap_optimizer
from apex_example_tpu.obs.spans import device_span
from apex_example_tpu.ops._vma import vary_like
from apex_example_tpu.ops.xentropy import softmax_cross_entropy
from apex_example_tpu.parallel.distributed import DDPConfig, allreduce_grads
from apex_example_tpu.parallel.mesh import DATA_AXIS


def mlm_loss(logits: jnp.ndarray, target: Tuple[jnp.ndarray, jnp.ndarray]
             ) -> jnp.ndarray:
    """Masked-LM loss: mean CE over masked positions only (weights mark
    them).  target = (labels, weights).  This form takes logits of every
    row (evaluation, the model-parallel steps); a train step that holds the
    encoder's output and the model's head apart takes ``mlm_loss.over_rows``
    and forms logits for the labelled rows alone.  Uses the fused-CE op,
    whose backward rematerializes the probabilities instead of saving them
    (ops/xentropy.py, the contrib-xentropy analog)."""
    labels, weights = target
    with device_span("loss"):
        ce = softmax_cross_entropy(logits, labels)
        denom = jnp.maximum(weights.sum(), 1.0)
        return (ce * weights).sum() / denom


# Rows of one block of the row-wise form: its float32 logits (1024 x 30522
# at BERT's vocabulary: 125 MB) are the largest array either pass holds.
MLM_BLOCK_ROWS = 1024


def _mlm_loss_over_rows(hidden: jnp.ndarray, head, params,
                        target: Tuple[jnp.ndarray, jnp.ndarray]
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``mlm_loss`` from the encoder's output ``hidden`` (B, S, H) and the
    model's ``head`` ((params, rows (R, H)) -> logits (R, V)): the same loss
    and the same gradients as ``mlm_loss(head(params, hidden), target)``,
    with the head and the CE evaluated on the rows of non-zero weight only
    (Google BERT's ``gather_indexes``, NVIDIA BERT's ``dense_seq_output``).

    The labelled rows are ordered first (a stable sort) and walked in
    blocks of ``MLM_BLOCK_ROWS``: gather, head, CE, weighted sum.  Both
    passes loop as many times as there are blocks holding a labelled row —
    the count is read from the batch, no label is dropped — and the backward
    forms each block's logits again, so neither holds more than one block's.
    A row of weight 0 inside the last block adds exactly 0 to the loss and
    to every gradient, as every such row does in the all-rows form.

    Returns ``(loss, head_rows)``: the rows the head ran on (blocks x block
    rows), float32.
    """
    labels, weights = target
    n = weights.size
    r = min(MLM_BLOCK_ROWS, n)
    with device_span("loss"):
        rows = hidden.reshape(n, -1)
        flat_w = weights.reshape(n).astype(jnp.float32)
        labelled = flat_w != 0
        blocks = (labelled.sum() + (r - 1)) // r
        # sorted place -> row; the tail fills the last block with rows past
        # the end (gathered as 0, of weight 0)
        order = jnp.pad(jnp.argsort(~labelled, stable=True),
                        (0, -n % r), constant_values=n)
        take = lambda a: jnp.take(a.reshape(n), order, mode="fill",
                                  fill_value=0)
        denom = jnp.maximum(flat_w.sum(), 1.0)
        # row -> sorted place, past the end for a row of no weight
        place = jnp.where(labelled, jnp.cumsum(labelled) - 1, order.size)
        # Inside shard_map a replicated parameter met by shard-varying rows
        # has its gradient psum-ed where they meet: that would be inside a
        # loop whose trip count differs from shard to shard.  Cast once,
        # here, and autodiff sums once, after the loop.
        varying = jax.tree_util.tree_map(lambda p: vary_like(p, rows),
                                         params)
        head_fn, consts = jax.closure_convert(
            lambda x: head(varying, x), rows[:r])
        loss = _blocked_ce(head_fn, r)(
            consts, rows, order, take(labels), take(flat_w) / denom,
            blocks, place)
    return loss, (blocks * r).astype(jnp.float32)


mlm_loss.over_rows = _mlm_loss_over_rows


def _blocked_ce(head_fn, r: int):
    """``f(consts, rows, order, labels, weights, blocks, place)``: the sum
    over the first ``blocks`` blocks of ``r`` sorted rows of weight x CE of
    ``head_fn(rows[order[block]], *consts)``, with its own VJP: the backward
    loops over the same blocks, forms each one's logits again and writes the
    block's ``d rows`` into its place among the sorted rows; ``place`` (a
    row's sorted place, past the end for a row of no weight) brings them
    home."""

    def block_sum(consts, picked, labels, weights):
        ce = softmax_cross_entropy(head_fn(picked, *consts), labels)
        return (ce * weights).sum()

    def block_of(i, rows, order, labels, weights):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * r, r)
        picked = jnp.take(rows, cut(order), axis=0, mode="fill",
                          fill_value=0)
        return picked, cut(labels), cut(weights)

    def total(consts, rows, order, labels, weights, blocks, place):
        def body(i, acc):
            return acc + block_sum(
                consts, *block_of(i, rows, order, labels, weights))
        return jax.lax.fori_loop(
            0, blocks, body, vary_like(jnp.zeros((), jnp.float32), rows))

    def fwd(*args):
        return total(*args), args

    def bwd(args, g):
        consts, rows, order, labels, weights, blocks, place = args

        def body(i, carry):
            d_consts, d_sorted = carry
            picked, lab, w = block_of(i, rows, order, labels, weights)
            dc, dp = jax.grad(block_sum, argnums=(0, 1))(
                consts, picked, lab, w * g)
            return (jax.tree_util.tree_map(jnp.add, d_consts, dc),
                    jax.lax.dynamic_update_slice_in_dim(d_sorted, dp,
                                                        i * r, 0))

        zeros = lambda shape, dtype: vary_like(jnp.zeros(shape, dtype), rows)
        d_consts, d_sorted = jax.lax.fori_loop(
            0, blocks, body,
            ([zeros(c.shape, c.dtype) for c in consts],
             zeros((order.size, rows.shape[-1]), rows.dtype)))
        d_rows = jnp.take(d_sorted, place, axis=0, mode="fill", fill_value=0)
        return d_consts, d_rows, None, None, None, None, None

    f = jax.custom_vjp(total)
    f.defvjp(fwd, bwd)
    return f


def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Next-token CE, mean over all positions (Transformer-XL objective)."""
    with device_span("loss"):
        return softmax_cross_entropy(logits, labels).mean()


def _global_lm_loss(logits, labels, axes):
    """Next-token CE averaged over the GLOBAL position count: psum-ed sum /
    psum-ed count, so shards (whose local means would misweight) combine
    exactly to lm_loss on the full batch.  One definition shared by the CP
    train/eval and MoE 'lm' train/eval steps."""
    with device_span("loss"):
        ce = softmax_cross_entropy(logits, labels)
        num = jax.lax.psum(ce.sum(), axes)
        den = jax.lax.psum(jnp.asarray(ce.size, jnp.float32), axes)
        return num / den


def make_txl_train_step(model, optimizer, policy: Policy,
                        ddp: Optional[DDPConfig] = None,
                        axis_name: Optional[str] = None,
                        max_grad_norm: float = 0.25,
                        grad_accum: int = 1):
    """Transformer-XL step: (state, mems, (inp, tgt)) → (state, mems', metrics).

    Mirrors the reference C5 recipe (SURVEY.md §1): FusedLayerNorm inside the
    model, global-norm grad clipping (the multi_tensor_l2norm path) before the
    update, segment recurrence via the mems carry.

    ``grad_accum=K`` splits the batch into K microbatches of independent
    *streams* (recurrence runs along time, not batch, so slicing the batch
    axis — of both the tokens and the (layers, B, mem, d) memory — keeps
    each stream's carry exact).  fp32 grads accumulate across microbatches,
    the clip/allreduce/step run once on the mean — the same convention as
    engine.make_train_step.
    """
    from apex_example_tpu.ops import clip_grad_norm

    opt = _wrap_optimizer(optimizer)
    ddp = ddp or DDPConfig()
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")

    def train_step(state: TrainState, mems, batch):
        inp, tgt = batch

        def grads_for(mems_mb, inp_mb, tgt_mb):
            def scaled_loss_fn(params):
                logits, new_mems = model.apply({"params": params}, inp_mb,
                                               mems=mems_mb)
                loss = lm_loss(logits, tgt_mb)
                return amp_lib.scale_loss(loss, state.scaler), (loss,
                                                                new_mems)
            return jax.grad(scaled_loss_fn, has_aux=True)(state.params)

        if grad_accum == 1:
            grads, (loss, new_mems) = grads_for(mems, inp, tgt)
        else:
            k = grad_accum
            if inp.shape[0] % k:
                raise ValueError(f"batch {inp.shape[0]} not divisible by "
                                 f"grad_accum {k}")
            split = lambda a: a.reshape(k, a.shape[0] // k, *a.shape[1:])
            # mems batch axis is dim 1 of (layers, B, mem, d).
            mems_k = jax.tree_util.tree_map(
                lambda m: jnp.moveaxis(
                    m.reshape(m.shape[0], k, m.shape[1] // k, *m.shape[2:]),
                    1, 0), mems)
            def micro(mems_mb, inp_mb, tgt_mb):
                g, (l, nm) = grads_for(mems_mb, inp_mb, tgt_mb)
                return (jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), g), l, nm)

            def body(carry, mb):
                gsum, lsum = carry
                gf, l, nm = micro(*mb)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, gf)
                return (gsum, lsum + l), nm

            # Microbatch 0 runs outside the scan so the carry's per-leaf
            # shard-variance types match what the body produces (see
            # engine.make_train_step for the full rationale — a zeros init
            # is mesh-invariant and shard_map's vma check rejects it).
            xs = (mems_k, split(inp), split(tgt))
            g0, l0, nm0 = micro(*jax.tree_util.tree_map(
                lambda a: a[0], xs))
            (gsum, lsum), new_mems_rest = jax.lax.scan(
                body, (g0, l0),
                jax.tree_util.tree_map(lambda a: a[1:], xs))
            grads = jax.tree_util.tree_map(
                lambda a, p: (a / k).astype(p.dtype), gsum, state.params)
            loss = lsum / k
            new_mems_k = jax.tree_util.tree_map(
                lambda first, rest: jnp.concatenate([first[None], rest]),
                nm0, new_mems_rest)
            new_mems = jax.tree_util.tree_map(
                lambda m: jnp.moveaxis(m, 0, 1).reshape(
                    m.shape[1], -1, *m.shape[3:]), new_mems_k)
        if axis_name is not None:
            grads = allreduce_grads(grads, ddp, axis_name)
            loss = jax.lax.pmean(loss, axis_name)
        grads, grads_finite = amp_lib.unscale_grads(grads, state.scaler)
        grads, gnorm = clip_grad_norm(grads, max_grad_norm)

        new_params, new_opt_state = opt.apply(grads, state.opt_state,
                                              state.params)
        if policy.uses_dynamic_scaling:
            new_params = amp_lib.select_tree(grads_finite, new_params,
                                            state.params)
            new_opt_state = amp_lib.select_tree(grads_finite, new_opt_state,
                                                state.opt_state)
        scaler = amp_lib.update_scaler(state.scaler, grads_finite)

        metrics = {"loss": loss, "grad_norm": gnorm,
                   "ppl": jnp.exp(loss), "scale": scaler.scale,
                   "grads_finite": grads_finite.astype(jnp.float32)}
        new_state = TrainState(step=state.step + 1, params=new_params,
                               batch_stats=state.batch_stats,
                               opt_state=new_opt_state, scaler=scaler)
        return new_state, new_mems, metrics

    return train_step


def make_bert_eval_step(model):
    """(params, (ids, (labels, weights))) -> {loss, masked_acc}: MLM loss
    and accuracy over masked positions only — the LM counterpart of the
    image harness's eval loop (engine.make_eval_step; SURVEY.md §3.5)."""
    def eval_step(params, batch) -> Dict:
        ids, (labels, weights) = batch
        logits = model.apply({"params": params}, ids, train=False)
        hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        denom = jnp.maximum(weights.sum(), 1.0)
        return {"loss": mlm_loss(logits, (labels, weights)),
                "masked_acc": (hit * weights).sum() / denom * 100.0}
    return eval_step


def make_gpt_eval_step(model):
    """(params, (x, y)) -> {loss}: next-token CE on a held-out batch; the
    harness reports corpus ppl = exp(mean loss) like the TXL eval loop
    (GPT has no recurrence carry, so the signature is BERT-shaped)."""
    def eval_step(params, batch) -> Dict:
        x, y = batch
        logits = model.apply({"params": params}, x, train=False)
        return {"loss": lm_loss(logits, y)}
    return eval_step


def make_txl_eval_step(model):
    """(params, mems, (inp, tgt)) -> (new_mems, {loss}): held-out next-token
    loss, threading the recurrence memory exactly like training (the
    reference evaluates TXL with mems carried).  Perplexity belongs at the
    AGGREGATE level — exp(mean loss), computed by the caller over all eval
    batches; a per-batch exp would make the averaged number Jensen-biased
    toward outlier batches."""
    def eval_step(params, mems, batch):
        inp, tgt = batch
        logits, new_mems = model.apply({"params": params}, inp,
                                       mems=mems, train=False)
        return new_mems, {"loss": lm_loss(logits, tgt)}
    return eval_step


def make_sharded_txl_train_step(mesh: Mesh, model, optimizer, policy: Policy,
                                ddp: Optional[DDPConfig] = None,
                                max_grad_norm: float = 0.25,
                                axis_name: str = DATA_AXIS,
                                donate: bool = True,
                                grad_accum: int = 1):
    """DDP Transformer-XL step.  mems are sharded on their batch axis
    (dim 1 of (layers, B, mem, d)); state is replicated."""
    per_shard = make_txl_train_step(model, optimizer, policy, ddp=ddp,
                                    axis_name=axis_name,
                                    max_grad_norm=max_grad_norm,
                                    grad_accum=grad_accum)
    mem_spec = P(None, axis_name)
    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), mem_spec, (P(axis_name), P(axis_name))),
        out_specs=(P(), mem_spec, P()))
    return jax.jit(sharded,
                   donate_argnums=(0, 1) if donate else ())


def make_bert_cp_train_step(mesh: Mesh, model, optimizer, policy: Policy,
                            donate: bool = True, grad_accum: int = 1,
                            state_shardings=None):
    """Ring context-parallel BERT MLM step over a ('data', 'context') mesh
    (train.py --context-parallel) — the long-context training path.

    The global (B, L) batch shards batch-over-'data' and
    sequence-over-'context'; per-token work (embeddings, LN, FFN, head)
    runs on local shards, attention rides the ppermute KV ring
    (parallel/context_parallel.ring_attention, flash-composed so even
    per-chunk score tiles stay in VMEM).  The MLM loss is the globally
    normalized weighted CE (psum-ed sums over both axes — per-shard
    masked counts differ, so a mean-of-means would misweight shards);
    params are replicated over both axes, so their grads arrive
    implicitly psum-ed (incl. the custom-VJP LayerNorm via
    _vma.align_param_grad) and every replica applies the identical
    update.  No reference analog (SURVEY.md §3.2: CP absent there).
    """
    from apex_example_tpu.engine import TrainState, make_train_step
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS

    def cp_mlm_loss(logits, target):
        labels, weights = target
        axes = (DATA_AXIS, CONTEXT_AXIS)
        with device_span("loss"):
            ce = softmax_cross_entropy(logits, labels)
            num = jax.lax.psum((ce * weights).sum(), axes)
            den = jnp.maximum(jax.lax.psum(weights.sum(), axes), 1.0)
            return num / den

    # grad_accum=K: the engine's microbatch scan splits the LOCAL batch dim;
    # each microbatch's loss is normalized by ITS OWN global (psum-ed)
    # masked count, so K-microbatch CP equals K-microbatch dense exactly
    # (both average per-microbatch globally-normalized losses).
    per_shard = make_train_step(model, optimizer, policy, axis_name=None,
                                loss_fn=cp_mlm_loss, compute_accuracy=False,
                                grad_accum=grad_accum)
    st_spec = _cp_state_spec(optimizer)
    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(st_spec, (P(DATA_AXIS, CONTEXT_AXIS),
                            (P(DATA_AXIS, CONTEXT_AXIS),
                             P(DATA_AXIS, CONTEXT_AXIS)))),
        out_specs=(st_spec, P()), **_cp_axis_names(mesh, model))
    jkw = {}
    if state_shardings is not None:
        # CP×TP: pin the returned state to its model-axis placement
        # (engine.gspmd_state_shardings) — the shard_map's out_specs only
        # govern the MANUAL axes, and with 'model' automatic the compiler
        # would otherwise be free to hand the updated params back
        # replicated, silently losing the TP sharding after one step.
        from jax.sharding import NamedSharding
        jkw["out_shardings"] = (state_shardings, NamedSharding(mesh, P()))
    return jax.jit(sharded, donate_argnums=(0,) if donate else (), **jkw)


def partial_manual_axis_names(mesh: Mesh, model,
                              manual_axes: frozenset) -> dict:
    """shard_map kwargs for a TP-composed step: with a nontrivial 'model'
    axis the map goes manual over ``manual_axes`` ONLY, leaving 'model'
    automatic so the GSPMD TP layers (tensor_parallel=True) run inside
    the manual program — the partially-manual composition shared by the
    CP x TP, MoE x TP and TP x PP paths.  Param model-axis shardings ride
    along from the arrays' placement (engine.gspmd_state_shardings)."""
    from apex_example_tpu.parallel.mesh import require_model_axis_match
    tp = require_model_axis_match(mesh, getattr(model, "tensor_parallel",
                                                False))
    return {"axis_names": set(manual_axes)} if tp > 1 else {}


def _cp_axis_names(mesh: Mesh, model) -> dict:
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
    return partial_manual_axis_names(
        mesh, model, frozenset({DATA_AXIS, CONTEXT_AXIS}))


def _cp_state_spec(optimizer):
    """shard_map TrainState spec for the CP steps: everything replicated
    EXCEPT a ZeRO optimizer's state (ZeRO x CP, round 5) — the flat
    (mu, nu) buffers shard over 'data' while params stay replicated over
    both axes.  The optimizer's reduce/slice/all-gather collectives run
    over 'data' inside the same shard_map; grads arrive implicitly
    psum-ed over BOTH axes (replicated params), so the update is
    context-invariant by construction."""
    from apex_example_tpu.engine import TrainState
    from apex_example_tpu.optim.distributed import DistributedFusedAdam
    if isinstance(optimizer, DistributedFusedAdam):
        return TrainState(step=P(), params=P(), batch_stats=P(),
                          opt_state=optimizer.state_spec(), scaler=P())
    return P()


def make_bert_cp_eval_step(mesh: Mesh, model):
    """Sequence-sharded held-out eval under the same KV ring as CP training
    (train.py --context-parallel --eval).

    Without this, the CP path could train at a context length the dense
    eval forward cannot touch: a single-device eval materializes the
    (L, L) score tensor CP exists to shard.  Shapes, collectives and the
    globally psum-normalized loss/masked-acc mirror
    :func:`make_bert_cp_train_step`'s forward exactly; the metrics are
    bit-comparable to the dense eval on the same params (tested).
    """
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS

    def per_shard(params, batch):
        ids, (labels, weights) = batch
        logits = model.apply({"params": params}, ids, train=False)
        axes = (DATA_AXIS, CONTEXT_AXIS)
        ce = softmax_cross_entropy(logits, labels)
        den = jnp.maximum(jax.lax.psum(weights.sum(), axes), 1.0)
        hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        return {"loss": jax.lax.psum((ce * weights).sum(), axes) / den,
                "masked_acc": jax.lax.psum((hit * weights).sum(), axes)
                / den * 100.0}

    spec = P(DATA_AXIS, CONTEXT_AXIS)
    sharded = jax.shard_map(per_shard, mesh=mesh,
                             in_specs=(P(), (spec, (spec, spec))),
                             out_specs=P(), **_cp_axis_names(mesh, model))
    return jax.jit(sharded)


def _cp_layout_wrap(fn, mesh, model, mode: str):
    """Shared CP-layout plumbing for the GPT CP train/eval factories:
    enforce that the factory's mode and the model's cp_mode agree (a
    mismatch trains/evals on inconsistently ordered data or the wrong
    attention program with no error), and wrap ``fn`` with the
    zigzag_shard pre-pass when the layout calls for it (ring and ulysses
    both use contiguous chunks — no reorder)."""
    model_mode = getattr(model, "cp_mode", "ring")
    if mode != model_mode:
        raise ValueError(
            f"mode={mode!r} but model.cp_mode={model_mode!r} — the batch "
            "layout and the model's position ids/attention program must "
            "agree or the computation is silently wrong")
    if mode != "zigzag":
        return fn
    from apex_example_tpu.parallel.context_parallel import zigzag_shard
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
    n = mesh.shape[CONTEXT_AXIS]

    def wrapped(carry, batch):
        x, y = batch
        return fn(carry, (zigzag_shard(x, n), zigzag_shard(y, n)))
    return wrapped


def make_gpt_cp_train_step(mesh: Mesh, model, optimizer, policy: Policy,
                           donate: bool = True, grad_accum: int = 1,
                           state_shardings=None, mode: str = "ring"):
    """Ring context-parallel GPT step over a ('data', 'context') mesh
    (train.py --context-parallel with a gpt arch).

    Same shape as :func:`make_bert_cp_train_step` with two causal
    specifics: attention runs the CAUSAL KV ring (future chunks skipped,
    diagonal chunk masked blockwise — models/bert.BertSelfAttention
    causal=True under context_parallel), and the objective is next-token
    CE averaged over the GLOBAL position count (a psum-ed sum / psum-ed
    count, so shard means never misweight).  The (x, y) pair arrives
    pre-shifted from the harness; both shard batch-over-'data' and
    sequence-over-'context' in the same contiguous chunk order the ring
    and the position offsets key on.

    ``mode`` selects the CP attention program and must match the model's
    ``cp_mode``: "ring" (contiguous causal KV ring), "zigzag" (the
    load-BALANCED causal ring — the factory reorders both sequences with
    ``zigzag_shard`` before the shard_map, so P('context') hands device i
    its (i, 2n-1-i) chunk pair and every ring step does identical live
    work), or "ulysses" (all-to-all head sharding: full sequence per
    device, H/N heads per device, exact attention).  Losses/grads are
    order-invariant sums, so every mode's trajectory equals the dense
    model exactly.
    """
    from apex_example_tpu.engine import make_train_step
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS

    def cp_lm_loss(logits, y):
        return _global_lm_loss(logits, y, (DATA_AXIS, CONTEXT_AXIS))

    per_shard = make_train_step(model, optimizer, policy, axis_name=None,
                                loss_fn=cp_lm_loss, compute_accuracy=False,
                                grad_accum=grad_accum)
    spec = P(DATA_AXIS, CONTEXT_AXIS)
    st_spec = _cp_state_spec(optimizer)
    sharded = jax.shard_map(per_shard, mesh=mesh,
                             in_specs=(st_spec, (spec, spec)),
                             out_specs=(st_spec, P()),
                             **_cp_axis_names(mesh, model))
    sharded = _cp_layout_wrap(sharded, mesh, model, mode)
    jkw = {}
    if state_shardings is not None:
        from jax.sharding import NamedSharding
        jkw["out_shardings"] = (state_shardings, NamedSharding(mesh, P()))
    return jax.jit(sharded, donate_argnums=(0,) if donate else (), **jkw)


def make_gpt_cp_eval_step(mesh: Mesh, model, mode: str = "ring"):
    """Sequence-sharded held-out eval under the same causal KV ring
    (train.py --context-parallel --eval, gpt archs): loss at the training
    context length, psum-normalized globally."""
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS

    def per_shard(params, batch):
        x, y = batch
        logits = model.apply({"params": params}, x, train=False)
        return {"loss": _global_lm_loss(logits, y,
                                        (DATA_AXIS, CONTEXT_AXIS))}

    spec = P(DATA_AXIS, CONTEXT_AXIS)
    sharded = jax.shard_map(per_shard, mesh=mesh,
                             in_specs=(P(), (spec, spec)), out_specs=P(),
                             **_cp_axis_names(mesh, model))
    return jax.jit(_cp_layout_wrap(sharded, mesh, model, mode))


def make_gspmd_txl_train_step(mesh: Mesh, model, optimizer, policy: Policy,
                              state_shardings,
                              max_grad_norm: float = 0.25,
                              donate: bool = True,
                              grad_accum: int = 1):
    """Tensor-parallel Transformer-XL step (the train.py --tensor-parallel
    path): same *annotate, don't orchestrate* contract as
    ``engine.make_gspmd_train_step`` — the plain single-device TXL step
    jitted with the TP layers' param shardings, batch AND the (layers, B,
    mem, d) memory carry sharded on 'data', Megatron collectives inserted
    by GSPMD at the layers' constraint points."""
    from jax.sharding import NamedSharding

    step = make_txl_train_step(model, optimizer, policy, axis_name=None,
                               max_grad_norm=max_grad_norm,
                               grad_accum=grad_accum)
    mems_sh = NamedSharding(mesh, P(None, DATA_AXIS))
    batch_sh = NamedSharding(mesh, P(DATA_AXIS))
    metrics_sh = NamedSharding(mesh, P())
    return jax.jit(step,
                   in_shardings=(state_shardings, mems_sh, batch_sh),
                   out_shardings=(state_shardings, mems_sh, metrics_sh),
                   donate_argnums=(0, 1) if donate else ())


# ------------------- Expert-parallel (MoE) BERT --------------------------
#
# The harness face of transformer/expert_parallel.py (train.py
# --moe-experts): switch-MoE encoder FFNs with E/n experts per device over
# the 'data' axis — EP rides the DP devices the way DeepSpeed-MoE does, so
# no new mesh axis is needed and every token still trains on its home
# shard.  No reference analog (SURVEY.md §3.2: EP documented as absent
# there); this is the same "library feature -> harness-reachable" move the
# CP path made in round 3.

def _is_expert_leaf(path) -> bool:
    """The ONE definition of which param leaves are EP-sharded expert
    stacks (under a 'moe' module, named w_in/w_out): used by both the
    shard_map spec tree and the device-placement overlay — they must
    never disagree or placement and specs silently diverge."""
    keys = {getattr(p, "key", None) for p in path}
    return "moe" in keys and ("w_in" in keys or "w_out" in keys)


def _moe_param_spec_tree(params):
    """P(DATA_AXIS) for the stacked [E, ...] expert weights (one expert
    per data-axis device), P() for everything else (router, attention,
    embeddings, head: replicated, their grads arrive implicitly
    psum-ed)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _leaf: P(DATA_AXIS) if _is_expert_leaf(path) else P(),
        params)


def bert_moe_state_specs(state: TrainState, optimizer) -> TrainState:
    """PartitionSpec TrainState for the EP step: expert stacks shard over
    'data', optimizer state mirrors its params-shaped fields
    (engine._opt_state_specs), all else replicates."""
    from apex_example_tpu.engine import _opt_state_specs
    tmap = jax.tree_util.tree_map
    pspecs = _moe_param_spec_tree(state.params)
    abs_params = tmap(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                      state.params)
    return TrainState(
        step=P(), params=pspecs,
        batch_stats=tmap(lambda _: P(), state.batch_stats),
        opt_state=_opt_state_specs(optimizer, abs_params, pspecs),
        scaler=tmap(lambda _: P(), state.scaler))


def bert_moe_state_shardings(mesh: Mesh, state: TrainState, optimizer,
                             base_shardings=None) -> TrainState:
    """NamedSharding tree for device_put / the orbax restore template.

    ``base_shardings`` (MoE x TP): the GSPMD NamedSharding tree from
    create_gspmd_train_state — non-expert leaves keep their model-axis
    placement, the expert stacks are overridden to P('data') (they are
    model-replicated; each data-axis device owns E/n experts)."""
    from jax.sharding import NamedSharding
    if base_shardings is None:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            bert_moe_state_specs(state, optimizer),
            is_leaf=lambda v: isinstance(v, P))

    # Overlay on the BASE tree by path (its structure may collapse
    # sharding-uniform subtrees like the scaler into one leaf): exactly
    # the expert-stack leaves (_is_expert_leaf, the same predicate the
    # spec tree uses) switch to P('data').
    return jax.tree_util.tree_map_with_path(
        lambda path, base_leaf: NamedSharding(mesh, P(DATA_AXIS))
        if _is_expert_leaf(path) else base_leaf, base_shardings)


def _moe_axis_names(mesh: Mesh, model) -> dict:
    return partial_manual_axis_names(mesh, model, frozenset({DATA_AXIS}))


def _moe_cp_axis_names(mesh: Mesh, model) -> dict:
    """EP x CP: manual over 'data' (expert all_to_all) AND 'context' (KV
    ring) jointly; 'model' would stay automatic but the TP triple
    composition is not wired (train.py rejects it)."""
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
    return partial_manual_axis_names(
        mesh, model, frozenset({DATA_AXIS, CONTEXT_AXIS}))


def _moe_batch_plumbing(mesh: Mesh, model, objective: str,
                        context_parallel: bool, mode: str):
    """The EP / EP x CP spec-and-layout epilogue the MoE train AND eval
    factories share: (per-item batch spec, shard_map manual-axes kwargs,
    layout wrapper).  One home so the mode validation and the zigzag
    pre-pass can never drift between the two paths."""
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
    if not context_parallel:
        return P(DATA_AXIS), _moe_axis_names(mesh, model), lambda fn: fn
    if objective == "mlm" and mode == "zigzag":
        # (the model layer rejects zigzag for non-causal attention anyway;
        # this keeps the error at the factory boundary)
        raise ValueError("zigzag is the load-balanced CAUSAL layout; "
                         "MLM BERT uses ring or ulysses")
    # ring/ulysses need no reorder, so for MLM the wrap is just the
    # mode<->model.cp_mode agreement check; the zigzag pre-pass only
    # ever fires on the (x, y) LM pair shape.
    return (P(DATA_AXIS, CONTEXT_AXIS), _moe_cp_axis_names(mesh, model),
            lambda fn: _cp_layout_wrap(fn, mesh, model, mode))


def _check_moe_model(mesh: Mesh, model, optimizer=None):
    E = mesh.shape[DATA_AXIS]
    if not model.moe_experts:
        raise ValueError("model has moe_experts=0; build it with "
                         "moe_experts=<data-axis size>")
    if model.moe_experts % E:
        raise ValueError(
            f"moe_experts={model.moe_experts} must be a multiple of the "
            f"data-axis size {E} (the all_to_all splits the [E, C, d] "
            f"dispatch buffer {E}-ways; each device owns "
            f"moe_experts/{E} experts)")
    if model.moe_axis_name != DATA_AXIS:
        raise ValueError(
            f"model.moe_axis_name={model.moe_axis_name!r} but the EP step "
            f"maps over {DATA_AXIS!r}; build the model with "
            f"moe_axis_name=DATA_AXIS or MoEMLP silently falls back to "
            f"its dense reference path")
    if optimizer is not None:
        from apex_example_tpu.optim.fused import FusedLAMB, FusedNovoGrad
        if isinstance(optimizer, (FusedLAMB, FusedNovoGrad)):
            raise ValueError(
                f"{type(optimizer).__name__} computes per-TENSOR statistics "
                "(trust ratio / ||g||^2 EMA); on the EP-sharded [E, ...] "
                "expert stacks each shard would see only its slice, "
                "silently diverging from the dense-model semantics — use "
                "adam/sgd/adagrad under --moe-experts")


def make_bert_moe_train_step(mesh: Mesh, model, optimizer, policy: Policy,
                             state_template: TrainState,
                             aux_weight: float = 1e-2,
                             donate: bool = True, grad_accum: int = 1,
                             objective: str = "mlm",
                             state_shardings=None,
                             context_parallel: bool = False,
                             mode: str = "ring"):
    """Expert-parallel BERT MLM step over the 'data' axis (train.py
    --moe-experts).

    The model returns (logits, aux); the objective is the globally
    psum-normalized masked CE plus ``aux_weight`` x the Switch
    load-balancing loss (already pmean-ed over the axis inside
    moe_forward).  Replicated-param grads arrive implicitly psum-ed
    through the psum-ed loss (the CP-step mechanism); the expert stacks'
    grads stay shard-local — each device owns its experts.  The dynamic-
    scaling finite flag is pmean-ed over 'data'
    (engine.make_train_step(finite_reduce_axes=...)): a local overflow in
    one expert's grads must skip the step and halve the scale on EVERY
    shard or the replicated scaler state diverges.

    ``context_parallel``: the EP x CP composition (train.py --moe-experts
    --context-parallel, the modern long-context-MoE stack): the batch
    additionally shards sequence-over-'context', attention rides the
    causal/ring KV programs on that axis, and the MoE all_to_all over
    'data' runs independently per context column — two manual axes, two
    independent collectives in one body.  Routing/capacity stay
    per-(data, context)-shard (the same per-device contract the pure EP
    path pins); the aux loss is additionally pmean-ed over 'context' so
    the objective (and the metrics' mesh-invariance) see the mean expert
    balance across sequence shards.  ``mode`` selects the CP attention
    program (ring/zigzag/ulysses; must match the model's cp_mode).
    """
    from apex_example_tpu.engine import make_train_step
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
    _check_moe_model(mesh, model, optimizer)
    if objective not in ("mlm", "lm"):
        raise ValueError(f"objective must be 'mlm' or 'lm', "
                         f"got {objective!r}")
    loss_axes = (DATA_AXIS, CONTEXT_AXIS) if context_parallel else DATA_AXIS

    def moe_loss(out, target):
        logits, aux = out
        if context_parallel:
            # per-context-column aux (moe_forward pmean-ed 'data' only)
            aux = jax.lax.pmean(aux, CONTEXT_AXIS)
        if objective == "mlm":
            labels, weights = target
            with device_span("loss"):
                ce = softmax_cross_entropy(logits, labels)
                num = jax.lax.psum((ce * weights).sum(), loss_axes)
                den = jnp.maximum(jax.lax.psum(weights.sum(), loss_axes),
                                  1.0)
            return (num / den
                    + jnp.asarray(aux_weight, jnp.float32) * aux)
        # next-token CE (MoE GPT)
        return (_global_lm_loss(logits, target, loss_axes)
                + jnp.asarray(aux_weight, jnp.float32) * aux)

    per_shard = make_train_step(model, optimizer, policy, axis_name=None,
                                loss_fn=moe_loss,
                                compute_accuracy=False,
                                grad_accum=grad_accum,
                                finite_reduce_axes=DATA_AXIS)
    # state_template fixes the spec TREE only (the per-leaf expert-vs-
    # replicated split); shapes/values are irrelevant, so the pre-
    # device_put host state works fine.
    spec_state = bert_moe_state_specs(state_template, optimizer)
    b, manual, wrap = _moe_batch_plumbing(mesh, model, objective,
                                          context_parallel, mode)
    batch_spec = (b, (b, b)) if objective == "mlm" else (b, b)
    sharded = wrap(jax.shard_map(per_shard, mesh=mesh,
                                  in_specs=(spec_state, batch_spec),
                                  out_specs=(spec_state, P()), **manual))
    jkw = {}
    if state_shardings is not None:
        # MoE x TP: pin the returned state to its combined placement
        # (expert stacks over 'data', TP leaves over 'model') — with
        # 'model' automatic the compiler would otherwise be free to hand
        # the updated params back replicated on that axis.
        from jax.sharding import NamedSharding
        jkw["out_shardings"] = (state_shardings, NamedSharding(mesh, P()))
    return jax.jit(sharded, donate_argnums=(0,) if donate else (), **jkw)


def make_bert_moe_eval_step(mesh: Mesh, model, params_template,
                            objective: str = "mlm",
                            context_parallel: bool = False,
                            mode: str = "ring"):
    """Expert-parallel held-out eval: same mesh, same all_to_all dispatch,
    metrics psum-normalized globally (mirrors make_bert_cp_eval_step's
    contract; --moe-experts --eval).  objective='lm' evaluates next-token
    CE for MoE GPT ({loss} only — the harness reports ppl).
    ``context_parallel``: sequence-sharded EP x CP eval under the same KV
    ring + per-column expert dispatch as training."""
    from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
    _check_moe_model(mesh, model)
    if objective not in ("mlm", "lm"):
        raise ValueError(f"objective must be 'mlm' or 'lm', "
                         f"got {objective!r}")
    axes = (DATA_AXIS, CONTEXT_AXIS) if context_parallel else DATA_AXIS

    def per_shard(params, batch):
        if objective == "mlm":
            ids, (labels, weights) = batch
            logits, _aux = model.apply({"params": params}, ids, train=False)
            ce = softmax_cross_entropy(logits, labels)
            den = jnp.maximum(jax.lax.psum(weights.sum(), axes), 1.0)
            hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
            return {"loss":
                    jax.lax.psum((ce * weights).sum(), axes) / den,
                    "masked_acc":
                    jax.lax.psum((hit * weights).sum(), axes)
                    / den * 100.0}
        x, y = batch
        logits, _aux = model.apply({"params": params}, x, train=False)
        return {"loss": _global_lm_loss(logits, y, axes)}

    b, manual, wrap = _moe_batch_plumbing(mesh, model, objective,
                                          context_parallel, mode)
    batch_spec = (b, (b, b)) if objective == "mlm" else (b, b)
    sharded = wrap(jax.shard_map(per_shard, mesh=mesh,
                                  in_specs=(_moe_param_spec_tree(
                                      params_template), batch_spec),
                                  out_specs=P(), **manual))
    return jax.jit(sharded)
