"""Expert parallelism (MoE): top-1 (Switch) / top-2 (GShard) routing over an
expert axis.

Reference status: EP is ABSENT from the reference family (SURVEY.md §3.2
marks it "documented as absent"); like context parallelism
(parallel/context_parallel.py) this is a TPU-first extension beyond the
reference, built because the mesh/collective machinery makes it natural and
a "complete" modern parallelism surface includes it.

TPU-native design (the Switch-Transformer dispatch, expressed as static-shape
XLA collectives — no dynamic shapes, jit-stable):

  1. router: logits = x @ w_r → top-1 expert per token, softmax gate
     (top_k=2: GShard-style second choice with renormalized gates).
  2. capacity: each expert accepts at most C tokens per device
     (C = ceil(tokens/E · capacity_factor)); overflow tokens are dropped
     (their combine weight is 0 — the standard switch trade that keeps every
     shape static).
  3. dispatch: one-hot position-in-expert (cumsum over the token dim) builds
     a [E, C, d] buffer per device; ``lax.all_to_all`` over the expert axis
     turns it into this device's experts' per-sender token blocks.
  4. expert FFN (dense→act→dense; k = E/n experts per device shard,
     batched over the local expert dim).
  5. inverse all_to_all + gate-weighted combine back to [tokens, d].

Gradients flow through dispatch/combine as through any other collectives
(all_to_all transposes to the inverse all_to_all).  A load-balancing aux
loss (mean fraction·prob product, Switch eq. 4) is returned for the trainer
to weight.

``EXPERT_AXIS = "expert"``; run inside shard_map with tokens sharded over
the axis (typically the same devices as data parallelism — EP reuses the DP
axis the way DeepSpeed-MoE does).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import flax.linen as nn

import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.ops.grouped_matmul import (grouped_matmul,
                                                 grouped_swiglu)

EXPERT_AXIS = "expert"


class MoEParams(NamedTuple):
    w_router: jnp.ndarray   # [d, E] (replicated)
    w_in: jnp.ndarray       # stacked [E, d, h]; [k, d, h] local shard
    w_out: jnp.ndarray      # stacked [E, h, d]; [k, h, d] local shard


def init_moe_params(rng, d: int, hidden: int, n_experts: int,
                    dtype=jnp.float32) -> MoEParams:
    """Logical params: router replicated, expert weights stacked [E, ...]
    and sharded over the expert axis (P(expert) on dim 0 → E/n experts
    per device at the shard_map boundary)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale = 1.0 / jnp.sqrt(d)
    return MoEParams(
        w_router=(jax.random.normal(k1, (d, n_experts)) * scale
                  ).astype(dtype),
        w_in=(jax.random.normal(k2, (n_experts, d, hidden)) * scale
              ).astype(dtype),
        w_out=(jax.random.normal(k3, (n_experts, hidden, d)) * scale
               ).astype(dtype))


def _dispatch_masks(logits: jnp.ndarray, capacity: int, top_k: int = 1
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-1 (Switch) or top-2 (GShard-style) dispatch for [T, E] router
    logits.

    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] gate-weighted,
    aux_loss scalar).  All shapes static; overflow tokens get all-zero
    rows.  Top-2 follows the GShard conventions: the two gates are
    renormalized to sum to 1, second choices queue BEHIND every kept
    first choice in each expert's capacity buffer (so under pressure the
    second opinions are the ones dropped), and the load-balancing loss
    keys on the FIRST-choice assignment fractions.
    """
    T, E = logits.shape
    if top_k not in (1, 2):
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    e1 = jnp.argmax(probs, axis=-1)                      # [T]
    g1 = jnp.take_along_axis(probs, e1[:, None], axis=-1)[:, 0]
    oh1 = jax.nn.one_hot(e1, E, dtype=jnp.float32)       # [T, E]

    # position of each first-choice token within its expert's queue
    pos1 = jnp.cumsum(oh1, axis=0) * oh1 - 1.0           # [T, E]
    keep1 = (pos1 < capacity) & (oh1 > 0)
    pc1 = jax.nn.one_hot(pos1.astype(jnp.int32), capacity,
                         dtype=jnp.float32)              # [T, E, C]
    d1 = pc1 * keep1[..., None]

    # Switch load-balancing loss: E · Σ_e fraction_e · mean-prob_e
    # (first-choice fractions in both modes).
    aux = E * jnp.sum(oh1.mean(axis=0) * probs.mean(axis=0))

    if top_k == 1:
        return d1, d1 * g1[:, None, None], aux

    e2 = jnp.argmax(probs - oh1 * 2.0, axis=-1)          # runner-up
    g2 = jnp.take_along_axis(probs, e2[:, None], axis=-1)[:, 0]
    oh2 = jax.nn.one_hot(e2, E, dtype=jnp.float32)
    # second choices start after each expert's KEPT first-choice count
    used1 = jnp.minimum(oh1.sum(axis=0), float(capacity))    # [E]
    pos2 = jnp.cumsum(oh2, axis=0) * oh2 - 1.0 + used1[None] * oh2
    keep2 = (pos2 < capacity) & (oh2 > 0)
    pc2 = jax.nn.one_hot(pos2.astype(jnp.int32), capacity,
                         dtype=jnp.float32)
    d2 = pc2 * keep2[..., None]

    denom = jnp.maximum(g1 + g2, 1e-9)
    combine = (d1 * (g1 / denom)[:, None, None]
               + d2 * (g2 / denom)[:, None, None])
    return d1 + d2, combine, aux


def moe_forward(params: MoEParams, x: jnp.ndarray,
                capacity_factor: float = 1.25,
                axis_name: str = EXPERT_AXIS,
                activation=jax.nn.relu,
                top_k: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Switch-MoE block over the expert axis.  Inside shard_map:

    x: [T, d] this device's tokens; params.w_in/w_out:
    [k, d, h]/[k, h, d] — this device's k = E/n experts of the stacked
    [E, ...] arrays (P(axis) on dim 0).

    Returns (y [T, d], aux_loss).
    """
    T, d = x.shape
    n = lax.axis_size(axis_name)
    k = params.w_in.shape[0]            # experts on THIS device
    E = params.w_router.shape[1]        # total experts
    # k experts per expert-axis device (E = k·n): the [E, C, d] send
    # buffer is split n-ways by the tiled all_to_all, so router width,
    # axis size, and the local weight shard must agree or every device
    # silently applies the wrong experts to other experts' tokens.
    if E != k * n:
        raise ValueError(
            f"moe_forward needs n_experts == local shard x axis size; got "
            f"router width {E}, axis '{axis_name}' size {n}, local shard "
            f"{k} (shard stacked [E, ...] weights with P('{axis_name}'))")
    # GShard capacity sizing: the dispatch demand is top_k slots per
    # token, so C scales with top_k or most second choices would be
    # silently dropped at the default factor.
    capacity = int(-(-T * top_k * capacity_factor // E))
    # lane-friendly capacity (C is a matmul/all_to_all dim)
    capacity = capacity + (-capacity) % 8
    C = capacity

    logits = x @ params.w_router.astype(x.dtype)         # [T, E]
    dispatch, combine, aux = _dispatch_masks(logits, capacity, top_k)

    # [E, C, d] expert-major send buffer; the tiled all_to_all splits it
    # into n k-expert blocks and swaps "which expert block" for "which
    # sender": recv row j·k+e = device j's tokens for THIS device's local
    # expert e.
    send = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                      dispatch).astype(x.dtype)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                    # [n·k, C, d]
    # group by local expert: [n, k, C, d] -> [k, n·C, d]
    recv = recv.reshape(n, k, C, d).transpose(1, 0, 2, 3) \
               .reshape(k, n * C, d)
    w_in = params.w_in.astype(x.dtype)                   # [k, d, h]
    w_out = params.w_out.astype(x.dtype)                 # [k, h, d]
    h = activation(jnp.einsum("kcd,kdh->kch", recv, w_in))
    out = jnp.einsum("kch,khd->kcd", h, w_out)           # [k, n·C, d]
    # back to sender-major [n·k, C, d] for the inverse all_to_all
    out = out.reshape(k, n, C, d).transpose(1, 0, 2, 3) \
             .reshape(n * k, C, d)
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                    # [E, C, d]
    y = jnp.einsum("ecd,tec->td", back.astype(jnp.float32),
                   combine).astype(x.dtype)
    return y, lax.pmean(aux, axis_name)


def moe_forward_dense_reference(params: MoEParams, x: jnp.ndarray,
                                capacity_factor: float = 1.25,
                                activation=jax.nn.relu,
                                top_k: int = 1
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """No-mesh golden: every expert computed densely on every token, the
    same dispatch/combine masks select the result.  Matches moe_forward
    exactly on a single shard (tests) and defines the semantics."""
    T, d = x.shape
    E = params.w_in.shape[0]
    capacity = int(-(-T * top_k * capacity_factor // E))
    capacity = capacity + (-capacity) % 8

    logits = x @ params.w_router.astype(x.dtype)
    dispatch, combine, aux = _dispatch_masks(logits, capacity, top_k)

    send = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                      dispatch).astype(x.dtype)           # [E, C, d]
    h = activation(jnp.einsum("ecd,edh->ech", send,
                              params.w_in.astype(x.dtype)))
    out = jnp.einsum("ech,ehd->ecd", h, params.w_out.astype(x.dtype))
    y = jnp.einsum("ecd,tec->td", out.astype(jnp.float32),
                   combine).astype(x.dtype)
    return y, aux


def _axis_is_bound(axis_name: str) -> bool:
    """Trace-time: is ``axis_name`` a live manual mesh axis here?

    Lets one module body serve both worlds: under the EP shard_map the
    collectives run; in eager/plain-jit contexts (init, dense eval, the
    golden tests) the dense reference runs.  Resolution happens at trace
    time, so jit sees a single static branch.
    """
    try:
        lax.axis_size(axis_name)
        return True
    except NameError:
        return False


class MoEMLP(nn.Module):
    """Switch-MoE replacement for a transformer FFN block (flax).

    Logical params: router [d, E], stacked expert weights w_in [E, d, h] /
    w_out [E, h, d].  Outside any mesh the dense reference runs on the full
    stack (init, golden tests, single-device eval).  Inside a shard_map
    with ``axis_name`` bound, the caller shards the stacked weights over
    that axis (P(axis) on dim 0 — E/n experts per device; see
    ``workloads.bert_moe_state_specs``) and the all_to_all dispatch runs.

    Returns ``(y, aux)`` — the load-balancing aux loss is part of the
    training objective (Switch eq. 4), so it is returned rather than sown:
    the model's output contract carries it to the loss function explicitly.
    """

    hidden_size: int
    intermediate_size: int
    n_experts: int
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    axis_name: str = EXPERT_AXIS
    top_k: int = 1

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        d, h, E = self.hidden_size, self.intermediate_size, self.n_experts
        init = nn.initializers.normal(1.0 / float(d) ** 0.5)
        dist = _axis_is_bound(self.axis_name)
        # flax verifies declared param shapes against the provided values
        # at apply time; inside the EP shard_map the stacked [E, ...]
        # arrays arrive SLICED to this device's experts (E/n of them), so
        # the declared leading dim is the local one.  Init always runs
        # outside the mesh (dist=False) and stores the full stack.
        e_local = E // lax.axis_size(self.axis_name) if dist else E
        params = MoEParams(
            w_router=self.param("router", init, (d, E), self.param_dtype),
            w_in=self.param("w_in", init, (e_local, d, h),
                            self.param_dtype),
            w_out=self.param("w_out", init, (e_local, h, d),
                             self.param_dtype))
        flat = x.reshape(-1, d).astype(self.dtype)
        if dist:
            y, aux = moe_forward(params, flat, self.capacity_factor,
                                 self.axis_name, activation=nn.gelu,
                                 top_k=self.top_k)
        else:
            y, aux = moe_forward_dense_reference(
                params, flat, self.capacity_factor, activation=nn.gelu,
                top_k=self.top_k)
        return y.reshape(x.shape).astype(self.dtype), aux


# ---------------------------------------------------------------------------
# Dropless routing for serving (sigmoid scores, selection-only bias, top-k,
# grouped product over the experts held).  A server may not drop a token an
# expert is full for, so nothing here has a capacity: every chosen
# (token, expert) pair is computed.  The capacity path above is the
# trainers' and is untouched.

def dropless_route(x: jnp.ndarray, w_router: jnp.ndarray,
                   bias: jnp.ndarray, top_k: int,
                   scale: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chosen experts ``[T, k]`` (int32) and gates ``[T, k]`` (float32) of
    tokens ``x [T, d]``.  Scores are ``sigmoid(x @ w_router)`` in float32;
    chosen are the ``top_k`` largest of ``score + bias`` (the bias steers
    selection only, ``noaux_tc``); a gate is the chosen expert's own score,
    normalized over the chosen and multiplied by ``scale``."""
    with jax.named_scope("moe_route"):
        s = jax.nn.sigmoid(jnp.matmul(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
        g = jnp.take_along_axis(s, idx, axis=-1)
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), g


def expert_load(idx: jnp.ndarray, n_experts: int,
                live: jnp.ndarray = None) -> jnp.ndarray:
    """Tokens routed to each expert ``[E]`` (int32) over the lanes that
    ``live [T]`` marks (all of them by default)."""
    w = jnp.ones(idx.shape[:1], jnp.int32) if live is None \
        else live.astype(jnp.int32)
    return jnp.zeros((n_experts,), jnp.int32).at[idx].add(w[:, None])


def dropless_experts(x: jnp.ndarray, idx: jnp.ndarray, gates: jnp.ndarray,
                     w_gate: jnp.ndarray, w_up: jnp.ndarray,
                     w_down: jnp.ndarray,
                     experts_held: Tuple[int, int],
                     live: jnp.ndarray = None
                     ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """What the experts held here add to tokens ``x [T, d]``:
    ``sum_i gates[t, i] * E_idx[t, i](x[t])`` over the chosen experts in
    ``experts_held = (first, count)``, each ``E(x) = w_down(silu(w_gate x)
    * w_up x)``.  The stacked weights hold the ``count`` experts from
    ``first`` on; pairs routed elsewhere add nothing (another chip's
    share).  The ``T * k`` pairs are sorted by expert, multiplied group by
    group (``ops.grouped_matmul``: each expert's weights are read once, no
    padding to a capacity; lanes that ``live [T]`` marks dead — the padding
    of a static serving batch — belong to no group, so the products' work
    follows the live tokens and not what the padding happens to hold) and
    summed back per token.  On the TPU the products are two Pallas calls
    under the ``moe_experts`` scope, ``grouped_swiglu`` (gate, up and the
    activation) and ``grouped_matmul`` (down), which never compute the
    rows past the last live pair: those hold anything until the combine
    selects them away.  Elsewhere, and under ``FORCE_XLA``, they are
    ``lax.ragged_dot``.  Returns ``(y, visits)``: ``visits [count]`` the
    row tiles the kernel visited for each expert held (one fetch of its
    weights a product), None from the XLA form."""
    T, k = idx.shape
    first, count = experts_held
    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < count)
        if live is not None:
            held = held & jnp.repeat(live, k)
        group = jnp.where(held, local, count)       # strangers sort last
        order = jnp.argsort(group, stable=True)
        xs = x[order // k]                           # [T*k, d]
        sizes = jnp.zeros((count,), jnp.int32).at[group].add(
            1, mode="drop")
    with jax.named_scope("moe_experts"):
        h, _ = grouped_swiglu(xs, w_gate, w_up, sizes)
        ys, visits = grouped_matmul(h, w_down, sizes)  # [T*k, d] float32
    with jax.named_scope("moe_combine"):
        # back to token order by a gather through the inverse permutation
        # (a row scatter runs its rows one after another on the TPU)
        inverse = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        g = jnp.where(held, gates.reshape(-1), 0.0)
        y = jnp.where(g[:, None] != 0, ys[inverse] * g[:, None], 0.0)
        return y.reshape(T, k, -1).sum(axis=1).astype(x.dtype), visits
