"""openPangu-Ultra-MoE-style decoder (``model_type: pangu_ultra_moe``): a
sandwich-normed block of latent attention (MLA) and a sigmoid-routed
dropless expert layer beside one shared expert, and ONE multi-token-
prediction module that drafts for the serve engine.

Per layer ``u = x + N2(Attn(N1(x)))``, ``x' = u + N4(FFN(N3(u)))``: each
sublayer's output is normed before it is added (``sandwich_norm``).  The
attention, the routed experts, the SwiGLU and the RMSNorm are
``models/layers.py``'s, as ``models/xing4.py``'s are (plain rotary is
``LatentAttention``'s YaRN at factor 1);
``experts_held = (first, count)`` gives the chip's share of the routed
experts and ``vocab_size`` its slice of the vocabulary (the router keeps
its published width; what the absent experts would add is left out).
``benchmarks/reference/pangu_moe.py`` holds the same equations in plain
float32; the configuration file lists what the published config leaves
open (``assumed``).

**The module** (DeepSeek-V3's form, which ``num_nextn_predict_layers``
names).  For position ``i``, from the model's output ``h_i`` (after the
final norm) and the NEXT token ``t_{i+1}``: ``m_i = W_eh [N_e(E[t_{i+1}]) ;
N_h(h_i)]``, ``z_i = Block(m)_i`` (one expert layer as above, its own
weights, its own latent cache leaf, rotary position ``i``), and
``N_m(z_i) W_head^T`` are the logits of ``t_{i+2}``.  Embedding and head are
the model's own.

**Two calls a serve tick** (``serve/engine.draft_tick``, one compiled
program; the serving contract is otherwise ``models/xing4.py``'s).  With
``paged["n_draft"]`` the paged forward returns ``(logits, hidden)``: the
head on each slot's ``K + 1`` *verify lanes* (``[SLOTS, K + 1, V]``: the
lane before the slot's draft lanes and those, or its sampled lane twice
where it fed no draft) and the normed hidden state of every lane, as the
tick's packed rows ``[R, d]`` (below).  The engine samples and verifies, and
calls again with ``draft_from = (hidden, next_ids, lane)``: the module
alone over every lane (``next_ids [SLOTS, C]`` packed onto the same rows),
writing its leaf at the same positions through the same block table, and
its logits at ``lane`` ``[SLOTS, V]``: the next draft.  Without ``n_draft``
(an engine built with ``speculate=0``) the head runs on the sampled lane
and the module does not run.

**Packed lanes** (``packed_lanes = True``, ``lane_head = 1 +
num_nextn_predict_layers``; ``ops/lane_pack.py``, PR 44).  Both calls run
everything token-wise on the tick's live lanes as ``lane_pack.rows(SLOTS,
C, 2)`` dense rows, as ``models/xing4.py`` does with a head of one: a
decoding slot's two lanes ``[t_p, d]`` (and a prompt's last two tokens) sit
in the rows' head, a longer chunk takes a group.  ONE ``LaneMap`` a call;
the verify lanes and the draft's lane are read at ``LaneMap.row_of``.  A
subclass with ``packed_lanes = False`` (the tests') is the ``[SLOTS, C]``
program, whose ``hidden`` is ``[SLOTS, C, d]``.

Counters (``counters`` collection): ``lanes_live [1, SLOTS]`` and
``rows_dense [1, 1]`` (the first call's; how full its rows were),
``expert_load [expert layers, E]``,
``expert_load_held [expert layers, count]`` (the same over the experts
held here), ``expert_weight_visits``, ``attn_positions_walked``; the
module's call sows its one layer's rows under the same names and the
engine lays them beside the model's.  Scopes: ``sandwich_norm`` (the four
norms a layer), ``mtp`` (the module whole: ``latent_attention`` and
``moe_*`` nest under it as in a layer).

Weights and activations are ``dtype``/``param_dtype`` (bfloat16 as
served); norm statistics, the rotation, the router, the softmax and the
logits are float32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from apex_example_tpu.models.layers import (LatentAttention, RoutedExperts,
                                            SwiGLU, fan_in, matmul_f32,
                                            rms_norm)
from apex_example_tpu.obs.spans import device_span
from apex_example_tpu.ops import lane_pack


class PanguLayer(nn.Module):
    """Attention and feed-forward, each between its two RMSNorms.  ``cfg``
    is the model's own field values.  Returns ``(x, load, visits,
    walked)`` as ``models/xing4.Xing4Layer``."""

    cfg: Tuple[Tuple[str, object], ...]
    dense: bool

    @nn.compact
    def __call__(self, x, pos, paged, live, lanes=None):
        """``x [B, L, d]``, or with ``lanes`` the tick's packed rows ``[R,
        d]`` (``live`` then ``[R]``); ``pos [B, L]`` either way."""
        c = dict(self.cfg)
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        dtype, pd = c["dtype"], c["param_dtype"]

        def norm(name, t):
            with device_span("sandwich_norm"):
                return rms_norm(t, self.param(name, nn.initializers.ones,
                                              (d,), pd), eps)

        # plain rotary: YaRN at factor 1 (no frequency is rescaled, both
        # mscales are 1)
        rope = (float(c["rope_theta"]), 1.0, float(c["max_position"]),
                32.0, 1.0, 1.0, 1.0)
        y, walked = LatentAttention(
            d, c["num_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["q_lora_rank"], c["kv_lora_rank"], eps, rope,
            dtype, pd, c["decode"], c["slot_decode"], c["kv_num_blocks"],
            c["kv_block_size"], name="attn")(
                norm("attn_norm", x), pos, paged, lanes)
        u = x + norm("attn_post_norm", y)
        h = norm("ffn_norm", u)
        load = visits = None
        if self.dense:
            y = SwiGLU(d, c["intermediate_size"], dtype, pd, name="mlp")(h)
        else:
            E = c["n_routed_experts"]
            y, load, visits = RoutedExperts(
                d, c["moe_intermediate_size"], E, c["num_experts_per_tok"],
                float(c["routed_scaling_factor"]),
                tuple(c["experts_held"] or (0, E)), dtype, pd,
                name="moe")(h, live)
        return u + norm("ffn_post_norm", y), load, visits, walked


class NextTokenModule(nn.Module):
    """``z = Block(W_eh [N_e(e) ; N_h(h)])``, normed: ``h`` the model's
    output at each position, ``e`` the embedding of the token that
    follows it."""

    cfg: Tuple[Tuple[str, object], ...]

    @nn.compact
    def __call__(self, h, e, pos, paged, live, lanes=None):
        c = dict(self.cfg)
        d, eps, pd = c["hidden_size"], c["rms_norm_eps"], c["param_dtype"]
        scale = lambda name: self.param(name, nn.initializers.ones, (d,), pd)
        with device_span("sandwich_norm"):
            both = jnp.concatenate([rms_norm(e, scale("enorm"), eps),
                                    rms_norm(h, scale("hnorm"), eps)], -1)
        m = matmul_f32(both, self.param("eh_proj", fan_in(2 * d),
                                        (2 * d, d), pd)).astype(c["dtype"])
        z, load, visits, walked = PanguLayer(self.cfg, False, name="block")(
            m, pos, paged, live, lanes)
        with device_span("sandwich_norm"):
            return rms_norm(z, scale("norm"), eps), load, visits, walked


class PanguMoEForCausalLM(nn.Module):
    """Returns float32 logits ``[B, L, V]`` from the plain forward (with
    ``mtp=True`` the module's ``[B, L, V]`` beside them: position ``i``'s
    are of token ``i + 2``), and from the paged one what the module
    docstring says."""

    vocab_size: int = 153600
    hidden_size: int = 7680
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    max_position: int = 131072
    # the one next-token module; the engine drafts this many tokens a tick
    num_nextn_predict_layers: int = 1
    # the routed experts held here, (first, count); None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.bfloat16
    # the serving contract (serve/slots.BlockPool clones with these)
    tensor_parallel: bool = False
    fused_attention: bool = False
    decode: bool = False
    slot_decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0
    kv_quant: bool = False

    # the paged head runs on a slot's verify lanes only: the engine can
    # verify this model's own drafts and no host proposer's
    all_lane_logits = False
    # the paged program's token-wise sublayers take the tick's live lanes
    # as lane_pack.rows(SLOTS, C, lane_head) dense rows, a slot's first
    # ``lane_head`` lanes (the fed token and its drafts) outside any group:
    # the engine budgets to that
    packed_lanes = True

    @property
    def lane_head(self) -> int:
        return 1 + self.num_nextn_predict_layers

    def __post_init__(self):
        if self.experts_held is not None:
            # (a configuration file gives a list; a module's fields are
            # hashed: serve/engine.py caches its step on the module)
            object.__setattr__(self, "experts_held",
                               tuple(self.experts_held))
        super().__post_init__()

    @nn.compact
    def __call__(self, input_ids, train: bool = True, paged=None,
                 mtp: bool = False, draft_from=None):
        del train
        if self.kv_quant or self.tensor_parallel:
            raise ValueError(
                "the latent cache leaf is head-less: kv_quant (per-head "
                "scale tables) and tensor parallelism (a head axis to "
                "shard) are not built for it (ROADMAP)")
        if self.num_nextn_predict_layers != 1:
            raise ValueError("this model carries one next-token module "
                             f"(got {self.num_nextn_predict_layers})")
        d, eps = self.hidden_size, self.rms_norm_eps
        cfg = tuple((f, getattr(self, f)) for f in self.__dataclass_fields__
                    if f not in ("parent", "name"))
        B, L = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        live = lanes = None
        if paged is not None:
            pos = paged["fill"][:, None] + pos
            live = jnp.arange(L)[None, :] < paged["n_new"][:, None]
            if self.packed_lanes:
                # ONE map a call: every layer packs and unpacks through it
                lanes = lane_pack.LaneMap(paged["n_new"], L, self.lane_head)
                live = lanes.row_live
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (self.vocab_size, d), self.param_dtype)
        head = self.param("head", fan_in(d), (d, self.vocab_size),
                          self.param_dtype)
        counted = ([], [], [])                # load, visits, walked

        def count(load, visited, walked):
            for rows, row in zip(counted, (load, visited, walked)):
                if row is not None:
                    rows.append(row)

        def embedded(ids):
            """``E[ids]``, of the tick's packed rows where it has a map
            (a dead row holds zeros from here on: lane_pack's promise)."""
            if lanes is None:
                return embed[ids].astype(self.dtype)
            return jnp.where(live[:, None],
                             embed[lanes.pack(ids)].astype(self.dtype), 0)

        def module(h, next_ids):
            """The next-token module over every position (every row)."""
            with device_span("mtp"):
                z, *rows = NextTokenModule(cfg, name="mtp")(
                    h, embedded(next_ids), pos, paged, live, lanes)
            count(*rows)
            return z

        def at_lanes(rows, lane):
            """``rows`` (``[S, C, ...]``, or packed ``[R, ...]``) at lanes
            ``lane [S, K]`` of each slot: ``[S, K, ...]``."""
            if lanes is None:
                return jnp.take_along_axis(rows, lane.reshape(
                    lane.shape + (1,) * (rows.ndim - 2)), axis=1)
            fed = paged["n_new"] > 0
            return jnp.where(fed.reshape((-1,) + (1,) * rows.ndim),
                             rows[lanes.row_of(lane)], 0)

        if draft_from is not None:
            # the tick's second call: the module alone, and its head on
            # the one lane the next draft is read from
            h, next_ids, lane = draft_from
            z = module(h, next_ids)
            with device_span("mtp"):
                out = matmul_f32(at_lanes(z, lane[:, None])[:, 0], head)
        else:
            x = embedded(input_ids)
            for i in range(self.num_layers):
                x, *rows = PanguLayer(
                    cfg, i < self.first_k_dense, name=f"layer_{i}")(
                        x, pos, paged, live, lanes)
                count(*rows)
            final = self.param("final_norm", nn.initializers.ones, (d,),
                               self.param_dtype)
            if paged is None:
                h = rms_norm(x, final, eps)
                out = matmul_f32(h, head)
                if mtp or self.is_initializing():
                    # (the init trace allocates the module's weights and
                    # its cache leaf)
                    z = module(h, jnp.concatenate(
                        [input_ids[:, 1:], jnp.zeros((B, 1), jnp.int32)], 1))
                    if mtp:
                        with device_span("mtp"):
                            out = out, matmul_f32(z, head)
            elif "n_draft" in paged:
                # the head on each slot's verify lanes
                K = self.num_nextn_predict_layers
                last = jnp.maximum(paged["n_new"] - 1, 0)
                verify = jnp.clip(
                    (last - paged["n_draft"])[:, None] + jnp.arange(K + 1),
                    0, last[:, None])
                h = rms_norm(x, final, eps)
                out = matmul_f32(at_lanes(h, verify), head), h
            else:
                # the head on each slot's sampled lane only
                lane = jnp.clip(paged["n_new"] - 1, 0, L - 1)
                out = matmul_f32(rms_norm(at_lanes(x, lane[:, None]), final,
                                          eps), head)
        keep = dict(reduce_fn=lambda _, new: new, init_fn=lambda: None)
        if paged is not None and draft_from is None:
            # how full the rows of the token-wise products were this tick
            # (the module's call runs on the same rows: said once)
            self.sow("counters", "lanes_live", paged["n_new"][None, :],
                     **keep)
            self.sow("counters", "rows_dense", jnp.full(
                (1, 1), live.size, jnp.int32), **keep)
        first, held = self.experts_held or (0, self.n_routed_experts)
        loads, visits, walks = counted
        for name, rows in (("expert_load", loads),
                           ("expert_load_held",
                            [r[first:first + held] for r in loads]),
                           ("expert_weight_visits", visits),
                           ("attn_positions_walked", walks)):
            if rows:
                self.sow("counters", name, jnp.stack(rows), **keep)
        return out


def openpangu_ultra_moe_718b_cut(**kw) -> PanguMoEForCausalLM:
    """One chip's share at the published widths (benchmarks/configs/
    openpangu_ultra_moe_718b.json): 16 chips share each expert layer
    (experts 0-15 live here), the vocabulary is split over 8, and of the
    pipeline's stages this chip shows the first dense layer, 4 of the 58
    expert layers and the last stage's module and head."""
    for k, v in dict(num_layers=5, first_k_dense=1, experts_held=(0, 16),
                     vocab_size=19200).items():
        kw.setdefault(k, v)
    return PanguMoEForCausalLM(**kw)


def pangu_moe_tiny(**kw) -> PanguMoEForCausalLM:
    """Test-scale configuration (same code path, CPU-friendly, float32)."""
    for k, v in dict(vocab_size=256, hidden_size=64, num_layers=3,
                     first_k_dense=1, num_heads=4, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24,
                     kv_lora_rank=32, intermediate_size=128,
                     moe_intermediate_size=32, n_routed_experts=8,
                     num_experts_per_tok=2, max_position=4096,
                     dtype=jnp.float32, param_dtype=jnp.float32).items():
        kw.setdefault(k, v)
    return PanguMoEForCausalLM(**kw)
