"""GPT-style decoder-only causal LM.

Reference status: the reference family's LM workloads are BERT (bidirectional
MLM) and Transformer-XL (causal via segment recurrence); a plain decoder-only
GPT is ABSENT there.  It is added here because it is the natural flagship for
the framework's long-context machinery: causal flash attention
(ops/attention.py), the causal ppermute KV ring (parallel/context_parallel),
Megatron TP/SP (transformer/tensor_parallel), ZeRO, and switch-MoE FFNs all
compose with it through the same module flags BERT uses — the model is the
composition demo, not new machinery.

Architecture: learned token+position embeddings -> N post-LN transformer
layers (models/bert.BertLayer with causal=True) -> final LayerNorm ->
tied decoder head (vocab logits, fp32).  The objective is next-token CE
(workloads.lm_loss) on an input/target pair shifted by one token — train.py
generates seq_len+1 tokens per example so the model always sees exactly
seq_len positions.
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_example_tpu.models.bert import BertLayer
from apex_example_tpu.normalization import FusedLayerNorm


class GPTForCausalLM(nn.Module):
    """Decoder-only transformer; returns (B, S, vocab) fp32 logits (plus the
    MoE aux loss when moe_experts > 0, mirroring BertForMaskedLM's
    contract)."""

    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 1024
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    ln_dtype: Optional[jnp.dtype] = None
    softmax_dtype: jnp.dtype = jnp.float32
    fused_attention: Union[bool, str] = "auto"
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    context_parallel: bool = False
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_axis_name: str = "expert"
    moe_top_k: int = 1
    # Context-parallel attention program: "ring" (contiguous causal KV
    # ring), "zigzag" (load-balanced causal ring — the step factory
    # reorders the batch with zigzag_shard and position ids follow), or
    # "ulysses" (all-to-all head sharding, full sequence per device).
    cp_mode: str = "ring"
    # Autoregressive KV-cache inference (see :func:`generate`): init with
    # a [B, max_len] dummy to allocate per-layer caches, then apply one
    # token at a time with mutable=["cache"].
    decode: bool = False
    # Block-paged slot decode (with decode=True): K/V live in one
    # [kv_num_blocks, kv_block_size, H*D] arena per layer, addressed
    # through per-slot block tables, and there is NO device-side index
    # state at all — the host (serve/slots.py BlockPool) owns fill
    # levels, allocation, refcounts and copy-on-write, and passes the
    # per-tick state in through the ``paged`` call argument.  Each batch
    # row is an independent request slot fed up to kv_block_size tokens
    # per step (chunked prefill) or one (decode); the geometry is
    # static, so one compiled step serves every slot mix.  This is the
    # substrate the continuous-batching engine (serve/) schedules on.
    # Float arenas on one chip are read by
    # ops.attention.paged_gqa_attention (a Pallas kernel over the live
    # blocks on the TPU, its XLA gather form on the CPU); int8 arenas and
    # tensor-parallel ones keep the gathered view (models/bert.py).
    slot_decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0
    # Quantized paged KV (ISSUE 13, with slot_decode): int8 arenas with
    # bf16 per-token block scales — quantize on the scatter write,
    # scale-fused dequant in the gathered attention, scales copied with
    # their blocks on COW (models/bert.py holds the mechanics).
    kv_quant: bool = False

    @nn.compact
    def __call__(self, input_ids, train: bool = True, paged=None):
        del train  # no dropout in the pretraining benchmark path
        if self.moe_experts and self.sequence_parallel:
            # (TP composes: the expert block replaces the FFN; Megatron
            # sharding applies to attention/embeddings/head.  CP composes
            # too: the expert all_to_all over 'data' and the KV ring over
            # 'context' are independent collectives — routing/capacity are
            # per-(data, context) shard, the pure-EP per-device contract.)
            raise ValueError("moe_experts does not compose with "
                             "sequence parallelism yet")
        if self.sequence_parallel and self.context_parallel:
            raise ValueError("sequence_parallel shards activations along "
                             "the sequence dim the context axis already "
                             "owns; CP composes with plain tensor_parallel")
        ln_io = self.ln_dtype or self.dtype
        b, L = input_ids.shape
        if self.tensor_parallel:
            from apex_example_tpu.transformer.tensor_parallel.layers import (
                VocabParallelEmbedding)
            word_emb = VocabParallelEmbedding(
                self.vocab_size, self.hidden_size, dtype=self.dtype,
                param_dtype=self.param_dtype, name="word_embeddings")
        else:
            word_emb = nn.Embed(self.vocab_size, self.hidden_size,
                                dtype=self.dtype,
                                param_dtype=self.param_dtype,
                                name="word_embeddings")
        if self.decode and (self.moe_experts or self.context_parallel
                            or self.sequence_parallel):
            # SP shards activations along the sequence dim, which is 1 in
            # per-token decode — its scatter/gather constraints cannot
            # partition it; rejecting here beats an opaque GSPMD
            # divisibility error deep in the trace.
            raise ValueError("decode (KV-cache) is the dense/TP inference "
                             "path: no CP/MoE/sequence-parallel "
                             "composition")
        if self.slot_decode and not self.decode:
            raise ValueError("slot_decode modifies the KV-cache indices; "
                             "it requires decode=True")
        x = word_emb(input_ids)
        pos = jnp.arange(L)[None, :]
        if self.decode and self.slot_decode:
            # Paged slot decode: positions come from the HOST's per-slot
            # fill levels (paged["fill"]), not a device counter — the
            # block pool is the single source of truth for how far each
            # slot has filled.  paged is None only on the init trace
            # (cache allocation), where plain arange positions serve the
            # [B, max_len] dummy.  The clip keeps garbage lanes of dead
            # slots inside the position table; real lanes never bind
            # (fill + j <= max_len - 1 <= max_position - 1).
            if paged is not None:
                pos = jnp.clip(paged["fill"][:, None] + pos,
                               0, self.max_position - 1)
        elif self.decode:
            # position = running cache index (checked BEFORE .variable
            # creates it: at allocation time the dummy covers 0..L-1)
            cache_ready = self.has_variable("cache", "cache_position")
            pi = self.variable("cache", "cache_position",
                               lambda: jnp.zeros((), jnp.int32))
            if cache_ready:      # per-token decode step
                pos = pos + pi.value
                pi.value = pi.value + L
        if self.context_parallel:
            from jax import lax as _lax
            from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
            i = _lax.axis_index(CONTEXT_AXIS)
            if self.cp_mode == "zigzag":
                # zigzag layout: this shard's halves are global chunks i
                # and 2n-1-i (each of length L/2)
                n = _lax.axis_size(CONTEXT_AXIS)
                c = L // 2
                pos = jnp.concatenate(
                    [jnp.arange(c) + i * c,
                     jnp.arange(c) + (2 * n - 1 - i) * c])[None, :]
            else:
                # contiguous chunks: global positions offset by the shard
                # index (the causal ring keys on the same order)
                pos = pos + i * L
        x = x + nn.Embed(self.max_position, self.hidden_size,
                         dtype=self.dtype, param_dtype=self.param_dtype,
                         name="position_embeddings")(pos)
        x = FusedLayerNorm(dtype=ln_io, name="embeddings_ln")(
            x.astype(ln_io)).astype(self.dtype)

        aux_total = jnp.zeros((), jnp.float32)
        for i in range(self.num_layers):
            x = BertLayer(self.hidden_size, self.num_heads,
                          self.intermediate_size, self.dtype,
                          self.param_dtype, self.ln_dtype,
                          self.softmax_dtype,
                          fused_attention=self.fused_attention,
                          tensor_parallel=self.tensor_parallel,
                          sequence_parallel=self.sequence_parallel,
                          context_parallel=self.context_parallel,
                          moe_experts=self.moe_experts,
                          moe_capacity_factor=self.moe_capacity_factor,
                          moe_axis_name=self.moe_axis_name,
                          moe_top_k=self.moe_top_k,
                          causal=True, cp_mode=self.cp_mode,
                          decode=self.decode,
                          slot_decode=self.slot_decode,
                          kv_num_blocks=self.kv_num_blocks,
                          kv_block_size=self.kv_block_size,
                          kv_quant=self.kv_quant,
                          name=f"layer_{i}")(x, None, paged=paged)
            if self.moe_experts:
                x, aux = x
                aux_total = aux_total + aux

        x = FusedLayerNorm(dtype=ln_io, name="final_ln")(
            x.astype(ln_io)).astype(self.dtype)
        logits = word_emb.attend(x)
        bias_init = nn.initializers.zeros
        if self.tensor_parallel:
            bias_init = nn.with_partitioning(bias_init, ("model",))
        logits = logits + self.param("lm_bias", bias_init,
                                     (self.vocab_size,), jnp.float32)
        logits = logits.astype(jnp.float32)
        if self.moe_experts:
            return logits, aux_total / self.num_layers
        return logits


def gpt_base(**kw) -> GPTForCausalLM:
    return GPTForCausalLM(**kw)


def gpt_tiny(**kw) -> GPTForCausalLM:
    """Test-scale configuration (same code path, CPU-friendly)."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("max_position", 128)
    return GPTForCausalLM(**kw)


def sample_tokens(rng, logits: jnp.ndarray, temperature,
                  top_k=0) -> jnp.ndarray:
    """Next-token selection over [B, V] logits with RUNTIME temperature and
    top-k — both enter as traced values (scalars or per-row [B] vectors),
    so ONE compiled decode program serves every sampling configuration.
    Per-row vectors are how the continuous-batching engine
    (serve/engine.py) mixes greedy and sampled requests in one batch.

    temperature == 0 selects argmax (greedy); top_k == 0 samples the full
    softmax; top_k > 0 restricts sampling to the k highest logits (a tie
    at the threshold keeps >= k candidates).

    The expensive lanes are fenced by runtime ``lax.cond``s, so a batch
    that is entirely greedy executes only the argmax, and the full-vocab
    sort runs only when some row actually wants top-k — the hot decode
    path does not pay for sampling features it isn't using.
    """
    B, V = logits.shape
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def topk_filter(lg):
        # Runtime k rules out lax.top_k (static k): the per-row cutoff
        # is the k-th largest logit via a descending sort, k clamped
        # into [1, V]; rows with k == 0 skip the filter.
        kk = jnp.clip(k, 1, V)
        desc = -jnp.sort(-lg, axis=-1)
        thresh = jnp.take_along_axis(desc, (kk - 1)[:, None], axis=-1)
        return jnp.where((k[:, None] > 0) & (lg < thresh), -jnp.inf, lg)

    def sample(lg):
        filtered = lax.cond(jnp.any(k > 0), topk_filter, lambda x: x, lg)
        # max() keeps the t == 0 lanes finite; their sample is discarded
        # by the where below (greedy wins), so their distribution is moot.
        return jax.random.categorical(
            rng, filtered / jnp.maximum(t, 1e-6)[:, None]).astype(jnp.int32)

    sampled = lax.cond(jnp.any(t > 0), sample, lambda lg: greedy, logits)
    return jnp.where(t > 0, sampled, greedy)


def generate(model: GPTForCausalLM, params, prompt: jnp.ndarray,
             max_len: int, temperature: float = 0.0, rng=None,
             top_k: int = 0) -> jnp.ndarray:
    """Autoregressive generation with a KV cache (greedy at temperature 0,
    categorical sampling otherwise).

    ``prompt`` is [B, P] int32; returns [B, max_len] — the prompt followed
    by max_len - P generated tokens.  TPU-idiomatic decode: ONE jitted
    ``lax.scan`` over single-token steps with static shapes throughout —
    per-layer K/V caches ([B, max_len, H, D], allocated by a one-time init
    trace) are scan carries, each step costs O(max_len·D) attention
    against the filled prefix instead of re-running the O(S²) forward on
    a growing sequence.  Prompt positions are fed through the same loop
    (their logits are discarded), so prefill and decode share one
    compiled program.

    Beyond-reference: the reference family is training-only; this makes
    the GPT family usable end-to-end (models/gpt.py docstring).

    Composes with tensor parallelism: for a ``tensor_parallel=True`` model
    under a registered ``parallel_state`` mesh, the per-layer KV caches
    shard over heads on the ``model`` axis exactly like training attention
    (pass TP-sharded ``params``; the constraint points in the layers do the
    rest).  The XLA reference ops are pinned for the trace — pallas custom
    calls are opaque to the SPMD partitioner (same as train.py's TP path).
    """
    B, P = prompt.shape
    if not 0 < P < max_len:
        raise ValueError(f"need 0 < prompt len {P} < max_len {max_len}")
    if model.max_position < max_len:
        raise ValueError(f"max_len {max_len} exceeds the model's position "
                         f"table ({model.max_position})")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and rng is None:
        raise ValueError("temperature > 0 samples; pass rng=PRNGKey")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0 (0 = full softmax), "
                         f"got {top_k}")
    dec = model.clone(decode=True, fused_attention=False)
    # cache ALLOCATION without compute: eval_shape traces the init only
    # abstractly (no training-scale dummy forward actually runs), then the
    # zeroed pytree is built from the shapes.
    shapes = jax.eval_shape(
        dec.init, jax.random.PRNGKey(0),
        jnp.zeros((B, max_len), jnp.int32))["cache"]
    cache = jax.tree_util.tree_map(
        lambda t: jnp.zeros(t.shape, t.dtype), shapes)
    tokens = jnp.zeros((B, max_len), jnp.int32).at[:, :P].set(prompt)
    if rng is None:
        rng = jax.random.PRNGKey(0)          # carried but unused (greedy)
    run = _decode_loop(dec, max_len)
    # Cost observability (obs/costmodel.py, --cost-model): with a
    # default instance installed the loop compiles through the AOT path
    # and the compilation is harvested; instrument() caches per
    # (name, fn), so repeated generate() calls at one config keep
    # reusing ONE compiled program — identity when no instance is set.
    # Lazy import: generate() is also used from contexts that never
    # touch the obs package.
    from apex_example_tpu.obs import costmodel as _costmodel
    run = _costmodel.instrument("gpt_decode_loop", run)
    args = (params, tokens, cache, rng, jnp.asarray(P, jnp.int32),
            jnp.asarray(float(temperature), jnp.float32),
            jnp.asarray(int(top_k), jnp.int32))
    if model.tensor_parallel:
        from apex_example_tpu.ops import _config as ops_config
        with ops_config.force_xla():
            return run(*args)
    return run(*args)


@functools.lru_cache(maxsize=32)
def _decode_loop(dec: GPTForCausalLM, max_len: int):
    """Jitted scan for :func:`generate`, cached on the static
    configuration (the module is a frozen dataclass, so it keys the
    cache): repeated generate() calls reuse one compiled program, and
    params enter as an ARGUMENT — baked-as-constants weights would bloat
    the executable and defeat the cache.  temperature and top_k ride as
    TRACED scalars through :func:`sample_tokens`, so one compiled program
    serves every sampling configuration — temperature used to be part of
    this cache key and recompiled the loop per distinct value."""

    def step(params, P, temperature, top_k, carry, t):
        tokens, cache, rng = carry
        B = tokens.shape[0]
        tok = lax.dynamic_slice(tokens, (0, t), (B, 1))
        logits, mut = dec.apply({"params": params, "cache": cache}, tok,
                                train=False, mutable=["cache"])
        cache = mut["cache"]
        rng, key = jax.random.split(rng)
        nxt = sample_tokens(key, logits[:, -1], temperature, top_k)
        # inside the prompt, keep the given token (prefill); past it,
        # write the model's choice
        cur = lax.dynamic_slice(tokens, (0, t + 1), (B, 1))[:, 0]
        nxt = jnp.where(t + 1 < P, cur, nxt)
        tokens = lax.dynamic_update_slice(tokens, nxt[:, None], (0, t + 1))
        return (tokens, cache, rng), None

    @jax.jit
    def run(params, tokens, cache, rng, P, temperature, top_k):
        # P rides as a TRACED scalar (only `t + 1 < P` consumes it), so
        # one compiled program serves every prompt length at this shape.
        (tokens, _, _), _ = lax.scan(
            functools.partial(step, params, P, temperature, top_k),
            (tokens, cache, rng), jnp.arange(max_len - 1))
        return tokens

    return run
