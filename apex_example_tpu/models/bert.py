"""BERT-base for masked-LM pretraining (workload C4, SURVEY.md §1).

The reference imports BERT from an external repo and exercises apex on it
(amp-O2 + FusedLAMB, BASELINE.json config 4); the parity target is the
standard BERT-base architecture: learned word+position+type embeddings with
post-embedding LayerNorm, 12 post-norm encoder layers (self-attention + GELU
FFN, hidden 768, heads 12, FFN 3072), and an MLM head whose decoder is tied
to the word embeddings.

TPU-native specifics:
- All LayerNorms are :class:`FusedLayerNorm` (the Pallas kernel — fp32 stats
  regardless of compute dtype, the MixedFusedLayerNorm contract).
- ``dtype``/``param_dtype`` thread the amp policy; attention logits and
  softmax run in fp32 (the op-classification "blacklist" of amp O1/O2:
  softmax is fp32; SURVEY.md §3.1).
- Static shapes throughout; the attention mask is an additive bias, so the
  whole step stays jit-compatible.
"""

from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp
from flax import linen as nn

from apex_example_tpu.normalization import FusedLayerNorm
from apex_example_tpu.obs.spans import device_span

# Measured fused-vs-XLA crossover on the v5e rig (PERF.md attention table):
# the flash kernel loses below ~2k tokens (XLA's fusions keep the small
# score tensor cheap; the kernel adds launch/blocking overhead) and wins
# above (O(S·D) HBM vs the naive path's O(S²) probability tensor).
FLASH_AUTO_MIN_SEQ = 2048


def _resolve_fused_attention(setting: Union[bool, str], seq_len: int,
                             softmax_dtype) -> bool:
    """The fused_attention policy: explicit bool wins; "auto" keys on the
    measured crossover.  The kernel's softmax is always fp32, so any
    half-softmax contract (O3) forces the naive path."""
    if softmax_dtype != jnp.float32:
        return False
    if isinstance(setting, bool):
        return setting
    if setting == "auto":
        return seq_len >= FLASH_AUTO_MIN_SEQ
    raise ValueError(f"fused_attention must be bool or 'auto', "
                     f"got {setting!r}")


def _softmax_attention(q, k, v, softmax_dtype, out_dtype,
                       bool_mask=None, add_bias=None):
    """The einsum attention core shared by the standard and KV-cache-decode
    paths: scaled QK^T (+boolean mask as a where, +additive bias), softmax
    in ``softmax_dtype``, context product.  ``bool_mask`` broadcasts
    against [B, H, Sq, Sk]; the -1e9/-1e4 "minus infinity enough" constant
    follows the half-dtype clamp rationale (fp16 overflows -1e9 to -inf
    and a fully-masked row would softmax to NaN)."""
    hd = q.shape[-1]
    sd = softmax_dtype
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(sd)
    logits = logits / jnp.sqrt(hd).astype(sd)
    neg = -1e9 if sd == jnp.float32 else -1e4
    if bool_mask is not None:
        logits = jnp.where(bool_mask, logits, jnp.asarray(neg, sd))
    if add_bias is not None:
        logits = logits + jnp.maximum(add_bias, neg).astype(sd)
    probs = nn.softmax(logits, axis=-1).astype(out_dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class BertSelfAttention(nn.Module):
    hidden_size: int
    num_heads: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # softmax is blacklisted under O0–O2 (fp32); O3 runs it half.  Resolved
    # by amp/autocast.module_dtypes and threaded in by the builder.
    softmax_dtype: jnp.dtype = jnp.float32
    # Blockwise flash-attention kernel (ops/attention.py).  Only taken when
    # the softmax contract is fp32 — the kernel always computes fp32 softmax,
    # so routing O3's half-softmax through it would silently upgrade
    # precision.  The op itself falls back to the XLA reference off-TPU.
    # "auto" (default) applies the measured crossover: kernel at seq >=
    # FLASH_AUTO_MIN_SEQ, XLA einsum path below.
    fused_attention: Union[bool, str] = "auto"
    # Megatron-style tensor parallelism (GSPMD form): q/k/v are column-
    # parallel (heads shard over the ``model`` axis), the output projection
    # is row-parallel.  Param names/shapes are identical to the dense path —
    # checkpoints interchange.  sequence_parallel additionally keeps the
    # activations outside the TP block sequence-sharded (Megatron-SP).
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    # Ring context parallelism (shard_map form): the sequence is sharded
    # over the 'context' mesh axis; q/k/v projections are per-token local,
    # attention runs as a ppermute KV ring whose per-chunk scores stay in
    # VMEM (parallel/context_parallel.ring_attention, flash-composed) —
    # the long-context training path (no reference analog).
    context_parallel: bool = False
    # Causal (decoder-only) masking: position t attends to keys <= t.  On
    # the einsum path a triangular bias; the flash kernel and the KV ring
    # take it natively (their blockwise/chunkwise skip logic).  Consumed
    # by models/gpt.py.
    causal: bool = False
    # Context-parallel attention program (with context_parallel):
    #   "ring"    — ppermute KV ring, contiguous chunks (flash-composed);
    #   "zigzag"  — load-balanced CAUSAL ring: local shards hold zigzag
    #               chunk pairs (i, 2n-1-i), identical live work per ring
    #               step (the caller reorders the batch with zigzag_shard);
    #   "ulysses" — all-to-all head sharding: full sequence per device,
    #               H/N heads per device, exact attention (DeepSpeed-
    #               Ulysses form; needs heads % axis size == 0).
    cp_mode: str = "ring"
    # Autoregressive KV-cache decoding (flax 'cache' collection, the
    # canonical single-token pattern): init with a [B, max_len] dummy
    # allocates cached_key/cached_value/cache_index; each subsequent call
    # takes ONE token, writes its k/v at the running index, and attends
    # against the filled prefix.  models/gpt.generate drives it.
    decode: bool = False
    # Block-paged slot decode (with decode=True): instead of a dense
    # [B, max_len, H, D] page per row, K/V live in one shared arena a
    # layer, kv_num_blocks blocks of kv_block_size tokens
    # (ops/paged_cache.py: the layout and the tick's operations on it).
    # Each batch row is an independent request slot whose sequence is
    # scattered across blocks named by a per-slot block table — the
    # ``paged`` call argument carries the table plus per-slot fill levels,
    # new-token counts and copy-on-write pairs, all host-owned
    # (serve/slots.py allocates; there is no device-side index state).
    # One compiled step advances every live slot by up to kv_block_size
    # tokens (chunked prefill) or one (decode); the geometry is static.
    # Which form of attention reads the arenas (PR 41): float arenas held
    # whole on one chip go through ops.attention.paged_gqa_attention — on
    # the TPU, and under the tests' interpreter, a Pallas kernel that
    # walks each slot's live blocks where they lie (two heads of 64 a
    # lane tile); on a plain CPU drive and under FORCE_XLA the op's XLA
    # form, which gathers the whole table row.  int8 arenas (kv_quant)
    # and arenas sharded over 'model' (tensor_parallel) keep the
    # gathered view and this module's own masked softmax.
    slot_decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0
    # Quantized paged KV (ISSUE 13, with slot_decode): the arenas store
    # int8 K/V with bf16 PER-TOKEN scales (a scale table beside each
    # arena) — quantized on the write, dequantized (scale-fused) in the
    # gathered attention, scale rows copied with their payload rows on
    # COW so prefix-sharing semantics carry over unchanged.  Geometry
    # stays static; the program still compiles exactly once.  The
    # attention math itself (softmax included) runs at full precision
    # on the dequantized values — the amp/lists sensitivity contract.
    kv_quant: bool = False

    @nn.compact
    def __call__(self, x, mask_bias, paged=None):
        d = self.hidden_size
        h = self.num_heads
        hd = d // h
        if self.decode and (self.context_parallel or self.sequence_parallel
                            or mask_bias is not None or not self.causal):
            raise ValueError(
                "decode (KV-cache) is the causal inference path: no "
                "CP/SP/mask composition (tensor_parallel composes: the "
                "cache shards over heads like training attention; SP's "
                "sequence-dim constraints cannot partition a length-1 "
                "decode step)")
        use_kernel = (not self.decode) and _resolve_fused_attention(
            self.fused_attention, x.shape[1], self.softmax_dtype)
        if self.tensor_parallel:
            from apex_example_tpu.transformer.tensor_parallel.layers import (
                ColumnParallelLinear, RowParallelLinear, batch_axis,
                constrain)
            dense_in = lambda name: ColumnParallelLinear(
                d, gather_output=False,
                sequence_parallel=self.sequence_parallel,
                dtype=self.dtype, param_dtype=self.param_dtype, name=name)
            dense_out = RowParallelLinear(
                d, input_is_parallel=True,
                sequence_parallel=self.sequence_parallel,
                dtype=self.dtype, param_dtype=self.param_dtype,
                name="output")
            # Heads shard over 'model': the (…, d)->(…, h, hd) reshape keeps
            # h outer, so the column-sharded feature dim becomes a sharded
            # head dim (hd stays whole — it is the MXU lane dim).
            head_spec = lambda t: constrain(t, batch_axis(), None, "model",
                                            None)
        else:
            dense_in = lambda name: nn.Dense(d, dtype=self.dtype,
                                             param_dtype=self.param_dtype,
                                             name=name)
            dense_out = nn.Dense(d, dtype=self.dtype,
                                 param_dtype=self.param_dtype, name="output")
            head_spec = lambda t: t
        q = head_spec(dense_in("query")(x).reshape(*x.shape[:-1], h, hd))
        k = head_spec(dense_in("key")(x).reshape(*x.shape[:-1], h, hd))
        v = head_spec(dense_in("value")(x).reshape(*x.shape[:-1], h, hd))
        if self.decode:
            from jax import lax as _lax
            cache_ready = self.has_variable("cache", "cached_key")
            if self.slot_decode:
                # [NB, BS, H*D] K and V leaves a layer: ops/paged_cache.py
                # holds the layout and every operation on it; the compiled
                # step only executes the table the host hands it.
                from apex_example_tpu.ops import paged_cache
                NB, BS = self.kv_num_blocks, self.kv_block_size
                kv_store = jnp.int8 if self.kv_quant else k.dtype
                ck, cv = (paged_cache.variable(self, name, NB, BS, kv_store,
                                               d)
                          for name in ("cached_key", "cached_value"))
                if self.kv_quant:
                    from apex_example_tpu.quant import kv as kv_quant
                    cks, cvs = (paged_cache.variable(
                        self, name, NB, BS, kv_quant.KV_SCALE_DTYPE)
                        for name in ("cached_key_scale",
                                     "cached_value_scale"))
            else:
                if self.kv_quant:
                    raise ValueError("kv_quant quantizes the block-"
                                     "paged arena; it requires "
                                     "slot_decode=True")
                ck = self.variable("cache", "cached_key", jnp.zeros,
                                   k.shape, k.dtype)
                cv = self.variable("cache", "cached_value", jnp.zeros,
                                   v.shape, v.dtype)
                ci = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((), jnp.int32))
            if cache_ready and self.slot_decode:
                if paged is None:
                    raise ValueError(
                        "paged slot decode needs the host state: pass "
                        "paged={'block_table', 'fill', 'n_new', "
                        "'cow_src', 'cow_dst'} (serve/engine.py builds "
                        "it each tick)")
                # Under TP the payload leaves shard over heads on 'model'
                # like the dense decode cache; tables, fills and scale
                # tables stay replicated (host policy, not sharded state).
                arena = (lambda t: constrain(t, None, None, "model")) \
                    if self.tensor_parallel else None
                table = paged["block_table"]          # [S, max_blocks]
                # 1. Copy-on-write, scale rows with their payload rows.
                cow_src, cow_dst = paged["cow_src"], paged["cow_dst"]
                ck.value, cv.value = paged_cache.cow(
                    (ck.value, cv.value), cow_src, cow_dst, arena)
                if self.kv_quant:
                    cks.value, cvs.value = paged_cache.cow(
                        (cks.value, cvs.value), cow_src, cow_dst)
                # 2. This tick's K/V: token j of slot s at fill[s] + j.
                with device_span("kv_write"):
                    pos = paged["fill"][:, None] \
                        + jnp.arange(x.shape[1])[None, :]
                flat = paged_cache.write_rows(table, pos, paged["n_new"],
                                              NB, BS)
                if self.kv_quant:
                    # Quantize on the write: one max-abs scale per token
                    # over its [h, hd] vector, through the SAME flat rows.
                    with device_span("kv_write"):
                        k, k_sc = kv_quant.quantize_write(k)
                        v, v_sc = kv_quant.quantize_write(v)
                    cks.value, cvs.value = paged_cache.write(
                        (cks.value, cvs.value), flat, (k_sc, v_sc))
                ck.value, cv.value = paged_cache.write(
                    (ck.value, cv.value), flat, (k, v), arena)
                # 3. Attention under the per-slot causal live mask: query
                # j (position fill+j) sees keys at positions <= fill+j;
                # stale rows sit beyond it, and the host discards dead
                # slots' lanes.  A float arena held whole on one chip is
                # read where it lies: ops.attention.paged_gqa_attention
                # (a Pallas kernel over each slot's live blocks on the
                # TPU, its XLA gather form on the CPU; softmax in fp32).
                # What that op cannot take keeps the gathered form below:
                # int8 rows with a scale table, heads sharded over 'model'
                # (a pallas_call is opaque to the partitioner), a half-
                # softmax contract.
                if not (self.kv_quant or self.tensor_parallel
                        or self.softmax_dtype != jnp.float32):
                    from apex_example_tpu.ops.attention import (
                        paged_gqa_attention)
                    with device_span("paged_attention"):
                        ctx, _ = paged_gqa_attention(
                            q, ck.value, cv.value, table, paged["fill"],
                            paged["n_new"], scale=1.0 / float(hd) ** 0.5)
                        return dense_out(ctx.reshape(*x.shape[:-1], d))
                # each slot's logical view ([S, max_blocks*BS, H, D])
                keys, vals = paged_cache.gather((ck.value, cv.value), table,
                                                heads=h)
                if self.kv_quant:
                    # scale-fused dequant: attention runs at full precision
                    k_sc, v_sc = paged_cache.gather((cks.value, cvs.value),
                                                    table)
                    with device_span("kv_gather"):
                        keys = kv_quant.dequantize_gather(keys, k_sc,
                                                          self.dtype)
                        vals = kv_quant.dequantize_gather(vals, v_sc,
                                                          self.dtype)
                with device_span("paged_attention"):
                    L = keys.shape[1]
                    live = jnp.arange(L)[None, None, :] <= pos[:, :, None]
                    # head_spec: under TP the arena shards over heads
                    # ('model') exactly like training attention.
                    ctx = _softmax_attention(q, head_spec(keys),
                                             head_spec(vals),
                                             self.softmax_dtype, self.dtype,
                                             bool_mask=live[:, None])
                    return dense_out(ctx.reshape(*x.shape[:-1], d))
            if cache_ready:      # per-token decode step (cache exists)
                if x.shape[1] != 1:
                    raise ValueError("decode takes ONE token per call "
                                     f"(got seq {x.shape[1]}); the "
                                     "[B, max_len] shape is for cache "
                                     "allocation at init only")
                idx = ci.value
                ck.value = _lax.dynamic_update_slice(ck.value, k,
                                                     (0, idx, 0, 0))
                cv.value = _lax.dynamic_update_slice(cv.value, v,
                                                     (0, idx, 0, 0))
                ci.value = idx + 1
                # keys beyond the running index are unwritten slots
                live = jnp.arange(ck.value.shape[1]) <= idx
                mask = live[None, None, None]
                # head_spec: under TP the cache shards over heads ('model')
                # exactly like training attention — the constraint keeps
                # GSPMD from gathering the [B, max_len, h, hd] cache.
                ctx = _softmax_attention(q, head_spec(ck.value),
                                         head_spec(cv.value),
                                         self.softmax_dtype, self.dtype,
                                         bool_mask=mask)
                return dense_out(ctx.reshape(*x.shape[:-1], d))
            # init trace on the [B, max_len] dummy: cache allocated above;
            # fall through to the standard causal path so params/shapes
            # initialize.
        if self.context_parallel:
            # Same projections as the dense path (identical param tree);
            # only the attention computation changes: a ppermute KV ring
            # over the 'context'-sharded sequence.
            if self.softmax_dtype != jnp.float32:
                # ring_attention always computes its online softmax in fp32;
                # silently upgrading O3's half-softmax contract would make
                # CP runs incomparable with the dense O3 model (mirror of
                # _resolve_fused_attention's fp32-softmax gate).
                raise ValueError(
                    "context_parallel attention computes fp32 softmax; "
                    f"softmax_dtype={self.softmax_dtype} (O3 half-softmax) "
                    "does not compose with it")
            from apex_example_tpu.parallel.context_parallel import (
                ring_attention)
            if mask_bias is not None:
                raise ValueError("context_parallel BERT does not support an "
                                 "attention mask (the benchmark MLM path "
                                 "uses none); masking would need per-chunk "
                                 "key-bias rotation in the ring")
            if self.cp_mode == "zigzag":
                if not self.causal:
                    raise ValueError(
                        "cp_mode='zigzag' is the load-BALANCED CAUSAL "
                        "layout; non-causal CP has uniform work already — "
                        "use the plain ring")
                from apex_example_tpu.parallel.context_parallel import (
                    ring_attention_zigzag)
                ctx = ring_attention_zigzag(q, k, v,
                                            scale=1.0 / float(hd) ** 0.5)
            elif self.cp_mode == "ulysses":
                from apex_example_tpu.parallel.context_parallel import (
                    ulysses_attention)
                ctx = ulysses_attention(q, k, v, causal=self.causal,
                                        scale=1.0 / float(hd) ** 0.5)
            elif self.cp_mode == "ring":
                # causal=True: contiguous sequence chunks; blocks entirely
                # in the future are skipped, the diagonal chunk masks
                # blockwise (zigzag is the load-balanced causal variant).
                ctx = ring_attention(q, k, v, causal=self.causal,
                                     scale=1.0 / float(hd) ** 0.5)
            else:
                raise ValueError(f"unknown cp_mode {self.cp_mode!r} "
                                 "(ring | zigzag | ulysses)")
            return dense_out(ctx.reshape(*x.shape[:-1], d))
        if use_kernel and not self.tensor_parallel:
            # (TP runs the einsum path: pallas_call is opaque to the SPMD
            # partitioner, while the einsums partition over the head dim.)
            from apex_example_tpu.ops.attention import flash_attention
            key_bias = None if mask_bias is None \
                else mask_bias[:, 0, 0, :].astype(jnp.float32)
            ctx = flash_attention(q, k, v, key_bias, causal=self.causal,
                                  scale=1.0 / float(hd) ** 0.5)
            return dense_out(ctx.reshape(*x.shape[:-1], d))
        tri = None
        if self.causal:
            S = x.shape[1]
            tri = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
        ctx = _softmax_attention(q, k, v, self.softmax_dtype, self.dtype,
                                 bool_mask=tri, add_bias=mask_bias)
        ctx = ctx.reshape(*x.shape[:-1], d)
        return dense_out(ctx)


class BertLayer(nn.Module):
    hidden_size: int
    num_heads: int
    intermediate_size: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    ln_dtype: Optional[jnp.dtype] = None     # LN I/O; None follows dtype
    softmax_dtype: jnp.dtype = jnp.float32
    fused_attention: Union[bool, str] = "auto"
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    context_parallel: bool = False
    # Switch-MoE FFN: >0 replaces the dense MLP with moe_experts experts
    # (transformer/expert_parallel.MoEMLP).  When >0 the layer returns
    # (x, aux_loss) — the load-balancing term belongs in the objective.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_axis_name: str = "expert"
    moe_top_k: int = 1
    causal: bool = False
    cp_mode: str = "ring"
    decode: bool = False
    slot_decode: bool = False
    kv_num_blocks: int = 0
    kv_block_size: int = 0
    kv_quant: bool = False

    @nn.compact
    def __call__(self, x, mask_bias, paged=None):
        # LN I/O dtype per the op classification (O1: fp32; O2/O3: half
        # I/O).  The Pallas kernel computes its statistics in fp32
        # regardless, so half I/O loses no precision in the moments — the
        # MixedFusedLayerNorm contract.
        ln_io = self.ln_dtype or self.dtype
        attn = BertSelfAttention(self.hidden_size, self.num_heads,
                                 self.dtype, self.param_dtype,
                                 self.softmax_dtype,
                                 fused_attention=self.fused_attention,
                                 tensor_parallel=self.tensor_parallel,
                                 sequence_parallel=self.sequence_parallel,
                                 context_parallel=self.context_parallel,
                                 causal=self.causal,
                                 cp_mode=self.cp_mode,
                                 decode=self.decode,
                                 slot_decode=self.slot_decode,
                                 kv_num_blocks=self.kv_num_blocks,
                                 kv_block_size=self.kv_block_size,
                                 kv_quant=self.kv_quant,
                                 name="attention")(x, mask_bias,
                                                   paged=paged)
        x = FusedLayerNorm(dtype=ln_io, name="attention_ln")(
            (x + attn).astype(ln_io))
        x = x.astype(self.dtype)
        if self.moe_experts:
            from apex_example_tpu.transformer.expert_parallel import MoEMLP
            y, aux = MoEMLP(self.hidden_size, self.intermediate_size,
                            self.moe_experts,
                            capacity_factor=self.moe_capacity_factor,
                            dtype=self.dtype, param_dtype=self.param_dtype,
                            axis_name=self.moe_axis_name,
                            top_k=self.moe_top_k, name="moe")(x)
        elif self.tensor_parallel:
            # Megatron MLP: column (sharded GELU features) -> row (the
            # all-reduce — or, under sequence_parallel, the reduce-scatter
            # onto sequence shards — lands at the row output constraint).
            # (checked after moe_experts: under the MoE x TP composition
            # the FFN is the expert block and TP applies to attention/head)
            from apex_example_tpu.transformer.tensor_parallel.layers import (
                ColumnParallelLinear, RowParallelLinear)
            y = ColumnParallelLinear(
                self.intermediate_size, gather_output=False,
                sequence_parallel=self.sequence_parallel, dtype=self.dtype,
                param_dtype=self.param_dtype, name="intermediate")(x)
            y = nn.gelu(y, approximate=False)
            y = RowParallelLinear(
                self.hidden_size, input_is_parallel=True,
                sequence_parallel=self.sequence_parallel, dtype=self.dtype,
                param_dtype=self.param_dtype, name="output")(y)
        else:
            y = nn.Dense(self.intermediate_size, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="intermediate")(x)
            y = nn.gelu(y, approximate=False)
            y = nn.Dense(self.hidden_size, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="output")(y)
        x = FusedLayerNorm(dtype=ln_io, name="output_ln")(
            (x + y).astype(ln_io))
        x = x.astype(self.dtype)
        return (x, aux) if self.moe_experts else x


class BertForMaskedLM(nn.Module):
    """BERT encoder + tied-decoder MLM head over one parameter tree.

    ``encode`` gives the encoder's output (B, S, H), ``head`` the fp32 vocab
    logits of any rows (..., H) of it, and ``__call__`` is the head of every
    row of the encoder's output.  A train step whose loss counts only the
    labelled rows (``workloads.mlm_loss.over_rows``) applies the two apart,
    where ``head_apart`` says it may, and forms logits for those rows alone.
    """

    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    ln_dtype: Optional[jnp.dtype] = None
    softmax_dtype: jnp.dtype = jnp.float32
    fused_attention: Union[bool, str] = "auto"
    # Megatron TP over the GSPMD 'model' mesh axis: vocab-sharded embeddings
    # + tied parallel LM head, column/row attention and MLP.  Consumed by
    # engine.make_gspmd_train_step / train.py --tensor-parallel.
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    # Ring context parallelism: __call__ runs inside shard_map with the
    # 'context' axis bound, input_ids holding THIS shard's sequence slice;
    # position ids offset by the shard index, attention rides the KV ring.
    # Consumed by workloads.make_bert_cp_train_step / --context-parallel.
    context_parallel: bool = False
    # Switch-MoE encoder FFNs (expert parallelism over moe_axis_name —
    # train.py --moe-experts binds it to the 'data' axis, DeepSpeed-MoE
    # style).  When >0 __call__ returns (logits, aux): the load-balancing
    # loss is part of the objective and rides the output contract.
    # Consumed by workloads.make_bert_moe_train_step.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_axis_name: str = "expert"
    moe_top_k: int = 1
    # context-parallel attention program: "ring" (default) or "ulysses"
    # (all-to-all head sharding; "zigzag" is causal-only -> GPT)
    cp_mode: str = "ring"

    @property
    def head_apart(self) -> bool:
        """Whether a step may gather rows of ``encode``'s output and apply
        ``head`` to them alone.  Not under tensor parallelism (the decoder is
        vocab-sharded and the rows data-sharded: the gather would cross the
        mesh), nor where ``encode`` returns an auxiliary loss beside the
        rows (MoE) or holds a slice of the sequence (context parallelism)."""
        return not (self.tensor_parallel or self.moe_experts
                    or self.context_parallel)

    def setup(self):
        if self.moe_experts and self.sequence_parallel:
            # SP re-shards the sequence dim the dispatch indexes.  (TP
            # composes: the FFN is the expert block and the Megatron
            # sharding applies to attention/embeddings/head on the
            # automatic model axis.  CP composes: every local token still
            # routes over the full expert set via the all_to_all on
            # 'data', independent of the KV ring on 'context' — per-shard
            # routing/capacity, the pure-EP per-device contract.)
            raise ValueError("moe_experts does not compose with "
                             "sequence parallelism yet")
        if self.sequence_parallel and self.context_parallel:
            raise ValueError("sequence_parallel shards activations along "
                             "the sequence dim the context axis already "
                             "owns; CP composes with plain tensor_parallel")
        self.ln_io = ln_io = self.ln_dtype or self.dtype
        if self.tensor_parallel:
            from apex_example_tpu.transformer.tensor_parallel.layers import (
                VocabParallelEmbedding)
            self.word_embeddings = VocabParallelEmbedding(
                self.vocab_size, self.hidden_size, dtype=self.dtype,
                param_dtype=self.param_dtype)
        else:
            self.word_embeddings = nn.Embed(
                self.vocab_size, self.hidden_size, dtype=self.dtype,
                param_dtype=self.param_dtype)
        self.position_embeddings = nn.Embed(
            self.max_position, self.hidden_size, dtype=self.dtype,
            param_dtype=self.param_dtype)
        self.embeddings_ln = FusedLayerNorm(dtype=ln_io)
        for i in range(self.num_layers):
            setattr(self, f"layer_{i}", BertLayer(
                self.hidden_size, self.num_heads, self.intermediate_size,
                self.dtype, self.param_dtype, self.ln_dtype,
                self.softmax_dtype,
                fused_attention=self.fused_attention,
                tensor_parallel=self.tensor_parallel,
                sequence_parallel=self.sequence_parallel,
                context_parallel=self.context_parallel,
                moe_experts=self.moe_experts,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_axis_name=self.moe_axis_name,
                moe_top_k=self.moe_top_k,
                cp_mode=self.cp_mode))
        self.mlm_dense = nn.Dense(self.hidden_size, dtype=self.dtype,
                                  param_dtype=self.param_dtype)
        self.mlm_ln = FusedLayerNorm(dtype=ln_io)
        bias_init = nn.initializers.zeros
        if self.tensor_parallel:
            bias_init = nn.with_partitioning(bias_init, ("model",))
        self.mlm_bias = self.param("mlm_bias", bias_init,
                                   (self.vocab_size,), jnp.float32)

    def _layers(self, input_ids, attention_mask):
        """Embeddings and the layers: (B, S) ids -> (B, S, H) rows and the
        sum of the layers' auxiliary losses (0 without ``moe_experts``)."""
        if self.context_parallel and attention_mask is not None:
            raise ValueError("context_parallel BERT does not support an "
                             "attention mask")
        L = input_ids.shape[1]
        x = self.word_embeddings(input_ids)
        pos = jnp.arange(L)[None, :]
        if self.context_parallel:
            # input_ids hold this context shard's slice; global positions
            # offset by the shard index (bound by the enclosing shard_map).
            from jax import lax as _lax
            from apex_example_tpu.parallel.mesh import CONTEXT_AXIS
            pos = pos + _lax.axis_index(CONTEXT_AXIS) * L
        x = x + self.position_embeddings(pos)
        x = self.embeddings_ln(x.astype(self.ln_io)).astype(self.dtype)

        if attention_mask is not None:
            mask_bias = jnp.where(attention_mask[:, None, None, :] > 0,
                                  0.0, -1e9).astype(jnp.float32)
        else:
            mask_bias = None

        aux_total = jnp.zeros((), jnp.float32)
        for i in range(self.num_layers):
            x = getattr(self, f"layer_{i}")(x, mask_bias)
            if self.moe_experts:
                x, aux = x
                aux_total = aux_total + aux
        return x, aux_total

    def encode(self, input_ids, attention_mask: Optional[jnp.ndarray] = None,
               train: bool = True):
        """The encoder's output, (B, S) ids -> (B, S, H) rows (without the
        auxiliary loss of ``moe_experts``, which ``__call__`` returns)."""
        del train  # no dropout in the pretraining benchmark path
        return self._layers(input_ids, attention_mask)[0]

    def head(self, x):
        """MLM head of rows (..., H): dense+gelu+LN, then the tied decoder;
        fp32 logits (..., V).  Under TP the decoder is the parallel LM head
        (vocab-sharded logits — the CE's logsumexp reduction over vocab
        becomes a psum, GSPMD's lowering of Megatron's
        vocab_parallel_cross_entropy)."""
        with device_span("mlm_head"):
            x = nn.gelu(self.mlm_dense(x), approximate=False)
            x = self.mlm_ln(x.astype(self.ln_io)).astype(self.dtype)
            logits = self.word_embeddings.attend(x) + self.mlm_bias
            return logits.astype(jnp.float32)

    def __call__(self, input_ids, attention_mask: Optional[jnp.ndarray] = None,
                 train: bool = True):
        del train
        x, aux_total = self._layers(input_ids, attention_mask)
        logits = self.head(x)
        if self.moe_experts:
            return logits, aux_total / self.num_layers
        return logits


def bert_base(**kw) -> BertForMaskedLM:
    return BertForMaskedLM(**kw)


def bert_tiny(**kw) -> BertForMaskedLM:
    """Test-scale configuration (same code path, CPU-friendly)."""
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("max_position", 128)
    return BertForMaskedLM(**kw)
