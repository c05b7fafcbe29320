"""The continuous-batching scheduler loop.

One engine tick = expire + admit + step + harvest:

1. **expire/shed** — stamp virtual arrivals, shed the backlog overflow
   (bounded admission, ``RequestQueue(max_pending=...)``), expire queued
   requests whose deadline passed without admission, and deadline-evict
   decoding slots whose request ran out of time mid-flight.
2. **admit** — pop admissible requests from the queue into free slots,
   gated by the BLOCK budget as well as the slot count: admission
   reserves a request's worst-case KV-block need (after prefix
   sharing, serve/slots.py), so out-of-blocks resolves here —
   deterministic head-of-line queueing (the popped head goes back to
   the queue front) — never as a stuck decoding slot.  A request the
   engine could NEVER serve (zero output budget: its prompt fills the
   cache; or a block need beyond the whole arena) terminates
   first-class with status "rejected" instead of occupying a slot to
   emit nothing.
3. **step** — ONE compiled decode program advances every live slot:
   a slot still inside its prompt feeds up to ``block_size`` prompt
   tokens (CHUNKED PREFILL — long prompts no longer take one tick per
   token) and discards every prediction except the one after its final
   prompt token; a slot past its prompt feeds its previously sampled
   token and keeps the new one.  Prefill chunks and decode steps ride
   the same program in the same batch (per-slot ``n_new`` lane
   counts), so requests admitted at different ticks coexist — and the
   K/V they cache live in block-paged arenas addressed through
   per-slot block tables (copy-on-write prefix sharing included)
   rather than dense per-slot pages.  The geometry is static; the
   program compiles exactly once.
4. **harvest** — detect EOS / length completions, evict their slots,
   emit ``request_complete`` records; per-slot host work is exception-
   contained, so a failure (or an injected ``slot_fail``) terminates
   only that slot's request (``request_failed`` record with the
   traceback digest) while the engine keeps ticking.  A NaN/degenerate-
   logits guard on the sampled-token path fails the affected slots the
   same way instead of feeding garbage back into the cache.

Every request terminates in a first-class ``Completion(status=...)``
(serve/queue.py: ok / timeout / shed / cancelled / failed / drained) —
overload, deadlines, faults and drains resolve requests explicitly
rather than silently dropping them.

Graceful drain (``drain()``): stop admission, hand queued requests back
with status "drained" (requeue-able on another replica), finish or
deadline-evict the in-flight slots, and emit a ``serve_drain`` record —
the serving counterpart of train.py's ``--preempt-grace`` path
(serve.py wires it to SIGTERM/SIGUSR1 and exits ``EX_TEMPFAIL``).

The per-tick host sync (fetching the sampled tokens) is the deliberate
cost of host-side scheduling, mirroring the telemetry layer's stance on
device fetches: the batch geometry stays static, so the compiled program
never changes — the TPU-native substrate for a serving engine.

Sharding (ISSUE 14): under a registered parallel_state mesh the engine
serves TP-sharded — weights placed per the training TP layers'
partition metadata and every paged-KV arena head-sharded over 'model'
(serve/slots.BlockPool.shard), while the block tables, free-list
allocator and admission logic above stay host-side and replicated.
The step lowers once per geometry with GSPMD shardings and greedy
output stays token-identical to the dense path.

Roles (ISSUE 14, serve/disagg.py): ``role="prefill"`` terminates each
request at its FIRST sampled token with status "handoff", shipping its
KV blocks through ``handoff_sink``; ``role="decode"`` admits such
handoffs (``admit_handoff``) and decodes with a [SLOTS, 1]-wide step —
its ticks stop paying for prefill lanes entirely.  ``role="both"``
(default) is the classic interleaved engine.

Sampling is per-slot (temperature / top_k vectors through
models/gpt.sample_tokens), so greedy and sampled requests batch together.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
import traceback
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_example_tpu.models.gpt import sample_tokens
from apex_example_tpu.obs import costmodel as costmodel_lib
from apex_example_tpu.obs import trace as trace_lib
from apex_example_tpu.obs.metrics import Histogram, nearest_rank
from apex_example_tpu.obs.slo import SloTracker
from apex_example_tpu.obs.spans import Phases, device_span
from apex_example_tpu.obs.tickprof import (ENGINE_KEY_AHEAD, ENGINE_PHASES,
                                           ENGINE_TICK)
from apex_example_tpu.ops import lane_pack
from apex_example_tpu.resilience.faults import FaultInjected
from apex_example_tpu.serve.queue import (STATUSES, Completion, Request,
                                          RequestQueue)
from apex_example_tpu.serve.slots import BlockPool


def _wall() -> float:
    """Wall clock, for the ``time`` field of EMITTED RECORDS only.
    Every duration in this module is a difference of ``perf_counter``
    readings (the monotonic clock); the two domains meet nowhere except
    the ``clock_sync`` anchor a --trace run writes (obs/trace.py) —
    never in a subtraction."""
    return time.time()


def _pct_dict(vals_ms: List[float]) -> Dict[str, float]:
    s = sorted(vals_ms)
    return {"p50": round(nearest_rank(s, 50), 3),
            "p95": round(nearest_rank(s, 95), 3),
            "max": round(s[-1], 3) if s else 0.0}


@dataclasses.dataclass(frozen=True)
class TickArgs:
    """The column layout of the ONE int32 array a tick hands its step
    program, a row a slot: ``[SLOTS, C + T + R + 6 (+ 2)]`` for ``chunk``
    C lanes, a block table of ``blocks`` T columns and a window leaves'
    ring table of ``ring`` R (0 for a model without window leaves, whose
    layout is what it was) —

      ``tok`` [S, C] | ``block_table`` [S, T] | ``ring_table`` [S, R] |
      ``fill`` | ``n_new`` | ``cow_src`` | ``cow_dst`` | ``top_k`` |
      ``temperature`` (float32, carried as its bit pattern) | ``aux``
      [S, 2] (``self_draft`` only: :func:`draft_tick`'s draft count and
      next prompt token)

    A hand-off to the runtime costs the host 0.3–0.5 ms whatever it
    carries (PERF.md §5), so the tick makes one.  :meth:`fields` is the
    layout's one definition: on the host's numpy array it gives writable
    views (what ``ServeEngine._tick`` fills), on the traced array inside
    the program the same names as slices (what the step computes from),
    so packer and unpacker cannot drift apart.  Frozen and hashable: a key
    of the step builders' ``lru_cache`` beside the module clone."""

    chunk: int
    blocks: int
    self_draft: bool = False
    ring: int = 0

    SCALARS = ("fill", "n_new", "cow_src", "cow_dst", "top_k",
               "temperature")

    @property
    def width(self) -> int:
        return self.chunk + self.blocks + self.ring + len(self.SCALARS) \
            + 2 * self.self_draft

    def fields(self, packed) -> Dict[str, Any]:
        """``packed`` [SLOTS, width] int32 by field name: views of a numpy
        array, slices of a jax one.  The temperature is reinterpreted, not
        converted, on either side: bit for bit what the request said."""
        C, T, R = self.chunk, self.blocks, self.ring
        out = {"tok": packed[:, :C], "block_table": packed[:, C:C + T]}
        if R:
            out["ring_table"] = packed[:, C + T:C + T + R]
        for j, name in enumerate(self.SCALARS, C + T + R):
            out[name] = packed[:, j]
        bits = out["temperature"]
        out["temperature"] = bits.view(np.float32) \
            if isinstance(packed, np.ndarray) \
            else lax.bitcast_convert_type(bits, jnp.float32)
        if self.self_draft:
            out["aux"] = packed[:, self.width - 2:]
        return out

    def blank(self, num_slots: int):
        """``(packed, fields)`` of a tick nobody has filled yet: zeros, no
        COW pair (-1, -1), no draft lane and no next prompt token (0, -1)."""
        packed = np.zeros((num_slots, self.width), np.int32)
        f = self.fields(packed)
        f["cow_src"][:] = f["cow_dst"][:] = -1
        if self.self_draft:
            f["aux"][:] = (0, -1)
        return packed, f


# what of a tick's fields the models' paged forward reads (``ring_table``
# where the layout has one)
_PAGED = ("block_table", "ring_table", "fill", "n_new", "cow_src", "cow_dst")


def _paged(a: Dict[str, Any]) -> Dict[str, Any]:
    return {k: a[k] for k in _PAGED if k in a}


@functools.lru_cache(maxsize=8)
def _slot_step(dec, args: TickArgs, dequant_weights: bool = False,
               lanes: bool = False):
    """One compiled decode step for a PAGED slot-decode model clone
    (cached on the frozen module config, block geometry included, and on
    the layout of its arguments, with params as an argument:
    models/gpt._decode_loop's contract).  ``step(params, cache, packed,
    rng)``: everything a tick says about its slots arrives as the one
    int32 array ``packed`` (:class:`TickArgs`, one host-to-device put a
    tick), taken apart as the program's first traced operations.  ``tok``
    is [SLOTS, C]: a prefill chunk for slots inside their prompt, one
    token (lane 0) for decoding slots; ``n_new`` says how many lanes are
    real per slot, and sampling reads the logits AFTER each slot's last
    real token (a model whose paged head runs on that lane alone hands
    back ``[SLOTS, 1, V]`` logits and the take is skipped).  COW copies,
    the block-table K/V scatter and the gathered-attention live mask all
    run inside this one program (ops/paged_cache.py, called from the
    models).  A model that sows into a ``counters`` collection (an expert
    layer's per-tick load) gets it back as a fourth output.
    ``cache`` is DONATED: the arena leaves keep one physical layout from
    argument to result, so the program updates them in place and the
    leaves passed in are deleted by the call — the caller must rebind its
    cache from the first output (ServeEngine does, straight after the
    call) and hold the old leaves nowhere else.  Besides the sampled
    tokens it returns a per-slot logits-finite mask: argmax/categorical
    over NaN logits yield an IN-RANGE index, so only the finiteness of
    the logits themselves can see NaN fallout, and computing it here
    fuses it into the decode program.

    ``dequant_weights`` (ISSUE 13): params arrive as quant/weights.py's
    int8/fp8 {qvalue, scale} leaves and the dequant is the step's FIRST
    traced op — the low-bit bytes are the step's arguments (what HBM
    streams), and XLA fuses the scale multiply into each consuming matmul.

    ``lanes`` (ISSUE 18): the speculative engine's program; two more
    outputs for the accept/reject harvest are its one static difference
    (and no ``counters``) —

      * ``lane_greedy`` [SLOTS, C]: argmax over every lane's logits.
        Lane j's logits condition on lanes 0..j (causal live mask), so
        lane_greedy[s, j] is the model's greedy continuation after the
        j-th fed token; comparing it against the NEXT draft lane is the
        whole accept rule, on logits chunked prefill computes anyway.
      * ``lane_finite`` [SLOTS, C]: per-lane logits-finiteness, so NaN
        fallout in ANY verified lane poisons the slot.

    ``nxt`` samples from the last REAL lane either way, so sampled-
    temperature slots in a speculative batch behave token-identically to
    the plain path.  Both flags are lru_cache keys: arming quantization
    or --speculate K (geometry [SLOTS, max(BS, K+1)]) builds ONE new
    program; re-running a variant reuses its compile."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, packed, rng):
        if dequant_weights:
            from apex_example_tpu.quant import weights as _qw
            with device_span("dequant_weights"):
                params = _qw.dequantize_tree(params)
        a = args.fields(packed)
        tok, n_new = a["tok"], a["n_new"]
        logits, mut = dec.apply(
            {"params": params, "cache": cache}, tok, train=False,
            paged=_paged(a),
            mutable=["cache"] if lanes else ["cache", "counters"])
        with device_span("sample"):
            if logits.shape[1] == tok.shape[1]:
                idx = jnp.clip(n_new - 1, 0, tok.shape[1] - 1)
                last = jnp.take_along_axis(logits, idx[:, None, None],
                                           axis=1)[:, 0]
            else:
                # the model ran its head on each slot's sampled lane only
                last = logits[:, 0]
            nxt = sample_tokens(rng, last, a["temperature"], a["top_k"])
            finite = jnp.all(jnp.isfinite(last), axis=-1)
            out = (mut["cache"], nxt, finite)
            if lanes:
                out += (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        jnp.all(jnp.isfinite(logits), axis=-1))
        if mut.get("counters"):
            # what the model counted this tick (an expert layer's load):
            # a fourth output, kept unfetched by the engine
            out += (mut["counters"],)
        return out

    return step


def draft_tick(dec, args: TickArgs, params, cache, packed, rng):
    """One serve tick of a model that drafts for itself with its own
    next-token module (``num_nextn_predict_layers``; models/pangu_moe.py):
    verify this tick's draft, deliver one token or two, and make the next
    draft.  Traced inside :func:`_draft_step`'s one program; ``packed`` is
    the tick's one argument array, taken apart as :func:`_slot_step` does
    (``args.fields``, with ``aux`` [SLOTS, 2] as its last two columns).

    A greedy decoding slot feeds ``[t_p, d]`` (``aux[:, 0]``, ``n_draft``,
    says how many of a slot's last lanes are drafts: 1 or 0).  The model's
    first call returns the head's logits on each slot's verify lanes
    ``[SLOTS, 2, V]`` (the lane before the draft and the draft's; the
    sampled lane twice where there is none) and the normed hidden state of
    every lane (``[SLOTS, C, d]``, or the tick's packed rows ``[R, d]`` from
    a model that declares ``packed_lanes``: it is only handed back).  ``n1``
    is sampled from the first as :func:`_slot_step` samples (so a
    sampled-temperature slot, which feeds no draft, behaves as on the plain
    path); the draft is accepted where ``d == n1``, and ``n2``, the greedy
    choice after it, is then delivered with it.  The second call
    runs the module on every lane — lane ``j`` reads the token that
    follows it: the next lane's inside a prompt chunk, ``aux[:, 1]`` (the
    prompt's next token, from the host; -1 where the prompt ends here or
    the slot decodes) or else ``n1`` after the verified lane, ``n2`` after
    the draft's — so its cache leaf fills in step with the model's, and
    reads the next draft at the last accepted lane.  A rejected lane's
    rows lie past the cursor in every leaf and are overwritten next tick.

    Returns ``(cache, picked [SLOTS, 3] int32 = n1, n2, next draft, finite
    [SLOTS], counters, logits, draft_logits)``: ``counters`` holds the
    model's (the module's rows after the layers') and ``drafts_verified``
    / ``drafts_accepted`` ``[1, 1]``, every tick; the two logits are for
    whoever compares them with a reference (the tests)."""
    a = args.fields(packed)
    tok, n_new = a["tok"], a["n_new"]
    n_draft, next_tok = a["aux"][:, 0], a["aux"][:, 1]
    paged = dict(_paged(a), n_draft=n_draft)
    (logits, hidden), mut = dec.apply(
        {"params": params, "cache": cache}, tok, train=False, paged=paged,
        mutable=["cache", "counters"])
    C = tok.shape[1]
    with device_span("sample"):
        n1 = sample_tokens(rng, logits[:, 0], a["temperature"], a["top_k"])
        finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))
    with device_span("draft_verify"):
        lane = jnp.arange(C)[None, :]
        verified = jnp.clip(n_new - 1 - n_draft, 0, C - 1)[:, None]
        following = jnp.concatenate(
            [tok[:, 1:], jnp.zeros_like(tok[:, :1])], axis=1)
        drafted = jnp.take_along_axis(following, verified, axis=1)[:, 0]
        accepted = (n_draft > 0) & (n1 == drafted)
        n2 = jnp.argmax(logits[:, 1], axis=-1).astype(jnp.int32)
        after = jnp.where(next_tok >= 0, next_tok, n1)
        next_ids = jnp.where(lane == verified, after[:, None], following)
        next_ids = jnp.where(lane == verified + 1, n2[:, None], next_ids)
        last = jnp.minimum(verified[:, 0] + accepted, C - 1)
    draft_logits, drafted_mut = dec.apply(
        {"params": params, "cache": mut["cache"]}, tok, train=False,
        paged=paged, draft_from=(hidden, next_ids, last),
        mutable=["cache", "counters"])
    with device_span("draft_verify"):
        picked = jnp.stack(
            [n1, n2, jnp.argmax(draft_logits, axis=-1).astype(jnp.int32)],
            axis=1)
        # the module's rows after the layers', and the tick's verdicts
        counters = dict(mut["counters"])
        for name, rows in drafted_mut["counters"].items():
            counters[name] = jnp.concatenate([counters[name], rows]) \
                if name in counters else rows
        one = lambda x: jnp.sum(x.astype(jnp.int32)).reshape(1, 1)
        counters["drafts_verified"] = one(n_draft)
        counters["drafts_accepted"] = one(accepted)
    return (drafted_mut["cache"], picked, finite, counters, logits,
            draft_logits)


@functools.lru_cache(maxsize=8)
def _draft_step(dec, args: TickArgs, dequant_weights: bool = False):
    """:func:`_slot_step` for a self-drafting model: :func:`draft_tick` as
    ONE compiled program ``step(params, cache, packed, rng)``, the cache
    donated.  The tick reads two of its outputs, as the plain step's, both
    requested from the device at once: the tokens (n1, n2 and the next
    draft of every slot in one array) and the logits-finite mask; the
    counters stay on the device."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, packed, rng):
        if dequant_weights:
            from apex_example_tpu.quant import weights as _qw
            with device_span("dequant_weights"):
                params = _qw.dequantize_tree(params)
        return draft_tick(dec, args, params, cache, packed, rng)[:4]

    return step


def _current_mesh():
    """The registered parallel_state mesh, or None when serving runs
    unsharded (no mesh, or every axis trivial)."""
    from apex_example_tpu.transformer import parallel_state
    mesh = parallel_state.get_mesh()
    if mesh is None or all(s <= 1 for s in mesh.shape.values()):
        return None
    return mesh


def _shard_params(mesh, dec, params):
    """Place ``params`` per the TP layers' partition metadata (heads/
    vocab over 'model', everything else replicated) — the same
    device_put the TP generate() test does, extended to quantized
    trees: an int8/fp8 ``{qvalue, scale}`` leaf shards its qvalue like
    the original kernel (same shape, same spec) with the per-channel
    scale replicated (small, and a replicated multiplicand fuses
    cleanly into the sharded matmul)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_example_tpu.quant.weights import is_quantized_leaf
    from apex_example_tpu.transformer.tensor_parallel.layers import (
        param_partition_specs)
    abs_vars = jax.eval_shape(dec.init, jax.random.PRNGKey(0),
                              jnp.zeros((1, 4), jnp.int32))
    specs = param_partition_specs(abs_vars)["params"]
    spec_by_path = {
        jax.tree_util.keystr(path): s
        for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=is_quantized_leaf)
    out = []
    for path, leaf in flat:
        spec = spec_by_path.get(jax.tree_util.keystr(path), P())
        if is_quantized_leaf(leaf):
            out.append({
                "qvalue": jax.device_put(leaf["qvalue"],
                                         NamedSharding(mesh, spec)),
                "scale": jax.device_put(leaf["scale"],
                                        NamedSharding(mesh, P()))})
        else:
            out.append(jax.device_put(leaf, NamedSharding(mesh, spec)))
    return jax.tree_util.tree_unflatten(treedef, out)


def _weight_dtype_name(mode: str, params) -> str:
    """serve_summary's ``weight_dtype`` (schema v11): the storage dtype
    of the quant-eligible weight classes — via the AMP quant policy
    when quantization is armed (so fp8 reports its emulated spelling on
    a jax without native fp8), and the ACTUAL params dtype when it is
    not (a bf16 checkpoint must report bf16, not a hardcoded
    float32)."""
    if mode != "none":
        from apex_example_tpu.amp.policy import get_quant_policy
        return get_quant_policy(mode).weight_dtype_name
    for leaf in jax.tree_util.tree_leaves(params):
        if hasattr(leaf, "dtype"):
            return str(leaf.dtype)
    return "none"


class SlotFailure(RuntimeError):
    """Raised inside one slot's harvest when its sampled token is
    degenerate (out-of-vocab / NaN-logits fallout) — contained to that
    slot like any other per-slot exception."""


def request_complete_record(comp: Completion,
                            run_id: Optional[str] = None, *,
                            with_tenant: bool = False) -> Dict[str, Any]:
    """The schema-v3 ``request_complete`` record for one ok completion.
    ``with_tenant`` (v17) stamps the scheduling lane — only set when
    tenancy is armed, so legacy streams stay byte-identical."""
    rec: Dict[str, Any] = {
        "record": "request_complete",
        "time": _wall(),
        "request_id": comp.request.uid,
        "prompt_tokens": len(comp.request.prompt),
        "output_tokens": len(comp.tokens),
        "ttft_ms": round((comp.ttft_s or 0.0) * 1e3, 3),
        "tpot_ms": round(comp.tpot_s * 1e3, 3),
        "finish_reason": comp.finish_reason,
        "slot": comp.slot,
        "queue_wait_ms": round((comp.queue_wait_s or 0.0) * 1e3, 3),
        "e2e_ms": round(comp.e2e_s * 1e3, 3),
        "admitted_step": comp.admitted_step,
        "finished_step": comp.finished_step,
        "temperature": float(comp.request.temperature),
        "top_k": int(comp.request.top_k),
    }
    if with_tenant:
        rec["tenant"] = getattr(comp.request, "tenant", "default")
    if run_id:
        rec["run_id"] = run_id
    return rec


def request_failed_record(comp: Completion,
                          run_id: Optional[str] = None, *,
                          with_tenant: bool = False) -> Dict[str, Any]:
    """The schema-v5 ``request_failed`` record for a timeout / cancelled
    / failed completion (drained requests ride the ``serve_drain``
    record instead — they are requeued, not failed)."""
    rec: Dict[str, Any] = {
        "record": "request_failed",
        "time": _wall(),
        "request_id": comp.request.uid,
        "status": comp.status,
        "prompt_tokens": len(comp.request.prompt),
        "output_tokens": len(comp.tokens),
        "failed_step": comp.finished_step,
    }
    if comp.slot >= 0:
        rec["slot"] = comp.slot
        rec["admitted_step"] = comp.admitted_step
    if comp.queue_wait_s is not None:
        rec["queue_wait_ms"] = round(comp.queue_wait_s * 1e3, 3)
    rec["e2e_ms"] = round(comp.e2e_s * 1e3, 3)
    if comp.error:
        rec["error"] = comp.error
    if with_tenant:
        rec["tenant"] = getattr(comp.request, "tenant", "default")
    if run_id:
        rec["run_id"] = run_id
    return rec


class ServeEngine:
    """Continuous-batching engine over a GPT-family model.

    ``model`` is the plain module, ``params`` its trained (or random)
    weights; the engine derives the paged slot-decode clone via its
    BlockPool (``block_size`` sets both the arena granularity and the
    chunked-prefill width; ``num_blocks`` defaults to dense capacity).
    ``sink`` (an obs.JsonlSink), when given, receives one
    ``request_complete`` / ``request_failed`` / ``shed`` record per
    terminated request; the caller writes the run header and the final
    ``serve_summary`` (see serve.py).  ``fault`` is an optional
    resilience ``FaultPlan`` whose step is a 1-based engine tick
    (``--inject-fault kind@tick``).
    """

    def __init__(self, model, params, *, num_slots: int = 4,
                 max_len: int = 128, block_size: int = 8,
                 num_blocks: Optional[int] = None, rng=None,
                 queue: Optional[RequestQueue] = None,
                 sink=None, run_id: Optional[str] = None,
                 fault=None, registry=None, kv_quant: bool = False,
                 weight_quant: str = "none", role: str = "both",
                 handoff_sink=None, slo=None,
                 slo_window_s: Optional[float] = None,
                 slo_window_ticks: int = 0, tick_profiler=None,
                 speculate: Optional[int] = None, proposer=None,
                 tenants=None, tag_tenants: bool = False,
                 advertise_prefixes: int = 0):
        if weight_quant not in ("none", "int8", "fp8"):
            raise ValueError(f"weight_quant must be none|int8|fp8, got "
                             f"{weight_quant!r}")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got "
                             f"{role!r}")
        if role == "prefill" and handoff_sink is None:
            raise ValueError("a prefill-role engine needs a "
                             "handoff_sink to ship finished prefills to "
                             "(serve/disagg.py transports)")
        # Self-drafting: a model that carries a next-token module
        # (``num_nextn_predict_layers``) is served with it — the module
        # drafts that many tokens a tick on the device, in the tick's own
        # program (_draft_step) — unless the caller says ``speculate=0``.
        # Every other model defaults to no speculation, as before.
        own = int(getattr(model, "num_nextn_predict_layers", 0))
        if speculate is None:
            speculate = own
        if speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        self.self_draft = bool(speculate and own)
        if self.self_draft and speculate > own:
            raise ValueError(
                f"speculate {speculate}: {type(model).__name__} drafts with "
                f"its {own} next-token module(s), {own} token(s) a tick "
                "(ROADMAP: K > 1)")
        if self.self_draft and proposer is not None:
            raise ValueError(
                f"{type(model).__name__} drafts for itself; its paged head "
                "runs on the verify lanes of its own draft only, so a host "
                "proposer has nothing to be verified against")
        if speculate and role != "both":
            raise ValueError("--speculate needs the interleaved engine "
                             "(role 'both'); disaggregated roles keep "
                             "their own step geometries")
        if speculate and speculate + 1 > max_len:
            raise ValueError(f"speculate {speculate} exceeds max_len "
                             f"{max_len} lanes")
        # the pool refuses speculation first where the cache tree holds
        # per-slot recurrent state (no rollback): the reason that stays
        # true whatever lanes the model's head runs on
        self.pool = BlockPool(model, num_slots, max_len,
                              block_size=block_size,
                              num_blocks=num_blocks, kv_quant=kv_quant,
                              spec_slack=speculate,
                              rows_read_next_token=self.self_draft)
        if self.pool.window is not None and role != "both":
            raise ValueError(
                f"role {role!r}: this model has window leaves, and a ring "
                "is not handed from a prefill to a decode worker (ROADMAP "
                "M3: hand-off of a ring between roles)")
        if speculate and not self.self_draft \
                and not getattr(model, "all_lane_logits", True):
            raise ValueError(
                "speculate verifies draft lanes against every lane's "
                f"logits; {type(model).__name__}'s paged head runs on the "
                "sampled lane only (ROADMAP: self-drafting)")
        # weight_quant names the mode ``params`` ALREADY carries (the
        # caller quantized at restore time — serve.py); the engine's
        # job is to dequantize inside the compiled step.
        self.weight_quant = weight_quant
        self.vocab_size = int(model.vocab_size)
        # Disaggregation (ISSUE 14): a "prefill" engine chunk-prefills
        # prompts, samples each request's FIRST token, then ships its
        # KV blocks through ``handoff_sink`` (status "handoff"); a
        # "decode" engine admits those payloads via admit_handoff() and
        # decodes ONE token per live slot per tick — its compiled step
        # is [SLOTS, 1]-wide, so decode ticks stop paying for the
        # [SLOTS, block_size] prefill geometry entirely.  "both" is the
        # classic interleaved engine.
        self.role = role
        self.handoff_sink = handoff_sink
        self.chunk = 1 if role == "decode" else self.pool.block_size
        # Speculation (ISSUE 18): K draft tokens per greedy slot per
        # tick, verified in ONE dispatch.  The step stays [SLOTS, C]
        # with C = max(block_size, K+1): prefill chunks and draft lanes
        # share the same static geometry, so arming --speculate K adds
        # exactly one compiled program (serve_spec_step) regardless of
        # acceptance behavior.  speculate == 0 leaves every line of the
        # plain path untouched.
        self.speculate = int(speculate)
        self.proposer = proposer
        if self.speculate and self.proposer is None \
                and not self.self_draft:
            from apex_example_tpu.spec import NgramProposer
            self.proposer = NgramProposer()
        if self.speculate:
            self.chunk = max(self.chunk, self.speculate + 1)
        # Lane packing (ops/lane_pack.py): a model that declares
        # ``packed_lanes`` runs its token-wise sublayers on the tick's
        # live lanes as dense rows, which hold every slot's first
        # ``lane_head`` lanes (1 where the model names none; a model that
        # drafts for itself names the fed token and its drafts) and this
        # many longer chunks; the marshal loop grants no more.
        # None — every other model — is no budget: each lane of
        # [SLOTS, C] is a row of the program, and the marshal is what it
        # was.  A decode-role engine has C = 1, budget 0 and no slot
        # that asks for more than a lane: packing is the identity there.
        self._lane_head = int(getattr(model, "lane_head", 1))
        self._chunk_budget = lane_pack.groups(
            num_slots, self.chunk, self._lane_head) \
            if getattr(model, "packed_lanes", False) else None
        self.prefill_chunks_deferred = 0
        self.prefill_ticks_deferring = 0
        self._window_released_seen = 0
        # Hand-offs between the tick's host thread and the runtime (the
        # key, the one put, the step's call, each fetch), summed over the
        # ticks that ran a step.  (Not the KV hand-offs between a prefill
        # and a decode worker: those are handoffs_in/_out.)
        self.runtime_handoffs = 0
        self.tokens_drafted = 0
        self.tokens_accepted = 0
        self.tokens_sampled = 0
        self.handoffs_in = 0
        self.handoff_requeued = 0
        self._handoff_bytes = 0
        self._handoff_ms: List[float] = []
        # Idempotent admission (ISSUE 15): uids this engine already
        # admitted — a redelivered claim (worker died between admit and
        # ack; duplicate delivery after lease skew) is detected here
        # and acked WITHOUT a second scatter.  A restarted fleet
        # replica seeds it from its outbox so a handoff completed just
        # before the crash is never served twice (serve.py).
        self.handoff_seen: set = set()
        self.handoff_redelivered: set = set()   # uids admitted from a
        #                                         reclaimed/adopted lease
        self.handoff_duplicates = 0
        # Live migration (ISSUE 20): MID-FLIGHT requests shipped whole —
        # KV blocks, generated tokens and sampler state — to a peer that
        # resumes them (extract_live / admit_migrated).  Same transport,
        # same idempotence set (handoff_seen keys on uid, and a uid is
        # admitted here at most once regardless of payload kind), its
        # own counters so the v18 summary can tell a drain-without-
        # eviction from a prefill->decode pipeline.
        self.migrations_in = 0
        self.migration_requeued = 0
        self.migration_duplicates = 0
        self.migration_redelivered: set = set()
        self._migration_bytes = 0
        self._migration_ms: List[float] = []
        # Mesh awareness: under a registered parallel_state mesh the
        # weights and per-layer KV arenas shard over heads on the
        # 'model' axis (the bert/gpt constraint points from the TP
        # training path do the in-trace work); block tables, free-list
        # and admission stay host-side and replicated.  The compiled
        # step lowers ONCE per geometry with GSPMD shardings; pallas
        # kernels are opaque to the partitioner, so sharded calls pin
        # the XLA reference ops exactly like generate() under TP.
        self.mesh = None
        self.dp = self.tp = 1
        mesh = _current_mesh()
        if mesh is not None:
            from apex_example_tpu.parallel.mesh import (
                DATA_AXIS, require_model_axis_match)
            self.tp = require_model_axis_match(
                mesh, bool(model.tensor_parallel))
            self.dp = mesh.shape.get(DATA_AXIS, 1)
            self.mesh = mesh
            params = _shard_params(mesh, self.pool.dec, params)
            self.pool.shard(mesh)
        self.params = params
        self.queue = queue if queue is not None else RequestQueue()
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        # The split the NEXT step will use, made while the chip runs this
        # tick's program: (the carried key it was split from, the key to
        # carry on, the step's key).  Held aside and committed on use, so
        # ``self.rng`` is at all times the state after exactly
        # ``compute_steps`` splits; a pair made from another key than the
        # one carried now (someone set ``rng``) is not used.
        self._key_ahead = None
        self.sink = sink
        self.run_id = run_id
        self.fault = fault
        self.registry = registry
        self.step_count = 0
        self.compute_steps = 0
        self.completions: List[Completion] = []
        self.counts: Dict[str, int] = {s: 0 for s in STATUSES}
        self.draining = False
        # --cost-model (obs/costmodel.py): when a default instance is
        # installed, the decode step compiles through the AOT path and
        # that ONE compilation lands as compile_event + cost_model
        # records — the batch geometry is static, so a second
        # compile_event for this name is a recompile regression.  The
        # prefill role instruments under its own name: its program is
        # [SLOTS, block_size]-wide while the decode role's is
        # [SLOTS, 1]-wide — one program per role, each compiling once.
        self.tick_args = TickArgs(self.chunk, self.pool.max_blocks,
                                  self.self_draft, self.pool.ring_blocks)
        self._step_fn = costmodel_lib.instrument(
            "serve_spec_step" if self.speculate
            else "serve_prefill_step" if role == "prefill"
            else "serve_decode_step",
            _draft_step(self.pool.dec, self.tick_args,
                        weight_quant != "none")
            if self.self_draft else
            _slot_step(self.pool.dec, self.tick_args,
                       dequant_weights=weight_quant != "none",
                       lanes=bool(self.speculate)))
        self._t0 = time.perf_counter()
        self._tokens_out = 0
        self._occupancy_sum = 0
        # Per-compute-tick gauges (schema v6/v7 serve_summary): live
        # slots, logical KV bytes, physically-held arena blocks and
        # admission-committed bytes — block-accurate occupancy (the
        # dense-page layout these replace measured ~92% kv_waste_pct).
        self._occ_hist = Histogram("serve.slots_live")
        self._kv_hist = Histogram("serve.kv_bytes_live")
        self._blk_hist = Histogram("serve.blocks_live")
        self._committed_hist = Histogram("serve.kv_bytes_committed")
        # What the model itself counted in a tick (a "counters"
        # collection: the expert layers' load over the live lanes), kept
        # with the gauges as (tick's end on perf_counter, device arrays)
        # and NOT fetched — a fetch a tick would sit in every tick's host
        # gap; whoever reads the log fetches (benchmarks/runners/
        # serve_blocked.py; 8192 ticks are kept).
        self.counter_log: collections.deque = collections.deque(
            maxlen=8192)
        # --trace (obs/trace.py): the process-default tracer, when one
        # is armed, receives the per-tick admit/dispatch/harvest spans
        # and a per-request lifecycle span tree.  Everything below is
        # host-side bookkeeping of timestamps the engine already takes:
        # tracing changes NO device work and the compiled decode step
        # is untouched.  _rtrace buffers each admitted request's
        # prefill-chunk windows so its whole tree can be emitted in
        # timestamp order at terminal time (a request stranded
        # mid-flight at a --steps cap simply never emits, rather than
        # leaving an unbalanced span behind).
        self._tracer = trace_lib.get_default()
        self._rtrace: Dict[str, List] = {}
        # --slo (obs/slo.py, ISSUE 16): the streaming SLO fold — pure
        # host-side state the terminal funnel and the per-tick gauge
        # block feed; windows close on wall time (slo_window_s) or
        # engine ticks (slo_window_ticks, the deterministic mode) and
        # emit slo_window/slo_breach records through the same sink.
        # The compiled step is untouched: arming --slo adds ZERO
        # compiled programs (the cost-model test asserts it).
        self.slo: Optional[SloTracker] = None
        if slo:
            self.slo = SloTracker(
                slo,
                window_s=slo_window_s if slo_window_s else 1.0,
                window_ticks=slo_window_ticks or 0,
                emit=sink.write if sink is not None else None,
                run_id=run_id)
        # --tick-profile (obs/tickprof.py, ISSUE 17): per-tick phase
        # decomposition, folded from the boundaries every tick reads
        # anyway (step(): obs.spans.Phases), so arming it changes no
        # device work and no value.  Idle-spin accounting (idle_ticks /
        # idle_wait_ms) is always on: it is free.
        self.tickprof = tick_profiler
        self.idle_ticks = 0
        self.idle_wait_ms = 0.0
        self._spool_ms = 0.0
        # --tenants (sched/, ISSUE 19): deficit-weighted round-robin
        # admission over per-tenant lanes.  The intake RequestQueue
        # stays exactly as-is (arrival gating, shed_overflow, queued
        # cancellation); matured pops drain into the scheduler's lanes
        # and the admit loop draws from DWRR order instead of FIFO.
        # Unarmed (tenants=None) the admit path is UNTOUCHED — streams
        # stay byte-identical to pre-v17 output.  Zero device work
        # either way: scheduling is pure host bookkeeping.
        self.sched = None
        if tenants is not None:
            from apex_example_tpu.sched import FairScheduler
            self.sched = FairScheduler(tenants)
        # Tenant stamps on terminal records normally ride with the
        # fair scheduler, but a FIFO control arm (tenancy measured,
        # fairness dropped) still needs them — its stream feeds the
        # same ci_gate --tenant-stream conservation ledger.
        self.tag_tenants = bool(tag_tenants) or self.sched is not None
        # --advertise-prefixes N (ISSUE 19): publish the N hottest
        # prefix chain-key digests + raw reuse counters in replica
        # heartbeats so the fleet router can route on KV CONTENT
        # (policy prefix_affinity).  Opt-in to keep unarmed heartbeats
        # byte-identical.
        if advertise_prefixes < 0:
            raise ValueError(f"advertise_prefixes must be >= 0, got "
                             f"{advertise_prefixes}")
        self.advertise_prefixes = int(advertise_prefixes)

    # ---------------------------------------------------------- intake

    def submit(self, request: Request) -> None:
        self.queue.submit(request)

    def cancel(self, uid: str) -> bool:
        """Cancel a request by uid: a queued one terminates immediately
        (status "cancelled", never admitted); a decoding one is evicted
        mid-flight with its partial tokens.  False if the uid is unknown
        or already terminal.  Call from the engine thread (queued-side
        cancellation alone is thread-safe via the queue's lock)."""
        req = self.queue.cancel(uid)
        if req is None and self.sched is not None:
            req = self.sched.cancel(uid)
        if req is not None:
            self._terminal_unadmitted(req, "cancelled")
            return True
        for i in self.pool.live:
            slot = self.pool.slots[i]
            if slot.request.uid == uid:
                self._terminal_slot(i, "cancelled", time.perf_counter())
                return True
        return False

    # ------------------------------------------------------------ tick

    def step(self) -> bool:
        """One engine tick.  Returns True when a decode step ran (some
        slot was live); False is an idle tick (virtual time still
        advances, so ``arrival_step`` gates keep maturing).

        The tick's phases (tickprof.ENGINE_PHASES) are read once, here
        and in ``_tick``, and feed the profiler's trace, the Tracer and
        the TickProfiler alike.  A tick with nothing live and nothing
        to admit annotates nothing (an idle spin must not flood a
        trace); one that turned away all it had closes after
        ``engine.admit`` alone."""
        busy = bool(self.pool.live or self.queue.ready(self.step_count)
                    or (self.sched is not None and self.sched.pending()))
        with Phases(ENGINE_TICK, "engine.admit", annotate=busy,
                    tick=self.step_count) as ph:
            return self._tick(ph)

    def _tick(self, ph: Phases) -> bool:
        pool = self.pool
        step = self.step_count
        tick1 = step + 1            # 1-based, for --inject-fault kind@tick
        now = ph.at[0]              # re-taken once the tokens are fetched
        if not self.draining:
            self.queue.mature(step)
            # Expire BEFORE evaluating the bound: requests already dead
            # in the queue must not count against max_pending and get a
            # healthy arrival shed over capacity that frees this tick.
            for req in self.queue.expire(step, now):
                self._terminal_unadmitted(req, "timeout")
            shed = self.queue.shed_overflow(step)
            if shed:
                # One arrived-backlog read for the whole batch of shed
                # records, not one O(backlog) scan per victim.
                pending = self.queue.arrived_pending(step)
                for req in shed:
                    self._terminal_unadmitted(req, "shed",
                                              pending=pending)
        # Mid-flight deadline eviction (drain included: "finish or
        # deadline-evict" is the drain contract) — checked at the tick
        # boundary, before the slot consumes another decode step.
        for i in list(pool.live):
            if pool.slots[i].request.expired(step, now):
                self._terminal_slot(i, "timeout", now)
        if not self.draining:
            sched = self.sched
            if sched is not None:
                # Tenancy armed (ISSUE 19): drain every matured intake
                # pop into the per-tenant lanes, sweep lane deadlines
                # the same tick the intake queue sweeps its own, and —
                # once intake is closed — finalize budget-parked heads
                # that can provably never admit (budgets never
                # replenish) so the run loop terminates.
                while True:
                    q_req = self.queue.pop(step)
                    if q_req is None:
                        break
                    sched.enqueue(q_req)
                for req in sched.expire(step, now):
                    self._terminal_unadmitted(req, "timeout")
                if self.queue.drained():
                    for req in sched.reject_overbudget_heads():
                        self._terminal_unadmitted(req, "rejected")
            while pool.free_count:
                req = sched.next() if sched is not None \
                    else self.queue.pop(step)
                if req is None:
                    break
                if not pool.fits(req):
                    # The satellite bugfix (ISSUE 8): a request whose
                    # prompt fills the cache (max_new_for == 0) — or
                    # whose worst-case block need exceeds the whole
                    # arena — used to occupy a slot and terminate with
                    # ZERO generated tokens.  It can never be served
                    # here; reject it first-class at admission.
                    if sched is not None:
                        sched.refund(req)   # unservable ≠ tenant spend
                    self._terminal_unadmitted(req, "rejected")
                    continue
                if not pool.can_admit(req):
                    # Out of KV blocks: deterministic head-of-line
                    # queueing — the head waits at the queue front
                    # until evictions free its worst-case budget (FIFO
                    # preserved; bounded, since every live slot
                    # finishes within max_len ticks).  The scheduler's
                    # push_front also refunds the budget debit.
                    if sched is not None:
                        sched.push_front(req)
                    else:
                        self.queue.push_front(req)
                    break
                pool.admit(req, step)
                if self._tracer is not None:
                    self._rtrace[req.uid] = []   # prefill-chunk buffer
        live = pool.live
        if not live:
            self.idle_ticks += 1
            self.step_count += 1
            if self.fault is not None:
                # Engine-level kinds are defined on TICKS, not decode
                # steps — an idle tick must still fire crash/sigterm/
                # hang, or a drill scheduled between arrival waves would
                # be silently skipped (equality never matches again).
                self.fault.maybe_fire(tick1)
            return False

        ph.set_meta(live=len(live))
        t_admit_end = ph.enter("engine.marshal")
        tracer = self._tracer
        self._spool_ms = 0.0
        with ph.child("engine.build") as build:
            # Chunk width: block_size for interleaved/prefill engines, ONE
            # for a decode-role engine — its slots only ever feed a single
            # token per tick (handoffs arrive pre-filled), so its compiled
            # step drops the prefill lanes and each decode tick pays
            # 1/block_size of the interleaved program's token FLOPs: the
            # decode-tick stall the disaggregation removes.
            C = self.chunk
            # Every array below is a view of ``packed``, the tick's one
            # argument (TickArgs): a row a slot, filled in place.
            packed, f = self.tick_args.blank(pool.num_slots)
            tok, fill, n_new = f["tok"], f["fill"], f["n_new"]
            cow_src, cow_dst = f["cow_src"], f["cow_dst"]
            temps, ks = f["temperature"], f["top_k"]
            drafts: Dict[int, List[int]] = {}
            # self-drafting: per slot, how many of its last lanes are drafts,
            # and the prompt token after a chunk that ends inside its prompt
            # (-1: the module reads the token sampled this tick)
            aux = f.get("aux")
            # The token budget of chunked prefill, for a model whose rows are
            # packed: a chunk of more lanes than the rows' head is granted
            # whole or not at all, oldest admission first; a slot granted
            # nothing has n_new = 0 this tick, stages no write and keeps
            # state and rows bit for bit.  Decoding slots (their drafts
            # with them) and a prompt's last token or two keep their lanes:
            # the rows' head always holds those.
            deferred = frozenset()
            if self._chunk_budget is not None:
                slots = pool.slots
                asking = sorted(
                    (i for i in live
                     if min(C, slots[i].n_prompt - slots[i].cursor)
                     > self._lane_head),
                    key=lambda i: (slots[i].admitted_step,
                                   slots[i].t_admitted))
                deferred = frozenset(asking[self._chunk_budget:])
                self.prefill_chunks_deferred += len(deferred)
                self.prefill_ticks_deferring += bool(deferred)
            for i in live:
                slot = pool.slots[i]
                fill[i] = slot.cursor
                if i in deferred:
                    continue
                # Chunked prefill: up to one block of prompt tokens per
                # tick; decode feeds the single previously-sampled token.
                n = min(C, slot.n_prompt - slot.cursor) if slot.prefilling \
                    else 1
                if self.speculate and not slot.prefilling \
                        and slot.request.temperature == 0:
                    # Speculative decode lanes: the last sampled token plus
                    # up to K host-drafted candidates, verified in the same
                    # dispatch.  Sampled-temperature slots keep the plain
                    # single-lane path — speculation is greedy-only.
                    draft = self._draft_for(slot)
                    drafts[i] = draft
                    n = 1 + len(draft)
                    tok[i, :n] = [slot.tokens[slot.cursor]] + draft
                else:
                    tok[i, :n] = slot.tokens[slot.cursor:slot.cursor + n]
                    if aux is not None and slot.cursor + n < slot.n_prompt:
                        aux[i, 1] = slot.tokens[slot.cursor + n]
                n_new[i] = n
                if aux is not None:
                    aux[i, 0] = len(drafts.get(i, ()))
                # Map/COW the blocks this slot writes this tick (draws from
                # the budget reserved at admission, so it cannot OOM).
                cow_src[i], cow_dst[i] = pool.stage_writes(i, n)
                temps[i] = slot.request.temperature
                ks[i] = slot.request.top_k
            # the tables last: stage_writes mapped this tick's blocks
            f["block_table"][:] = pool.table
            if "ring_table" in f:
                f["ring_table"][:] = pool.ring_table
            build.set_metadata(lanes=int(n_new.sum()))
        # Every hand-off to the runtime the chip waits for, from here to
        # the tokens' return, is a child span (tickprof.ENGINE_HANDOFFS)
        # and is counted as it is made: the key, the one put, the step's
        # call, each fetch.  The key was split while the chip ran the last
        # step's program (below); the first step splits on the spot.
        with ph.child("engine.rng"):
            ahead, self._key_ahead = self._key_ahead, None
            if ahead is None or ahead[0] is not self.rng:
                ahead = self._split_key()
            _, self.rng, key = ahead
        with ph.child("engine.put", arg="packed", bytes=packed.nbytes):
            packed_dev = jnp.asarray(packed)
        ph.enter("engine.enqueue")
        args = (self.params, pool.cache, packed_dev, key)
        if self.mesh is not None:
            # Pallas custom calls are opaque to the SPMD partitioner;
            # pin the XLA reference ops for the sharded trace exactly
            # like generate() under TP (the compiled program is cached,
            # so this costs nothing after the first call).
            from apex_example_tpu.ops import _config as ops_config
            with ops_config.force_xla():
                outs = self._step_fn(*args)
        else:
            outs = self._step_fn(*args)
        # The compiled call has returned (enqueue cost paid) but its
        # outputs may still be computing: what follows is the device's
        # run and the device-to-host copy.  (On CPU jax dispatch is
        # synchronous, so the device time hides in engine.enqueue.)
        ph.enter("engine.sync")
        next_draft = None
        counted = []
        # what the tick reads of the step's outputs, in the order read
        if self.self_draft:
            # [n1, n2, the next draft] a slot in the one array of tokens
            pool.cache, picked, finite, *counted = outs
            reads = {"picked": picked, "finite": finite}
        elif self.speculate:
            pool.cache, nxt, finite, greedy, lanes_ok = outs
            reads = {"lane_greedy": greedy, "lane_finite": lanes_ok,
                     "nxt": nxt, "finite": finite}
        else:
            pool.cache, nxt, finite, *counted = outs
            reads = {"nxt": nxt, "finite": finite}
        # Every device-to-host copy is requested now, so that they arrive
        # together when the program ends and only the first read waits.
        for value in reads.values():
            value.copy_to_host_async()
        # The next step's key, while the chip runs this one's program: the
        # same split of the same carried key, so the chain of keys is the
        # one a split a step gives.  Nothing waits for it and the chip is
        # busy: no hand-off of the count, a span of its own name.
        with ph.child(ENGINE_KEY_AHEAD):
            self._key_ahead = self._split_key()
        got = {}
        for out, value in reads.items():      # the scheduler's host sync
            with ph.child("engine.fetch", out=out, bytes=value.nbytes):
                got[out] = np.asarray(value)
        handoffs = 3 + len(got)     # the key, the put, the call, the reads
        finite = got["finite"]
        if self.self_draft:
            picked = got["picked"]
            lane_greedy, next_draft, nxt = (picked[:, :2], picked[:, 2],
                                            picked[:, 0])
            lane_finite = np.repeat(finite[:, None], 2, axis=1)
        else:
            nxt = got["nxt"]
            lane_greedy = got.get("lane_greedy")
            lane_finite = got.get("lane_finite")
        ph.set_meta(handoffs=handoffs)
        self.runtime_handoffs += handoffs
        now = t_dispatch_end = ph.enter("engine.harvest")

        fault = self.fault
        fail_slot = -1
        if fault is not None:
            if fault.kind == "nan" and fault.due(tick1):
                # Degenerate-sampling drill: what NaN logits do to the
                # sampled-token path, deterministically.  The guard below
                # fails every affected slot instead of feeding the
                # garbage token back into the cache.  Only consumed when
                # some slot actually KEEPS this tick's token — a slot
                # still short of its prompt end after this tick's chunk
                # discards the output, and the drill would be spent with
                # zero effect, so it defers to the first tick that can
                # express it (FaultPlan.due is >=, and the serve path
                # has no resume to double-fire).
                slots = pool.slots
                if any(slots[i].cursor + int(n_new[i])
                       >= slots[i].n_prompt for i in live):
                    fault.take()
                    nxt = np.full_like(nxt, -1)
                    if lane_greedy is not None:
                        # Speculative slots harvest from the verify
                        # lanes, not nxt — poison those too so the
                        # drill expresses under --speculate.
                        lane_greedy = np.full_like(lane_greedy, -1)
            elif fault.kind == "slot_fail" and fault.due(tick1):
                fault.take()
                fail_slot = live[0]

        for i in live:
            slot = pool.slots[i]
            reason = None
            was_prefilling = slot.prefilling
            try:
                if i == fail_slot:
                    raise FaultInjected(
                        f"injected slot_fail at tick {tick1} (slot {i})")
                if i in drafts:
                    # Speculative accept/reject harvest: appends the
                    # accepted draft prefix + the bonus token from the
                    # first mismatching lane, and commits only lanes
                    # with canonical KV — rollback for rejected lanes
                    # is the cursor simply not advancing past them.
                    reason = self._harvest_spec(
                        i, drafts[i], lane_greedy, lane_finite,
                        int(n_new[i]), now)
                else:
                    pool.commit_writes(i, int(n_new[i]))
                    if tracer is not None and was_prefilling \
                            and i not in deferred:
                        # Buffer the chunk window (the tick's dispatch
                        # span) on the request; its tree is emitted
                        # whole, in timestamp order, at terminal time.
                        self._rtrace.setdefault(
                            slot.request.uid, []).append(
                            (t_admit_end, t_dispatch_end, int(n_new[i]),
                             int(cow_dst[i]) >= 0))
                    if slot.prefilling:
                        continue       # prompt chunk fed; output discarded
                    out = int(nxt[i])
                    if not bool(finite[i]):
                        raise SlotFailure(
                            f"non-finite logits in slot {i} — NaN/Inf "
                            "reached the sampled-token path (poisoned "
                            "params or cache row)")
                    if not 0 <= out < self.vocab_size:
                        raise SlotFailure(
                            f"degenerate sampled token {out} (vocab "
                            f"{self.vocab_size}) — poisoned sampling "
                            "path")
                    if slot.n_generated == 0:
                        slot.t_first_token = now
                    slot.tokens.append(out)
                    slot.n_generated += 1
                    self._tokens_out += 1
                    self.tokens_sampled += 1
                    req = slot.request
                    if req.eos_id is not None and out == req.eos_id:
                        reason = "eos"
                    elif slot.n_generated >= pool.max_new_for(req):
                        reason = "length"
            except Exception as e:   # noqa: BLE001 — slot-level isolation
                # One request's failure must not take down the batch: the
                # other slots' caches and host state are untouched, so
                # their token streams continue bit-exact.
                self._terminal_slot(i, "failed", now, error=e)
                continue
            # Terminal transitions run OUTSIDE the isolation try: a sink
            # IO failure inside _finish is an ENGINE-level fault (it
            # would hit every record), and catching it above would both
            # mislabel it a slot failure and re-terminate an
            # already-evicted slot.
            if self.self_draft:
                # what the module drafted for the next tick, for a slot that
                # goes on decoding greedily (any other keeps one lane)
                slot.draft = int(next_draft[i]) if not slot.prefilling \
                    and slot.request.temperature == 0 else None
            if reason is not None:
                self._finish(i, reason, now)
            elif self.role == "prefill" and slot.n_generated == 1:
                # Prefill role: the prompt is fully cached and the
                # FIRST token sampled — ship the KV blocks to a decode
                # worker instead of occupying a prefill slot with
                # 1-token decode ticks.  (A request whose first token
                # already finished it — eos, or a 1-token budget —
                # completed above and never transits.)
                self._handoff_slot(i, now)
        self.compute_steps += 1
        self._occupancy_sum += len(live)
        ph.enter("engine.gauges")
        # Gauge the tick AFTER harvest: what is RESIDENT at the tick
        # boundary (a finished slot's blocks were just unref'd — the
        # reclamation the dense layout could never express).
        live_slots = len(self.pool.live)
        kv_live = self.pool.kv_bytes_live()
        blocks_live = self.pool.blocks_live()
        self._occ_hist.observe(live_slots)
        self._kv_hist.observe(kv_live)
        self._blk_hist.observe(blocks_live)
        self._committed_hist.observe(self.pool.kv_bytes_committed())
        if counted:
            if self.pool.window is not None:
                # what the window arena holds of each slot beside what the
                # full arena does, and the blocks handed back this tick
                # (every tick, 0 included, like the model's own)
                released = self.pool.window_blocks_released
                row = lambda v: np.asarray(v, np.int32).reshape(1, -1)
                counted[0] = dict(
                    counted[0],
                    window_blocks_released=row(
                        released - self._window_released_seen),
                    window_tokens_held=row(self.pool.window_tokens_held()),
                    full_tokens_held=row(
                        [0 if s is None else s.cursor
                         for s in self.pool.slots]))
                self._window_released_seen = released
            if self._chunk_budget is not None:
                # beside what the model counted, on the host already
                # (every tick, like the model's own: a reader takes a
                # counter some ticks lack for one the window lacks)
                counted[0] = dict(counted[0], prefill_chunks_deferred=np
                                  .full((1, 1), len(deferred), np.int32))
            self.counter_log.append((now, counted[0]))
        if self.registry is not None:
            self.registry.gauge("serve.slots_live").set(live_slots)
            self.registry.gauge("serve.kv_bytes_live").set(kv_live)
            self.registry.gauge("serve.blocks_live").set(blocks_live)
        if self.slo is not None:
            self.slo.observe_tick(live_slots=live_slots,
                                  num_slots=self.pool.num_slots,
                                  blocks_live=blocks_live,
                                  kv_bytes_live=kv_live)
        t_end = ph.close()
        self.step_count += 1
        # The records of the two host-side consumers, written after the
        # tick has closed so that they never pollute its measurement.
        if tracer is not None:
            # One "tick" B/E pair on the engine row with its admit /
            # dispatch (marshal + compiled step + host sync: what the
            # tick paid for device work) / harvest children; idle ticks
            # emit nothing — a wall-clock producer's idle spin must not
            # flood the stream.
            sid = tracer.begin("tick", tid="engine", ts=ph.at[0],
                               cat="tick",
                               args={"tick": step, "live": len(live)})
            for name, t0, t1, meta in (
                    ("admit", ph.at[0], t_admit_end, None),
                    ("dispatch", t_admit_end, t_dispatch_end,
                     {"lanes": int(n_new.sum())}),
                    ("harvest", t_dispatch_end, t_end,
                     {"live": live_slots, "blocks": blocks_live})):
                tracer.complete(name, t0, t1 - t0, tid="engine",
                                cat="tick", parent_id=sid, args=meta)
            tracer.end("tick", tid="engine", ts=t_end)
        if self.tickprof is not None:
            # Contiguous boundaries telescope: the phases sum to the
            # wall EXACTLY (modulo float rounding), which is what
            # perf_ledger's 1% consistency gate verifies.
            folded = {p: 0.0 for p in ENGINE_PHASES.values()}
            for name, into in ENGINE_PHASES.items():
                folded[into] += ph.ms(name)
            folded["harvest"] -= self._spool_ms
            self.tickprof.observe_tick(
                ph.at[0], (t_end - ph.at[0]) * 1e3,
                spool_io=self._spool_ms, **folded)
        if fault is not None:
            # crash/sigterm/hang fire AFTER the tick's harvest (matching
            # the training loops: forensics hold the last good tick).
            fault.maybe_fire(tick1)
        return True

    def _split_key(self):
        """One split of the carried key, with the key it was made from:
        ``(self.rng, the key to carry on, the step's key)``.  The caller
        commits it (``_tick``'s ``engine.rng``) or holds it aside."""
        return (self.rng, *jax.random.split(self.rng))

    # ------------------------------------------------------ speculation

    def _draft_for(self, slot) -> List[int]:
        """Ask the proposer for this tick's draft, clamped so staged KV
        writes can never outrun the slot's logical budget: at most K
        lanes, at most chunk-1 (the program's spare lane count), and at
        most remaining-1 — the +1 bonus token of a fully-accepted draft
        must still fit under max_new_for.  A proposer returning junk
        (out-of-vocab ids) is truncated at the first bad token; draft
        QUALITY can only cost throughput, never correctness."""
        req = slot.request
        remaining = self.pool.max_new_for(req) - slot.n_generated
        k = min(self.speculate, remaining - 1, self.chunk - 1)
        if k <= 0:
            return []
        if self.self_draft:
            draft = [] if slot.draft is None else [slot.draft]
        else:
            draft = self.proposer.propose(req.uid, req.prompt,
                                          slot.tokens[slot.n_prompt:], k)
        out: List[int] = []
        for t in list(draft)[:k]:
            t = int(t)
            if not 0 <= t < self.vocab_size:
                break
            out.append(t)
        return out

    def _harvest_spec(self, i: int, draft: List[int], lane_greedy,
                      lane_finite, n: int, now: float) -> Optional[str]:
        """Accept/reject harvest for one speculative slot.  The fed
        lanes were [last_sampled, d0..d_{k-1}]; lane j's logits
        condition on everything up to and including lane j, so
        lane_greedy[j] is the model's greedy choice for the position
        draft[j] claims.  Accept the longest matching prefix d0..d_{m-1}
        plus the bonus token lane_greedy[m] (the model's own pick at the
        first mismatch — or after a fully-accepted draft), walking
        eos/length exactly as m+1 one-token ticks would have.  Commit
        1 + kept-draft lanes: the bonus token has no KV yet (it is next
        tick's lane 0), and rejected lanes' stale rows sit beyond the
        cursor where the live mask hides them until overwritten."""
        pool = self.pool
        slot = pool.slots[i]
        req = slot.request
        lanes = lane_greedy[i]
        if not bool(lane_finite[i, :n].all()):
            raise SlotFailure(
                f"non-finite logits in slot {i} — NaN/Inf reached a "
                "speculative verify lane (poisoned params or cache "
                "row)")
        m = 0
        while m < len(draft) and int(lanes[m]) == draft[m]:
            m += 1
        bonus = int(lanes[m])
        if not 0 <= bonus < self.vocab_size:
            raise SlotFailure(
                f"degenerate greedy token {bonus} (vocab "
                f"{self.vocab_size}) — poisoned sampling path")
        self.tokens_drafted += len(draft)
        if self.self_draft:
            # each draft with the output position it claimed and its verdict
            slot.drafts.extend((slot.n_generated + j, d, j < m)
                               for j, d in enumerate(draft))
        if slot.n_generated == 0:
            slot.t_first_token = now
        reason = None
        n_keep = 0
        budget = pool.max_new_for(req)
        for pos, t in enumerate(draft[:m] + [bonus]):
            slot.tokens.append(t)
            slot.n_generated += 1
            self._tokens_out += 1
            n_keep += 1
            if pos < m:
                self.tokens_accepted += 1
            else:
                self.tokens_sampled += 1
            if req.eos_id is not None and t == req.eos_id:
                reason = "eos"
                break
            if slot.n_generated >= budget:
                reason = "length"
                break
        pool.commit_writes(i, 1 + min(n_keep, m))
        return reason

    # ------------------------------------------------------- terminals

    def _finish(self, idx: int, reason: str, now: float) -> None:
        self._evict_terminal(idx, reason, "ok", now)

    def _terminal_slot(self, idx: int, status: str, now: float,
                       error: Optional[BaseException] = None) -> None:
        """Evict a live slot with a non-ok status (timeout / cancelled /
        failed): partial tokens kept, ``request_failed`` emitted."""
        self._evict_terminal(idx, status, status, now, error=error)

    def _evict_terminal(self, idx: int, finish_reason: str, status: str,
                        now: float,
                        error: Optional[BaseException] = None) -> None:
        """The one terminal sequence for an admitted request: build the
        Completion from the slot, account it, evict, emit the record —
        ok and non-ok paths share it so the accounting can never
        desynchronize."""
        slot = self.pool.slots[idx]
        digest = None
        if error is not None:
            tb = traceback.format_exception(type(error), error,
                                            error.__traceback__)
            digest = f"{type(error).__name__}: {error}"
            tail = "".join(tb)[-2000:]
            digest = f"{digest}\n{tail}" if tail else digest
        comp = Completion(
            request=slot.request,
            tokens=slot.tokens[slot.n_prompt:],
            finish_reason=finish_reason,
            slot=idx,
            admitted_step=slot.admitted_step,
            finished_step=self.step_count,
            t_admitted=slot.t_admitted,
            t_first_token=slot.t_first_token,
            t_finish=now,
            status=status,
            error=digest,
            drafts=slot.drafts)
        self.completions.append(comp)
        self.counts[status] += 1
        if self.slo is not None and status not in ("handoff", "migrated"):
            # A handoff/migration continues elsewhere — the destination
            # owns its terminal; scoring it here would double-count the
            # uid.
            self.slo.observe_request(
                status,
                ttft_ms=None if comp.ttft_s is None
                else comp.ttft_s * 1e3,
                tpot_ms=None if comp.tpot_s is None
                else comp.tpot_s * 1e3,
                queue_wait_ms=None if comp.queue_wait_s is None
                else comp.queue_wait_s * 1e3)
        self._trace_request(comp, slot_blocks=slot.n_mapped)
        self.pool.evict(idx)
        if self.sink is not None and status not in ("handoff", "migrated"):
            # A handoff's record is the kv_handoff _handoff_slot wrote,
            # a migration's the kv_migration extract_live wrote (the
            # request is continuing elsewhere, not failing here).
            record = request_complete_record if status == "ok" \
                else request_failed_record
            self.sink.write(record(comp, self.run_id,
                                   with_tenant=self.tag_tenants))

    def _terminal_unadmitted(self, req: Request, status: str,
                             pending: Optional[int] = None) -> None:
        """Terminate a never-admitted request: shed at arrival, expired
        in the queue, cancelled while queued, rejected as unservable at
        admission, or drained for requeueing (the drain record carries
        the requeued ids; shed gets its own record, with ``pending`` the
        tick's post-shed arrived backlog — computed once by the caller;
        timeout/cancelled/rejected ride ``request_failed``)."""
        now = time.perf_counter()
        comp = Completion(
            request=req, tokens=[], finish_reason=status, slot=-1,
            admitted_step=-1, finished_step=self.step_count,
            t_admitted=None, t_first_token=None, t_finish=now,
            status=status)
        self.completions.append(comp)
        self.counts[status] += 1
        if self.slo is not None:
            # Never admitted: no latencies to fold — still scored
            # (bad unless drained) so overload shows up in the burn.
            self.slo.observe_request(status)
        self._trace_request(comp)
        if self.sink is None:
            return
        if status == "shed":
            rec: Dict[str, Any] = {
                "record": "shed", "time": _wall(), "request_id": req.uid,
                "reason": "queue_full", "step": self.step_count,
                "pending": pending if pending is not None
                else self.queue.arrived_pending(self.step_count)}
            if self.queue.max_pending is not None:
                rec["max_pending"] = self.queue.max_pending
            if self.tag_tenants:
                rec["tenant"] = getattr(req, "tenant", "default")
            if self.run_id:
                rec["run_id"] = self.run_id
            self.sink.write(rec)
        elif status in ("timeout", "cancelled", "failed", "rejected"):
            self.sink.write(request_failed_record(
                comp, self.run_id,
                with_tenant=self.tag_tenants))
        # "drained": accounted by the serve_drain record, not per-request.

    # --------------------------------------------------------- handoff

    def _handoff_slot(self, idx: int, now: float) -> None:
        """Prefill-role terminal: gather slot ``idx``'s KV blocks into a
        :class:`~apex_example_tpu.serve.disagg.KvHandoff` (deep copy —
        COW-shared prefix blocks ship as payload bytes, never as
        references), emit the ``kv_handoff`` record (direction "out"),
        evict the slot with status "handoff" and push the payload into
        the transport.  Runs OUTSIDE the slot-isolation try like every
        terminal transition."""
        from apex_example_tpu.serve.disagg import KvHandoff
        slot = self.pool.slots[idx]
        req = slot.request
        fill, n_blocks, payload = self.pool.extract_blocks(idx)
        payload_bytes = sum(int(a.nbytes) for a in payload.values())
        # The REAL first-token latency is measurable only here, where
        # the first token was sampled — the decode side's timestamps
        # live in its own clock domain, so they ride the out record.
        ttft_ms = round((slot.t_first_token - req.t_arrival) * 1e3, 3) \
            if slot.t_first_token is not None else None
        queue_ms = round((slot.t_admitted - req.t_arrival) * 1e3, 3)
        handoff = KvHandoff(
            uid=req.uid, request=req, tokens=[int(t) for t in slot.tokens],
            fill=fill, block_size=self.pool.block_size,
            kv_dtype=self.pool.kv_dtype, payload=payload,
            payload_bytes=payload_bytes, t_out_wall=_wall(),
            src=self.role, ttft_ms=ttft_ms, queue_wait_ms=queue_ms)
        self._handoff_bytes += payload_bytes
        if self.sink is not None:
            rec: Dict[str, Any] = {
                "record": "kv_handoff", "time": _wall(),
                "request_id": req.uid, "direction": "out",
                "fill": fill, "blocks": n_blocks,
                "payload_bytes": payload_bytes,
                "kv_dtype": self.pool.kv_dtype,
                "prompt_tokens": len(req.prompt),
                "first_token": int(slot.tokens[-1]),
                "queue_wait_ms": queue_ms,
                "src": self.role}
            if ttft_ms is not None:
                rec["ttft_ms"] = ttft_ms
            if self.run_id:
                rec["run_id"] = self.run_id
            self.sink.write(rec)
        self._evict_terminal(idx, "handoff", "handoff", now)
        if self.tickprof is not None:
            # Spool IO attribution: the sink call is filesystem work
            # (serve/disagg.py spool write + fsync), not scheduler
            # cost — measured here, subtracted from harvest.
            t0 = time.perf_counter()
            self.handoff_sink(handoff)
            self._spool_ms += (time.perf_counter() - t0) * 1e3
        else:
            self.handoff_sink(handoff)

    def admit_handoff(self, handoff) -> bool:
        """Decode-role intake: admit a prefill worker's KV handoff into
        a slot, scattering its block payload into this engine's arena
        and resuming at ``cursor == fill`` with the first token already
        sampled.  Returns False — with NO state left behind — when a
        free slot or the worst-case block budget is missing right now:
        the caller requeues the same handoff deterministically and
        retries after evictions free capacity.  A handoff this engine
        could NEVER serve terminates first-class as "rejected" and
        returns True (consumed).  A handoff whose uid this engine
        ALREADY admitted — a redelivery of a claim that was never
        acked, or a duplicate delivery — is consumed idempotently: a
        ``kv_handoff`` record with ``duplicate: true`` lands, nothing
        is scattered, and True tells the caller to ack it."""
        if getattr(handoff, "kind", "handoff") == "migration":
            # Live-migration payloads (ISSUE 20) ride the same spool
            # and the same drive loops; dispatch here so every existing
            # poll -> admit -> ack caller works unchanged.
            return self.admit_migrated(handoff)
        req = handoff.request
        if req.uid in self.handoff_seen:
            # The ack-crash window closes here: admitted before, so the
            # payload (and possibly the finished request) already lives
            # in this engine — ack the redelivery, never scatter twice.
            self.handoff_duplicates += 1
            if self.sink is not None:
                rec: Dict[str, Any] = {
                    "record": "kv_handoff", "time": _wall(),
                    "request_id": req.uid, "direction": "in",
                    "fill": handoff.fill, "blocks": 0,
                    "payload_bytes": handoff.payload_bytes,
                    "kv_dtype": self.pool.kv_dtype,
                    "duplicate": True,
                    "redelivered": int(handoff.redelivered),
                    "dst": self.role}
                if self.run_id:
                    rec["run_id"] = self.run_id
                self.sink.write(rec)
            return True
        if self.draining:
            return False             # drain stopped admission (requeue)
        if handoff.block_size != self.pool.block_size:
            raise ValueError(
                f"handoff block_size {handoff.block_size} vs engine "
                f"{self.pool.block_size} — prefill and decode roles "
                "must share the arena geometry")
        if not self.pool.fits(req):
            self._terminal_unadmitted(req, "rejected")
            return True
        if not self.pool.can_admit_prefilled(req):
            if not handoff.requeued:
                # Counted once per handoff (an episode, not a retry
                # tally — the caller retries every tick and the wait
                # itself shows up in handoff_ms).
                handoff.requeued = 1
                self.handoff_requeued += 1
            return False
        now = time.perf_counter()
        idx = self.pool.admit_prefilled(req, self.step_count,
                                        handoff.fill, handoff.payload,
                                        handoff.tokens)
        slot = self.pool.slots[idx]
        slot.n_generated = len(handoff.tokens) - len(req.prompt)
        slot.t_first_token = now
        self.handoffs_in += 1
        self.handoff_seen.add(req.uid)
        if handoff.redelivered:
            self.handoff_redelivered.add(req.uid)
        self._handoff_bytes += handoff.payload_bytes
        transit_ms = max((_wall() - handoff.t_out_wall) * 1e3, 0.0)
        self._handoff_ms.append(transit_ms)
        if self._tracer is not None:
            self._rtrace[req.uid] = []
        if self.sink is not None:
            rec = {
                "record": "kv_handoff", "time": _wall(),
                "request_id": req.uid, "direction": "in",
                "fill": handoff.fill, "blocks": slot.n_mapped,
                "payload_bytes": handoff.payload_bytes,
                "kv_dtype": self.pool.kv_dtype,
                "prompt_tokens": len(req.prompt),
                "first_token": int(handoff.tokens[-1]),
                "handoff_ms": round(transit_ms, 3),
                "requeued": handoff.requeued,
                "dst": self.role}
            if handoff.redelivered:
                rec["redelivered"] = int(handoff.redelivered)
            if handoff.src:
                rec["src"] = handoff.src
            if self.run_id:
                rec["run_id"] = self.run_id
            self.sink.write(rec)
        return True

    # ------------------------------------------------------- migration

    def extract_live(self, uid: str):
        """Snapshot a MID-FLIGHT request into a migration payload
        (ISSUE 20): its arena blocks (storage-dtype-exact via
        extract_blocks — int8 payload + scales ship as-is), cursor,
        full token list, and sampler state (temperature / top_k ride
        the Request itself), evicting the slot with status "migrated"
        (outside the availability denominator — the destination owns
        the terminal).  Returns the :class:`KvHandoff` with
        ``kind="migration"`` for the caller to ship, or None when the
        uid holds no live slot.  Works at any point in the lifecycle:
        mid-prefill (fill < prompt length, zero generated tokens —
        the destination resumes the chunked prefill) as well as deep
        into decode.  ``admit_migrated`` resumes it token-identically
        under greedy sampling (temperature 0): the arena rows are
        bit-exact copies and argmax needs no RNG; sampled-temperature
        requests resume with the destination's stream."""
        for i in self.pool.live:
            if self.pool.slots[i].request.uid == uid:
                return self._migrate_slot(i, time.perf_counter())
        return None

    def _migrate_slot(self, idx: int, now: float):
        """Build one live slot's migration payload and evict it with
        status "migrated" — the live-migration counterpart of
        _handoff_slot.  Returns the payload; the CALLER ships it (drain
        passes its ``migrate`` callable; router-driven rebalance pushes
        straight into a transport)."""
        from apex_example_tpu.serve.disagg import KvHandoff
        pool = self.pool
        slot = pool.slots[idx]
        req = slot.request
        BS = pool.block_size
        # The satellite bugfix (ISSUE 20): under --speculate,
        # stage_writes maps blocks for draft lanes the accept decision
        # then REJECTS — their rows are unverified garbage past the
        # committed cursor, and the cursor-rollback invariant (stale
        # rows hidden by the live mask until overwritten) only holds
        # inside this engine.  Ship exactly the blocks the cursor
        # covers; admit_prefilled allocates ceil(fill/BS) on the
        # destination and rejects a longer payload as malformed.
        n_ship = (slot.cursor + BS - 1) // BS
        fill, _, payload = pool.extract_blocks(idx, n_ship)
        # Same invariant on the token list: everything past tokens[fill]
        # (the one pending next-feed token of a decoding slot) was never
        # verified against committed KV and must not resume elsewhere.
        tokens = [int(t) for t in slot.tokens]
        if not slot.prefilling:
            tokens = tokens[:fill + 1]
        payload_bytes = sum(int(a.nbytes) for a in payload.values())
        handoff = KvHandoff(
            uid=req.uid, request=req, tokens=tokens,
            fill=fill, block_size=BS,
            kv_dtype=pool.kv_dtype, payload=payload,
            payload_bytes=payload_bytes, t_out_wall=_wall(),
            src=self.role, kind="migration")
        self._migration_bytes += payload_bytes
        if self.sink is not None:
            rec: Dict[str, Any] = {
                "record": "kv_migration", "time": _wall(),
                "request_id": req.uid, "direction": "out",
                "fill": fill, "blocks": n_ship,
                "payload_bytes": payload_bytes,
                "kv_dtype": pool.kv_dtype,
                "prompt_tokens": len(req.prompt),
                "tokens_generated": slot.n_generated,
                "src": self.role}
            if self.tag_tenants:
                rec["tenant"] = getattr(req, "tenant", "default")
            if self.run_id:
                rec["run_id"] = self.run_id
            self.sink.write(rec)
        self._evict_terminal(idx, "migrated", "migrated", now)
        # The uid has LEFT this engine: a future payload for it (the
        # rebalance ping-pong, A -> B -> A) is a NEW incarnation, not a
        # duplicate delivery — suppression must forget it, or the
        # second visit would be acked-and-dropped (a lost request).
        self.handoff_seen.discard(req.uid)
        self.migration_redelivered.discard(req.uid)
        return handoff

    def admit_migrated(self, handoff) -> bool:
        """Resume a migrated mid-flight request (ISSUE 20): the intake
        twin of admit_handoff with the same contract — False with NO
        state left behind when a slot or the block budget is missing
        (the caller requeues and retries), True when consumed (admitted,
        rejected-as-unservable, or suppressed as a duplicate of a uid
        this engine already admitted).  Differences from the one-shot
        handoff path: the slot resumes with ``n_generated`` tokens
        already emitted (possibly zero — a mid-prefill migration keeps
        prefilling here), ``t_first_token`` is stamped only when the
        first token truly happened elsewhere, and the stream records
        are ``kv_migration``."""
        req = handoff.request
        if req.uid in self.handoff_seen:
            self.migration_duplicates += 1
            if self.sink is not None:
                rec: Dict[str, Any] = {
                    "record": "kv_migration", "time": _wall(),
                    "request_id": req.uid, "direction": "in",
                    "fill": handoff.fill, "blocks": 0,
                    "payload_bytes": handoff.payload_bytes,
                    "kv_dtype": self.pool.kv_dtype,
                    "duplicate": True,
                    "redelivered": int(handoff.redelivered),
                    "dst": self.role}
                if self.run_id:
                    rec["run_id"] = self.run_id
                self.sink.write(rec)
            return True
        if self.draining:
            return False             # drain stopped admission (requeue)
        if handoff.block_size != self.pool.block_size:
            raise ValueError(
                f"migration block_size {handoff.block_size} vs engine "
                f"{self.pool.block_size} — source and destination must "
                "share the arena geometry")
        if not self.pool.fits(req):
            self._terminal_unadmitted(req, "rejected")
            return True
        if not self.pool.can_admit_prefilled(req):
            if not handoff.requeued:
                handoff.requeued = 1
                self.migration_requeued += 1
            return False
        now = time.perf_counter()
        idx = self.pool.admit_prefilled(req, self.step_count,
                                        handoff.fill, handoff.payload,
                                        handoff.tokens)
        slot = self.pool.slots[idx]
        slot.n_generated = len(handoff.tokens) - len(req.prompt)
        if slot.n_generated > 0:
            # The first token was sampled on the SOURCE; stamping it at
            # admission keeps TTFT finite in this engine's clock domain
            # (the cross-domain truth rides the out record).  A
            # mid-prefill migration leaves it None — the first token
            # genuinely happens here.
            slot.t_first_token = now
        self.migrations_in += 1
        self.handoff_seen.add(req.uid)
        if handoff.redelivered:
            self.migration_redelivered.add(req.uid)
        self._migration_bytes += handoff.payload_bytes
        transit_ms = max((_wall() - handoff.t_out_wall) * 1e3, 0.0)
        self._migration_ms.append(transit_ms)
        if self._tracer is not None:
            self._rtrace[req.uid] = []
        if self.sink is not None:
            rec = {
                "record": "kv_migration", "time": _wall(),
                "request_id": req.uid, "direction": "in",
                "fill": handoff.fill, "blocks": slot.n_mapped,
                "payload_bytes": handoff.payload_bytes,
                "kv_dtype": self.pool.kv_dtype,
                "prompt_tokens": len(req.prompt),
                "tokens_generated": slot.n_generated,
                "migration_ms": round(transit_ms, 3),
                "requeued": handoff.requeued,
                "dst": self.role}
            if handoff.redelivered:
                rec["redelivered"] = int(handoff.redelivered)
            if handoff.src:
                rec["src"] = handoff.src
            if self.tag_tenants:
                rec["tenant"] = getattr(req, "tenant", "default")
            if self.run_id:
                rec["run_id"] = self.run_id
            self.sink.write(rec)
        return True

    # ----------------------------------------------------------- trace

    def _trace_request(self, comp: Completion,
                       slot_blocks: int = 0) -> None:
        """Emit one terminated request's lifecycle span tree (--trace):
        a root "request" span on its own ``req/<uid>`` row, with
        submit-handoff / queued / per-chunk prefill / decode child
        spans and first_token + terminal-status instants — every
        timestamp a ``perf_counter`` the request already accumulated on
        its way through, emitted in timestamp order at terminal time
        (obs/trace.py module docstring on why X-after-the-fact)."""
        tracer = self._tracer
        if tracer is None:
            return
        req = comp.request
        chunks = self._rtrace.pop(req.uid, [])
        t_arr = req.t_arrival
        t_sub = req.t_submit
        start = t_sub if t_sub is not None and t_sub < t_arr else t_arr
        tid = f"req/{req.uid}"
        args: Dict[str, Any] = {
            "request_id": req.uid, "status": comp.status,
            "prompt_tokens": len(req.prompt),
            "output_tokens": len(comp.tokens)}
        if comp.slot >= 0:
            args["slot"] = comp.slot
            args["admitted_tick"] = comp.admitted_step
            args["blocks"] = slot_blocks
            args["cow_copies"] = sum(1 for c in chunks if c[3])
        root = tracer.complete("request", start, comp.t_finish - start,
                               tid=tid, cat="request", args=args)
        if t_sub is not None and t_arr > t_sub:
            # loadgen -> queue handoff (Request.t_submit): client-side
            # latency the queue-wait metric must not absorb.
            tracer.complete("submit", t_sub, t_arr - t_sub, tid=tid,
                            cat="request", parent_id=root)
        q_end = comp.t_admitted if comp.t_admitted is not None \
            else comp.t_finish
        tracer.complete("queued", t_arr, q_end - t_arr, tid=tid,
                        cat="request", parent_id=root)
        for t0, t1, n_toks, cow in chunks:
            tracer.complete("prefill", t0, t1 - t0, tid=tid,
                            cat="request", parent_id=root,
                            args={"tokens": n_toks, "cow": cow})
        if comp.t_first_token is not None:
            tracer.instant("first_token", ts=comp.t_first_token,
                           tid=tid, parent_id=root)
            tracer.complete("decode", comp.t_first_token,
                            comp.t_finish - comp.t_first_token,
                            tid=tid, cat="request", parent_id=root)
        tracer.instant(comp.status, ts=comp.t_finish, tid=tid,
                       parent_id=root, args={"tick": comp.finished_step})

    # ------------------------------------------------------------ loop

    def run(self, max_steps: Optional[int] = None,
            idle_wait_s: float = 0.0, stop=None,
            on_tick=None) -> List[Completion]:
        """Drive ticks until the queue is drained and every slot is free
        (or ``max_steps`` ticks, or ``stop()`` — a callable the caller
        flips on SIGTERM to hand control to ``drain()``).
        ``idle_wait_s`` throttles idle spins when a producer thread
        feeds the queue in wall-clock time.  ``on_tick(engine)``, when
        given, runs after every tick (idle ticks included) — the
        replica-mode hook serve.py uses to flush its completion outbox
        and heartbeat without the engine knowing about either."""
        while max_steps is None or self.step_count < max_steps:
            if stop is not None and stop():
                break
            if self.work_drained() and not self.pool.any_live():
                break
            ran = self.step()
            if on_tick is not None:
                on_tick(self)
            if not ran and idle_wait_s:
                # v15 idle accounting: the sleep the summary used to
                # lose — idle_wait_ms measures what was actually slept
                # (the scheduler may overshoot idle_wait_s).
                t0 = time.perf_counter()
                time.sleep(idle_wait_s)
                self.idle_wait_ms += (time.perf_counter() - t0) * 1e3
        return self.completions

    # ----------------------------------------------------------- drain

    def drain(self, signal_name: str = "SIGTERM",
              migrate=None) -> Dict[str, Any]:
        """Graceful drain: stop admission, hand every still-queued
        request back with status "drained" (requeue-able elsewhere),
        then keep ticking until the in-flight slots finish or deadline-
        evict.  Returns (and emits, with a sink) the ``serve_drain``
        record; the caller then writes the normal, un-aborted
        ``serve_summary`` and exits ``EX_TEMPFAIL``.

        ``migrate`` (ISSUE 20) turns drain into drain-WITHOUT-eviction:
        a callable (typically ``transport.send``) each live slot's
        extract_live payload is pushed through instead of ticking the
        slot to completion — in-flight work leaves as "migrated"
        (resumed token-identically on a peer), zero ticks spent, zero
        deadline evictions, and the serve_drain record carries the
        ``migrated`` count."""
        self.draining = True
        drain_step = self.step_count
        if self._tracer is not None:
            # B/E (not X): the drain-phase ticks nest inside it on the
            # engine row, and a drain always runs to completion within
            # the bounded cap below, so the pair is balanced.
            self._tracer.begin("drain", tid="engine", cat="tick",
                               args={"signal": str(signal_name),
                                     "tick": drain_step})
        before = dict(self.counts)
        requeued = []
        if self.sched is not None:
            # Lane-parked requests drained the intake earlier, so they
            # arrived first — requeue them ahead of the intake backlog.
            requeued.extend(self.sched.drain())
        requeued.extend(self.queue.drain())
        for req in requeued:
            self._terminal_unadmitted(req, "drained")
        in_flight = len(self.pool.live)
        if migrate is not None:
            # Drain-without-eviction: ship every live slot MID-FLIGHT.
            # The loop below then sees no live slots — a migrating
            # drain spends zero decode ticks and can never deadline-
            # evict what it was asked to preserve.
            now = time.perf_counter()
            for i in list(self.pool.live):
                migrate(self._migrate_slot(i, now))
        # Bounded by construction: every live slot finishes within
        # max_len ticks (length cap) — the slack covers prefill already
        # under way.  A wedge here would be a bug, not load.
        cap = self.step_count + self.pool.max_len + 2
        while self.pool.any_live() and self.step_count < cap:
            self.step()
        rec: Dict[str, Any] = {
            "record": "serve_drain",
            "time": _wall(),
            "signal": str(signal_name),
            "step": drain_step,
            "in_flight": in_flight,
            "completed": self.counts["ok"] - before["ok"],
            "evicted": (self.counts["timeout"] - before["timeout"])
            + (self.counts["failed"] - before["failed"]),
            "requeued": len(requeued),
            "requeued_ids": [r.uid for r in requeued],
        }
        if migrate is not None:
            # Gated on the migrating drain (v18): a classic drain's
            # record stays byte-identical to pre-v18 output.
            rec["migrated"] = self.counts["migrated"] \
                - before["migrated"]
        if self.run_id:
            rec["run_id"] = self.run_id
        if self._tracer is not None:
            self._tracer.end("drain", tid="engine",
                             args={"completed": rec["completed"],
                                   "evicted": rec["evicted"],
                                   "requeued": rec["requeued"]})
        if self.sink is not None:
            self.sink.write(rec)
        return rec

    # --------------------------------------------------------- metrics

    def summary_record(self) -> Dict[str, Any]:
        """The ``serve_summary`` for everything terminated so far (the
        caller writes it to the sink and closes).  Schema v5 added
        per-status counts + the availability ratio (ok / every terminal
        status the server owned — drained requests are requeued
        elsewhere, so they sit outside the denominator); v7 adds the
        block-pool gauges (blocks_live / kv_bytes_committed /
        prefix_hit_rate / cow_copies) and makes ``kv_waste_pct``
        block-accurate: held-block bytes minus logically-live bytes,
        per compute tick — the dense layout's fixed full-page
        reservation measured ~92% here."""
        duration = time.perf_counter() - self._t0
        comps = self.completions
        ok = [c for c in comps if c.status == "ok"]
        # Drained, handed-off AND migrated requests continue elsewhere —
        # all three sit outside the availability denominator (v12/v18).
        owned = len(comps) - self.counts["drained"] \
            - self.counts["handoff"] - self.counts["migrated"]
        pool = self.pool
        rec: Dict[str, Any] = {
            "record": "serve_summary",
            "time": _wall(),
            "requests": len(comps),
            "output_tokens": self._tokens_out,
            "tokens_per_sec": round(self._tokens_out / max(duration, 1e-9),
                                    1),
            "steps": self.step_count,
            "compute_steps": self.compute_steps,
            "slots": pool.num_slots,
            "max_len": pool.max_len,
            "block_size": pool.block_size,
            "blocks_total": pool.num_blocks,
            "duration_s": round(duration, 3),
            "completed": self.counts["ok"],
            "timed_out": self.counts["timeout"],
            "shed": self.counts["shed"],
            "cancelled": self.counts["cancelled"],
            "failed": self.counts["failed"],
            "drained": self.counts["drained"],
            "rejected": self.counts["rejected"],
            "prefix_hit_rate": round(pool.prefix_hit_rate(), 4),
            "cow_copies": pool.cow_copies,
            "availability": round(self.counts["ok"] / owned, 3)
            if owned else 1.0,
            # v11 (ISSUE 13): the precision story — arena payload dtype,
            # weight storage mode, and the dtype-accurate vs
            # bf16-equivalent per-token costs the QUANT report line and
            # the ci_gate --quant-stream compression floor key on.
            "kv_dtype": pool.kv_dtype,
            "weight_dtype": _weight_dtype_name(self.weight_quant,
                                               self.params),
            "kv_bytes_per_token": pool.kv_bytes_per_token(),
            "kv_bytes_per_token_bf16": pool.kv_bytes_per_token_bf16(),
            # v12 (ISSUE 14): which part of the disaggregated topology
            # this engine played, and under which mesh.
            "role": self.role,
        }
        if self.mesh is not None:
            rec["mesh"] = f"data={self.dp},model={self.tp}"
            rec["dp"] = self.dp
            rec["tp"] = self.tp
        if self.counts["handoff"]:
            rec["handoffs_out"] = self.counts["handoff"]
        if self.handoffs_in:
            rec["handoffs_in"] = self.handoffs_in
        if self.handoff_requeued:
            rec["handoff_requeued"] = self.handoff_requeued
        if self.handoff_duplicates:
            rec["handoff_duplicates"] = self.handoff_duplicates
        if self.handoff_redelivered:
            rec["handoff_redelivered"] = len(self.handoff_redelivered)
        if self._handoff_bytes:
            rec["handoff_bytes"] = self._handoff_bytes
        if self._handoff_ms:
            rec["handoff_ms"] = _pct_dict(self._handoff_ms)
        # v18 (ISSUE 20): the live-migration ledger — every field gated
        # on actual migration traffic, so a migration-free stream stays
        # byte-identical to pre-v18 output.
        if self.counts["migrated"]:
            rec["migrations_out"] = self.counts["migrated"]
        if self.migrations_in:
            rec["migrations_in"] = self.migrations_in
        if self.migration_requeued:
            rec["migration_requeued"] = self.migration_requeued
        if self.migration_duplicates:
            rec["migration_duplicates"] = self.migration_duplicates
        if self.migration_redelivered:
            rec["migration_redelivered"] = len(self.migration_redelivered)
        if self._migration_bytes:
            rec["migration_bytes"] = self._migration_bytes
        if self._migration_ms:
            rec["migration_ms"] = _pct_dict(self._migration_ms)
        # Lane packing's token budget: chunks left waiting a tick because
        # the packed rows were spent, and the ticks that left any.  Gated
        # on its having happened, like the ledgers above.
        if self.prefill_chunks_deferred:
            rec["prefill_chunks_deferred"] = self.prefill_chunks_deferred
            rec["prefill_ticks_deferring"] = self.prefill_ticks_deferring
        if self.compute_steps:
            rec["runtime_handoffs_per_tick"] = round(
                self.runtime_handoffs / self.compute_steps, 3)
            rec["occupancy"] = round(
                self._occupancy_sum / (self.compute_steps
                                       * pool.num_slots), 3)
        # Arena-lifetime reservation (constant) + the per-tick block
        # gauges.  kv_waste_pct compares what the held blocks could
        # store against what live slots logically filled — the
        # block-rounding + reuse-lag overhead of the paged layout
        # (clamped at 0: heavy sharing counts shared tokens once
        # physically but once PER SLOT logically).
        reserved = pool.kv_bytes_reserved()
        rec["kv_bytes_reserved"] = reserved
        if self.compute_steps:
            kv = self._kv_hist.summary()
            blk = self._blk_hist.summary()
            rec["slot_occupancy"] = self._occ_hist.summary()
            rec["kv_bytes_live"] = kv
            rec["blocks_live"] = blk
            rec["kv_bytes_committed"] = self._committed_hist.summary()
            held = blk["mean"] * pool.block_size \
                * pool.kv_bytes_per_token()
            if held:
                rec["kv_waste_pct"] = round(
                    max(0.0, 100.0 * (1.0 - kv["mean"] / held)), 2)
        if ok:
            rec["ttft_ms"] = _pct_dict([c.ttft_s * 1e3 for c in ok])
            rec["tpot_ms"] = _pct_dict([c.tpot_s * 1e3 for c in ok])
            rec["queue_wait_ms"] = _pct_dict(
                [c.queue_wait_s * 1e3 for c in ok])
        if self.slo is not None:
            # v14 (ISSUE 16): score the trailing partial window first,
            # then embed the cumulative fold — spec, window/breach
            # totals, worst burn, sketch percentiles (the ci_gate
            # sketch-vs-exact check compares these against the exact
            # ttft_ms/tpot_ms dicts above).
            self.slo.flush()
            rec["slo"] = self.slo.summary()
        # v15 (ISSUE 17): idle-spin accounting (always on — a
        # producer-driven run's sleeps are no longer invisible) + the
        # cumulative host-overhead fraction when the profiler is armed.
        rec["idle_ticks"] = self.idle_ticks
        rec["idle_wait_ms"] = round(self.idle_wait_ms, 3)
        if self.tickprof is not None and self.tickprof.ticks:
            rec["host_overhead_frac"] = round(
                self.tickprof.host_overhead_frac(), 6)
        # v16 (ISSUE 18): the speculation ledger — emitted ONLY when
        # --speculate armed the engine, so an unarmed stream stays
        # byte-identical to pre-v16 output.  Conservation (ci_gate
        # --spec-stream): tokens_accepted <= tokens_drafted, and
        # output_tokens == tokens_accepted + tokens_sampled (every
        # emitted token is either a verified draft lane or a model
        # sample — the bonus lane and plain/sampled-path tokens).
        if self.speculate:
            rec["speculate_k"] = self.speculate
            rec["draft_kind"] = "mtp" if self.self_draft \
                else getattr(self.proposer, "name", "custom")
            rec["tokens_drafted"] = self.tokens_drafted
            rec["tokens_accepted"] = self.tokens_accepted
            rec["tokens_sampled"] = self.tokens_sampled
            rec["acceptance_rate"] = round(
                self.tokens_accepted / self.tokens_drafted, 4) \
                if self.tokens_drafted else 0.0
            if self.compute_steps:
                rec["tokens_per_tick"] = round(
                    self._tokens_out / self.compute_steps, 4)
        # v17 (ISSUE 19): the per-tenant scheduling ledger — emitted
        # ONLY when --tenants armed the fair scheduler, so an unarmed
        # stream stays byte-identical to pre-v17 output.  Each block
        # carries the DWRR config (weight / slo_class / budget), the
        # admitted-token debit total and the per-status terminal counts
        # (what ci_gate --tenant-stream conserves against the stream's
        # per-request records).
        if self.sched is not None:
            tenants = self.sched.summary()
            for c in comps:
                name = getattr(c.request, "tenant", "default")
                blk = tenants.setdefault(name, {
                    "weight": float(self.sched.spec(name).weight),
                    "slo_class": self.sched.spec(name).slo_class,
                    "admitted_tokens": 0, "queued": 0})
                counts = blk.setdefault("counts", {})
                counts[c.status] = counts.get(c.status, 0) + 1
            rec["tenants"] = tenants
        if self.run_id:
            rec["run_id"] = self.run_id
        return rec

    def slo_sketch(self) -> Optional[Dict[str, Any]]:
        """Compact serialized cumulative SLO sketches for a replica
        heartbeat (``replica_state.slo_sketch``); None without --slo."""
        return None if self.slo is None else self.slo.sketch_state()

    def host_overhead_frac(self) -> Optional[float]:
        """Cumulative (wall - device) / wall for a replica heartbeat
        (``replica_state.host_overhead_frac``); None without an armed
        --tick-profile profiler (or before its first compute tick)."""
        if self.tickprof is None or not self.tickprof.ticks:
            return None
        return self.tickprof.host_overhead_frac()

    # ---------------------------------------- scheduler-aware work view

    def unadmitted(self) -> int:
        """Requests waiting anywhere before admission: the intake queue
        PLUS the scheduler's lanes (v17 — with tenancy armed, lane
        residents have left ``queue.pending()``'s view but are very
        much still work)."""
        n = self.queue.pending()
        if self.sched is not None:
            n += self.sched.pending()
        return n

    def work_drained(self) -> bool:
        """True once no request can ever arrive or admit again: intake
        closed and empty, and (tenancy armed) every lane empty.  The
        run-loop exit test — ``queue.drained()`` alone would strand
        lane residents."""
        if not self.queue.drained():
            return False
        return self.sched is None or self.sched.pending() == 0

    def runnable_backlog(self) -> int:
        """Backlog that needs engine ticks RIGHT NOW: intake pops plus
        admissible lane work.  Budget-parked lanes count only once the
        intake is drained (a tick then finalizes them ``rejected``);
        behind an open intake they are NOT runnable — a drive loop
        with only parked work must idle-wait, not spin virtual time
        forward (which would race their virtual deadlines against
        host speed)."""
        n = self.queue.pending()
        if self.sched is not None:
            n += (self.sched.pending() if self.queue.drained()
                  else self.sched.admissible_pending())
        return n

    def tenant_admitted(self) -> Optional[Dict[str, int]]:
        """Per-tenant admitted-token totals for a replica heartbeat
        (``replica_state.tenant_admitted``); None unless tenancy is
        armed — unarmed heartbeats stay byte-identical."""
        if self.sched is None:
            return None
        return {name: tok
                for name, tok in self.sched.admitted_tokens.items()
                if tok}

    def prefix_advert(self) -> Optional[Dict[str, Any]]:
        """The prefix-cache advertisement for a replica heartbeat
        (``replica_state.prefix_keys`` + raw reuse counters); None
        unless ``--advertise-prefixes`` armed it."""
        if not self.advertise_prefixes:
            return None
        shared, total = self.pool.prefix_counters()
        return {
            "prefix_keys": self.pool.hot_prefix_hashes(
                self.advertise_prefixes),
            "prefix_shared_tokens": int(shared),
            "prefix_prompt_tokens": int(total),
        }
