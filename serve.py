#!/usr/bin/env python
"""Continuous-batching inference CLI (the serving counterpart of train.py).

Restores GPT params from a CheckpointManager checkpoint (template-free —
serving needs no optimizer state) or random-inits for smoke runs, then
drives the slot-based engine (apex_example_tpu/serve/) against a
deterministic synthetic request stream with staggered arrivals.

    # random-init smoke: 16 requests through 4 slots
    python serve.py --requests 16 --slots 4 --metrics-jsonl serve.jsonl

    # serve a trained checkpoint, sampled with per-request top-k
    python serve.py --arch gpt_tiny --checkpoint-dir ckpts \\
        --temperature 0.8 --top-k 40 --metrics-jsonl serve.jsonl

    # overload drill: bursts past the slot count + queue bound shed
    # deterministically, tight virtual deadlines exercise timeouts
    python serve.py --requests 24 --slots 2 --max-pending 4 --burst 12 \\
        --deadline-steps 40 --metrics-jsonl serve.jsonl

    # shared-system-prompt workload: prefix sharing packs the common
    # 16 tokens into refcounted blocks (COW on divergence)
    python serve.py --requests 16 --shared-prefix 16 \\
        --metrics-jsonl serve.jsonl

    # then summarize per-status accounting + latency (jax-free):
    python tools/serve_report.py serve.jsonl

The KV cache is BLOCK-PAGED (ISSUE 8; README "Paged KV cache"):
per-layer arenas of --num-blocks x --block-size token blocks, per-slot
block tables gathered inside the one compiled decode step, chunked
prefill (up to --block-size prompt tokens per tick), and admission by
worst-case block budget — out-of-blocks resolves as deterministic
head-of-line queueing, and a request that could never be served (its
prompt fills the cache) terminates with status "rejected" at admission.

Quantization (ISSUE 13; README "Quantization"): ``--weight-quant
{int8,fp8}`` quantizes the restored weights per-channel at restore
time (dequant runs scale-fused inside the one compiled decode step;
layernorms/biases stay high-precision per amp/lists.py) and
``--kv-quant`` stores the paged KV arenas as int8 with bf16 per-token
block scales — quantize on the scatter write, dequant in the gathered
attention, scales copied with their blocks under COW/prefix sharing.
Geometry stays static, so the program still compiles exactly once;
``serve_summary`` carries ``kv_dtype``/``weight_dtype`` and the
dtype-accurate vs bf16-equivalent per-token bytes (schema v11), and
``tools/ci_gate.py --quant-stream`` enforces the >= 1.9x compression
floor over a recorded stream.

Sharded + disaggregated serving (ISSUE 14; README "Sharded &
disaggregated serving"): ``--mesh dp,tp`` registers a
(data=dp, model=tp) device mesh and serves the Megatron-TP model —
weights and per-layer paged-KV arenas shard over heads on 'model',
block tables and admission stay host-side, the decode program lowers
once with GSPMD shardings, and TP-served greedy output is
token-identical to the dense path (int8 weights/KV included).
``--role prefill`` chunk-prefills prompts, samples each request's
first token and ships its KV blocks (storage-dtype-exact payloads +
scales + fill levels) to the ``--handoff-dir`` spool; ``--role
decode`` admits those payloads into its own arena and decodes with a
[slots, 1]-wide step — so long prompts stop stalling decode ticks.

The spool speaks a LEASED crash-safe protocol (ISSUE 15; README
"Disaggregated serving resilience"): decode workers claim files by
atomic rename and hold a ``--handoff-lease`` wall-clock lease,
ack-by-delete at admission, reclaim a dead peer's expired claims (or
adopt their own pre-crash claims on restart) so handoffs REDELIVER
instead of stranding, detect redeliveries of already-admitted uids
against the engine's seen-set (acked as duplicates, never scattered
twice), quarantine corrupt payloads to ``*.bad`` instead of dying,
and bound the wait for a producer that died sentinel-less
(``--handoff-idle-timeout``).  N decode workers can share one spool.
Both sides emit schema-v13 ``kv_handoff`` records (with
redelivered/duplicate/quarantine provenance) and ``tools/ci_gate.py
--disagg-stream`` checks a recorded deployment for conservation —
redelivery tolerated, exactly-once admission and terminal per uid.
A decode worker composes with the fleet protocol via ``--outbox``
alone (no ``--inbox`` — the spool is its intake); a prefill worker
takes the full inbox/outbox pair.

Resilience (README "Serving resilience"; ISSUE 5): SIGTERM/SIGUSR1
triggers a graceful drain — admission stops, queued requests are handed
back with status "drained" (requeue-able on another replica), in-flight
slots finish or deadline-evict, a ``serve_drain`` record plus the
normal un-aborted ``serve_summary`` close the stream, and the process
exits 75 (EX_TEMPFAIL) so a supervisor (tools/supervise.py --no-resume)
restarts it.  ``--inject-fault {crash,sigterm,hang,nan,slot_fail}@tick``
makes every failure path deterministic; ``--flight-recorder`` keeps
crash forensics for the paths that ARE crashes.

With --metrics-jsonl the run emits schema-v5 records through the obs
sink: a run_header, one ``request_complete`` / ``request_failed`` /
``shed`` per terminated request, an optional ``serve_drain``, and a
closing ``serve_summary`` (throughput, latency percentiles, per-status
counts, availability).  The stream passes tools/metrics_lint.py like
every other obs stream.

Live migration (ISSUE 20; README "Live migration & elastic
pools"): ``--migrate-dir`` arms a second leased spool for MID-FLIGHT
requests.  A SIGTERM drain then ships every live slot — KV blocks
(storage-dtype-exact, int8 + scales included), cursor/fill, generated
tokens and sampler state — to the spool instead of evicting or
requeueing it (status "migrated", outside the availability
denominator), and every tick the engine polls the spool and resumes
any peer's shipped request token-identically (``admit_migrated``
rides the same claim/ack/redelivery/duplicate machinery as the
prefill handoff).  The spool is shared and long-lived: no close
sentinel is ever written, so replicas can come and go.

Fleet replica mode (ISSUE 12; README "Fleet serving & chaos
scenarios"): ``--inbox``/``--outbox`` replace the synthetic workload
with the file-based fleet protocol — a router (fleet.py /
apex_example_tpu/fleet/) APPENDS request specs to the inbox and this
process APPENDS one terminal line per request to the outbox.  Both
files are append-only and replayed across supervised restarts: a
restarted attempt re-reads the whole inbox and skips every uid already
in the outbox, so a crash re-serves exactly the requests that never
reached a terminal status (crash-safe exactly-once).  A
``{"close": true}`` sentinel ends the stream (exit 0).  With
``--metrics-jsonl`` the replica also heartbeats schema-v10
``replica_state`` records (tick / queue depth / blocks_live / pid) the
router tails for health and its ``least_kv`` policy.
``--seed-substream I`` derives replica i's synthetic workload from
``substream(seed, i)`` so standalone fleet members sharing one base
seed serve disjoint, individually-deterministic streams.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from collections import deque


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="continuous-batching GPT inference")
    p.add_argument("--arch", default="gpt_tiny",
                   choices=["gpt_tiny", "gpt_base", "xing4_tiny",
                            "xing4_29b_a4b_cut", "granite_hybrid_tiny",
                            "granite_4_0_h_micro", "pangu_moe_tiny",
                            "openpangu_ultra_moe_718b_cut", "trinity_tiny",
                            "trinity_mini_cut", "lfm2_tiny",
                            "lfm2_8b_a1b_cut"],
                   help="gpt_*: the post-LN decoder (float32). xing4_*: "
                        "latent attention, dropless experts, hyper-"
                        "connected residual (models/xing4.py): the tiny "
                        "preset in float32, the published widths cut to 6 "
                        "layers in bfloat16 — the architecture sets the "
                        "dtype; --kv-quant, --weight-quant, --speculate "
                        "and a model-sharded --mesh are refused where the "
                        "model, pool or engine cannot build them. "
                        "granite_*: Mamba-2 layers whose recurrent state "
                        "lives per slot beside the paged K/V of its GQA "
                        "attention layers (models/granite_hybrid.py): the "
                        "tiny preset in float32, granite-4.0-h-micro whole "
                        "(40 layers, bfloat16); no prefix is shared "
                        "(prefix_hit_rate reads 0: the state at a prefix "
                        "boundary is held nowhere), and --speculate (a "
                        "state cannot be rolled back), --kv-quant and a "
                        "model-sharded --mesh (not built) are refused. "
                        "pangu_*/openpangu_*: sandwich-normed latent "
                        "attention and dropless experts with ONE next-"
                        "token module that drafts for the engine "
                        "(models/pangu_moe.py): the tiny preset in "
                        "float32, one chip's share of openPangu-Ultra-"
                        "MoE-718B at its published widths in bfloat16 (16 "
                        "of 256 experts, 1/8 of the vocabulary, 5 layers "
                        "and the module); served drafting one token a "
                        "tick with no flag (--speculate 0 turns it off, "
                        "--speculate 2, --kv-quant and a model-sharded "
                        "--mesh are refused). trinity_*: window and full "
                        "attention layers mixed (gated, QK-normed GQA) "
                        "with all 128 sigmoid-routed experts "
                        "(models/trinity.py): the tiny preset in float32 "
                        "(window 8), Trinity-Mini's published widths cut "
                        "to 5 layers in bfloat16; the window layers' K/V "
                        "live in a second, ring-sized arena whose blocks "
                        "are handed back while a request runs; no prefix "
                        "is shared, and --kv-quant, --speculate, --role "
                        "prefill|decode and a model-sharded --mesh are "
                        "refused. lfm2_*: gated short-convolution layers "
                        "whose two kept rows live per slot beside the "
                        "paged K/V of QK-normed rotary GQA layers, 32 "
                        "sigmoid-routed experts and no shared one "
                        "(models/lfm2.py): the tiny preset in float32, "
                        "LFM2-8B-A1B's published widths cut to 13 layers "
                        "in bfloat16; no prefix is shared, and "
                        "--speculate, --kv-quant and a model-sharded "
                        "--mesh are refused")
    p.add_argument("--checkpoint-dir", default=None,
                   help="CheckpointManager directory to restore params "
                        "from (omit = random init, smoke mode)")
    p.add_argument("--checkpoint-step", type=int, default=None,
                   help="checkpoint step (default: latest)")
    p.add_argument("--slots", type=int, default=4,
                   help="KV-cache slot count (the max decode batch)")
    p.add_argument("--max-len", type=int, default=None,
                   help="per-slot cache length (default: the model's "
                        "position table, capped at 128 for gpt_tiny)")
    p.add_argument("--block-size", type=int, default=8,
                   help="KV arena block granularity in tokens: chunked "
                        "prefill feeds up to this many prompt tokens "
                        "per tick, and prefix sharing/allocation happen "
                        "per block (serve/slots.py)")
    p.add_argument("--num-blocks", type=int, default=None,
                   help="KV arena size in blocks per layer (default: "
                        "slots * ceil(max_len / block_size) — the dense "
                        "layout's capacity; admission reserves each "
                        "request's worst-case block budget against it)")
    p.add_argument("--requests", type=int, default=16,
                   help="synthetic request count")
    p.add_argument("--prompt-len", default="4:12",
                   help="prompt length, N or MIN:MAX tokens")
    p.add_argument("--shared-prefix", type=int, default=0,
                   help="prepend one common N-token system prompt to "
                        "every request (drawn once per seed) — the "
                        "prefix-sharing workload: shared KV blocks are "
                        "computed once and refcounted, measurable in "
                        "serve_summary's prefix_hit_rate/cow_copies")
    p.add_argument("--repetitive", action="store_true",
                   help="templated workload: each prompt tiles a short "
                        "per-request motif to its sampled length "
                        "(deterministic per seed) — self-repeating "
                        "spans the prompt-lookup drafter can exploit, "
                        "the honest traffic shape for --speculate "
                        "acceptance measurements")
    p.add_argument("--max-new", default="4:16",
                   help="output budget, N or MIN:MAX tokens")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy, >0 samples")
    p.add_argument("--top-k", type=int, default=0,
                   help="restrict sampling to the k highest logits "
                        "(0 = full softmax)")
    p.add_argument("--eos-id", type=int, default=None,
                   help="token id that ends a request early")
    p.add_argument("--stagger", type=int, default=2,
                   help="virtual engine steps between request arrivals "
                        "(0 = all arrive at once)")
    p.add_argument("--burst", type=int, default=1,
                   help="arrivals per wave: B requests land together "
                        "every --stagger ticks (deterministic overload "
                        "mode; 1 = the classic one-by-one stagger)")
    p.add_argument("--tenants", default=None, metavar="SPEC",
                   help="multi-tenant scheduling (ISSUE 19): arm "
                        "deficit-weighted round-robin admission over "
                        "per-tenant lanes instead of FIFO.  SPEC is "
                        "';'-separated clauses "
                        "name[:weight=W,budget=TOKENS,class="
                        "interactive|batch,mix=M,burst=B,"
                        "shared_prefix=P] — weight shapes the DWRR "
                        "share, budget caps admitted tokens (over-"
                        "budget requests park, then reject at drain), "
                        "interactive lanes preempt batch admission; "
                        "mix/burst/shared_prefix shape the synthetic "
                        "workload per tenant (sched/tenants.py)")
    p.add_argument("--advertise-prefixes", type=int, default=0,
                   metavar="N",
                   help="replica mode: advertise the N hottest prefix "
                        "chain-key digests + raw prefix-reuse counters "
                        "in replica_state heartbeats (schema v17) — "
                        "what the fleet router's prefix_affinity "
                        "policy routes on (0 = off, heartbeats "
                        "unchanged)")
    p.add_argument("--max-pending", type=int, default=None,
                   help="admission control: bound on the arrived request "
                        "backlog; overflow is shed deterministically "
                        "(default: unbounded)")
    p.add_argument("--shed-policy", default="newest",
                   choices=["newest", "oldest"],
                   help="which side of the backlog to shed on overflow "
                        "(newest = reject incoming, the default)")
    p.add_argument("--deadline-steps", type=int, default=None,
                   help="per-request deadline in engine ticks after "
                        "arrival (deterministic; expires queued requests "
                        "without admitting and evicts decoding slots "
                        "mid-flight)")
    p.add_argument("--deadline-s", type=float, default=None,
                   help="per-request wall-clock TTL from arrival")
    p.add_argument("--steps", type=int, default=0,
                   help="engine tick cap (0 = run until drained)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seed-substream", type=int, default=None,
                   metavar="I",
                   help="derive the workload seed as substream(seed, I) "
                        "— fleet members sharing a base seed serve "
                        "disjoint yet deterministic prompt sets "
                        "(serve/loadgen.py)")
    p.add_argument("--inbox", default=None, metavar="JSONL",
                   help="fleet replica mode: serve request specs "
                        "APPENDED to this file by a router instead of "
                        "the synthetic workload; replayed from byte 0 "
                        "on every supervised restart; a "
                        "'{\"close\": true}' line ends the stream")
    p.add_argument("--outbox", default=None, metavar="JSONL",
                   help="fleet replica mode: append one terminal line "
                        "per request (uid/status/tokens); append-only "
                        "across restarts — the restart-skip set and "
                        "the router's completion feed")
    p.add_argument("--replica-id", default="replica",
                   help="this replica's name in heartbeat and fleet "
                        "records")
    p.add_argument("--heartbeat-s", type=float, default=0.25,
                   metavar="S",
                   help="replica-mode health heartbeat period: a "
                        "schema-v10 replica_state record (tick, queue "
                        "depth, blocks_live, pid) every S seconds on "
                        "the metrics stream")
    p.add_argument("--mesh", default=None, metavar="DP,TP",
                   help="serve TP-sharded: register a (data=DP, "
                        "model=TP) device mesh — weights and per-layer "
                        "paged-KV arenas shard over heads on 'model' "
                        "(the training TP layout), block tables and "
                        "admission stay host-side; the decode program "
                        "compiles once with GSPMD shardings and greedy "
                        "output stays token-identical to the dense "
                        "path.  Needs DP*TP visible devices (virtual "
                        "CPU devices via XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    p.add_argument("--role", default="both",
                   choices=["both", "prefill", "decode"],
                   help="disaggregated serving (serve/disagg.py): "
                        "'prefill' chunk-prefills prompts, samples each "
                        "request's first token and ships its KV blocks "
                        "to --handoff-dir; 'decode' admits those "
                        "payloads and decodes with a [slots, 1]-wide "
                        "step (no prefill lanes); 'both' is the classic "
                        "interleaved engine")
    p.add_argument("--handoff-dir", default=None, metavar="DIR",
                   help="KV-handoff spool directory connecting a "
                        "--role prefill process to one or more --role "
                        "decode processes (atomic npz files claimed by "
                        "lease + a close sentinel; serve/disagg.py)")
    p.add_argument("--handoff-lease", type=float, default=30.0,
                   metavar="S",
                   help="decode role: wall-clock lease on each claimed "
                        "spool file — a claim whose holder dies is "
                        "reclaimed by any peer after S seconds and the "
                        "handoff redelivered (default 30)")
    p.add_argument("--handoff-idle-timeout", type=float, default=None,
                   metavar="S",
                   help="decode role: exit after S idle seconds when "
                        "the spool never closes (the producer died "
                        "before writing the sentinel) instead of "
                        "waiting forever (default: wait)")
    p.add_argument("--migrate-dir", default=None, metavar="DIR",
                   help="live-migration spool (ISSUE 20; --role both "
                        "only): a SIGTERM drain ships every in-flight "
                        "request's KV blocks + cursor + generated "
                        "tokens here instead of evicting it, and every "
                        "tick this replica polls the spool and resumes "
                        "peers' shipped requests token-identically "
                        "(leased claim/ack/redelivery, same protocol "
                        "as --handoff-dir; --handoff-lease sets the "
                        "lease).  Shared + long-lived: no close "
                        "sentinel is written")
    p.add_argument("--weight-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="quantize the restored weights for serving "
                        "(ISSUE 13): symmetric per-channel int8, or "
                        "float8_e4m3 where this jax supports it (else "
                        "emulated on the e4m3 grid); layernorms/biases "
                        "stay high-precision per the AMP op tables "
                        "(amp/lists.py) and dequant runs scale-fused "
                        "inside the one compiled decode step")
    p.add_argument("--kv-quant", action="store_true",
                   help="store the paged KV arenas as int8 with bf16 "
                        "per-token block scales: quantize on the "
                        "scatter write, dequantize in the gathered "
                        "attention, scales copied with their blocks "
                        "under COW/prefix sharing (quant/kv.py) — "
                        "~1.9x the bf16 arena's bytes, ~3.9x fp32's")
    p.add_argument("--speculate", type=int, default=None, metavar="K",
                   help="speculative decoding (ISSUE 18): a host-side "
                        "proposer drafts up to K tokens per greedy slot "
                        "per tick and the engine verifies all lanes in "
                        "ONE [SLOTS, max(block_size, K+1)]-wide "
                        "dispatch, accepting the longest draft prefix "
                        "matching the model's own argmax — greedy "
                        "outputs stay token-identical to generate() "
                        "while tokens/tick rises above 1.0; rejected "
                        "lanes roll back for free (the cursor simply "
                        "does not advance).  0 = off, bit-identical to "
                        "the plain path; not given = what the model "
                        "carries: 0, or 1 for a model with a next-token "
                        "module, which drafts on the device instead of "
                        "the host proposer")
    p.add_argument("--draft", default="ngram",
                   choices=["ngram", "none"],
                   help="draft proposer for --speculate: 'ngram' "
                        "matches the last N generated tokens against "
                        "the request's own prompt + history (no second "
                        "model); 'none' never drafts — the off-switch "
                        "that keeps the speculative program armed but "
                        "degenerates every tick to single-lane decode")
    p.add_argument("--draft-ngram", type=int, default=3, metavar="N",
                   help="match-window length for --draft ngram "
                        "(longest window tried first, falling back to "
                        "shorter suffixes)")
    p.add_argument("--metrics-jsonl", default=None,
                   help="emit schema-valid serving records to this JSONL")
    p.add_argument("--trace", action="store_true",
                   help="with --metrics-jsonl: emit schema-v9 "
                        "trace_event records — per-tick admit/dispatch/"
                        "harvest spans and a per-request lifecycle span "
                        "tree (queued -> prefill chunks -> first_token "
                        "-> decode -> terminal status) — exportable to "
                        "Perfetto via tools/trace_export.py; host-only, "
                        "the compiled decode step is untouched "
                        "(README 'Request tracing')")
    p.add_argument("--cost-model", action="store_true",
                   help="with --metrics-jsonl: AOT-compile the slot "
                        "decode step and emit schema-v6 compile_event + "
                        "cost_model records (per-tick decode flops/HBM "
                        "bytes + roofline verdict; obs/costmodel.py — "
                        "the decode program still compiles exactly once)")
    p.add_argument("--slo", default=None, metavar="SPEC",
                   help="with --metrics-jsonl: arm the streaming SLO "
                        "plane (ISSUE 16) — a comma list like "
                        "'ttft_ms=250,tpot_ms=40,availability=0.999'. "
                        "Terminal requests are scored good/bad against "
                        "the latency targets, folded into online "
                        "quantile sketches and tumbling windows, and "
                        "each window emits a schema-v14 slo_window "
                        "record (p50/p90/p99, counts, error-budget "
                        "burn rate) plus an slo_breach record when the "
                        "burn rate exceeds 1.0; serve_summary carries "
                        "the cumulative verdict (README 'SLO "
                        "monitoring').  Host-only: the compiled decode "
                        "step is untouched")
    p.add_argument("--slo-window-s", type=float, default=None,
                   metavar="S",
                   help="tumbling SLO window length in wall-clock "
                        "seconds (default 1.0); windows with no "
                        "terminal events are skipped, not emitted")
    p.add_argument("--slo-window-ticks", type=int, default=0,
                   metavar="N",
                   help="close SLO windows every N engine ticks "
                        "instead of on wall-clock — the deterministic "
                        "mode tests and recorded fixtures use "
                        "(0 = wall-clock windows)")
    p.add_argument("--tick-profile", action="store_true",
                   help="with --metrics-jsonl: arm the hot-path tick "
                        "profiler (obs/tickprof.py, ISSUE 17) — every "
                        "compute tick decomposes into admit / "
                        "dispatch_enqueue / device_wait (an explicit "
                        "block-until-ready boundary, the first time "
                        "enqueue cost and device execution are "
                        "separable) / harvest / spool_io / telemetry, "
                        "folded into online quantile sketches; every "
                        "Nth tick emits a schema-v15 tick_profile "
                        "record and the run closes with an "
                        "overhead_summary (host_gap_ms, per-phase "
                        "percentiles, host_overhead_frac — what "
                        "tools/perf_ledger.py regression-gates).  "
                        "Value-preserving and compile-free: greedy "
                        "outputs stay token-identical and no new "
                        "program compiles (README 'Hot-path "
                        "profiling')")
    p.add_argument("--tick-profile-every", type=int, default=16,
                   metavar="N",
                   help="emit a tick_profile record every N compute "
                        "ticks (default 16; the cumulative "
                        "overhead_summary always folds EVERY tick)")
    p.add_argument("--inject-fault", default="", metavar="KIND@TICK",
                   help="deterministic serve-path fault drill at a "
                        "1-based engine tick: crash | sigterm | hang | "
                        "nan | slot_fail (resilience/faults.py; sigterm "
                        "exercises the drain path, slot_fail the "
                        "slot-isolation path).  Handoff drills (the "
                        "disagg resilience path, @N = the Nth "
                        "send/admit): handoff_torn | sentinel_lost on "
                        "a --role prefill process, "
                        "handoff_crash_preack | handoff_dup on a "
                        "--role decode process")
    p.add_argument("--flight-recorder", action="store_true",
                   help="arm crash forensics (obs/flight.py): abnormal "
                        "exits write a crash_dump + aborted summary to "
                        "the metrics stream; SIGTERM stays with the "
                        "drain handler (release_signal handover)")
    p.add_argument("--no-drain", action="store_true",
                   help="do not catch SIGTERM/SIGUSR1 for graceful "
                        "drain (signals then kill the process as before)")
    return p


class _Outbox:
    """The replica-side completion outbox: APPEND-only (it must survive
    supervised restarts — truncation would forget what attempt K-1
    already served), one JSON line per terminal request.  On startup it
    replays itself into the inbox feeder's skip logic (crash-safe
    exactly-once):

    - a NON-drained terminal ends the uid for good — every later inbox
      occurrence is skipped;
    - a "drained" line consumed ONE inbox occurrence without serving it
      (the router requeued that copy — possibly to a sibling, possibly
      back to THIS replica as a fresh inbox line when it is the only
      survivor), so exactly that many occurrences are skipped and the
      next one is served.  Treating drained as terminal would silently
      lose requeue-to-self requests after a restart."""

    def __init__(self, path: str):
        self.path = path
        self.done = set()
        self._drained: dict = {}        # uid -> unconsumed drain count
        if os.path.exists(path):
            with open(path) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        ev = json.loads(line)
                    except json.JSONDecodeError:
                        continue        # a killed writer's torn tail
                    if isinstance(ev, dict) and "uid" in ev:
                        if ev.get("status") == "drained":
                            self._drained[ev["uid"]] = \
                                self._drained.get(ev["uid"], 0) + 1
                        else:
                            self.done.add(ev["uid"])
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._fh = open(path, "a")
        self._consumed = 0

    def should_skip(self, uid: str) -> bool:
        """Called by the inbox feeder once per inbox OCCURRENCE of
        ``uid`` (feeder thread only — no locking needed)."""
        if uid in self.done:
            return True
        n = self._drained.get(uid, 0)
        if n > 0:
            self._drained[uid] = n - 1  # that occurrence was drained
            return True
        return False

    def flush_from(self, engine) -> None:
        comps = engine.completions
        # Redelivery provenance rides the outbox (ISSUE 15): the fleet
        # router's disagg accounting keys on which terminals came from
        # a redelivered handoff admission.
        redelivered = getattr(engine, "handoff_redelivered", ())
        with_tenant = getattr(engine, "sched", None) is not None
        for c in comps[self._consumed:]:
            ev = {"uid": c.request.uid, "status": c.status,
                  "finish_reason": c.finish_reason,
                  "tokens": [int(t) for t in c.tokens],
                  "tick": c.finished_step,
                  "ttft_ms": None if c.ttft_s is None
                  else c.ttft_s * 1e3,
                  "tpot_ms": None if c.tpot_s is None
                  else c.tpot_s * 1e3}
            if with_tenant:
                ev["tenant"] = getattr(c.request, "tenant", "default")
            if c.request.uid in redelivered:
                ev["redelivered"] = True
            self._fh.write(json.dumps(ev, separators=(",", ":")) + "\n")
        self._consumed = len(comps)
        self._fh.flush()

    def close(self) -> None:
        self._fh.close()


def _feed_inbox(path, queue, outbox, stop_event, request_cls):
    """Daemon thread: tail the inbox JSONL (which may not exist yet)
    and submit every spec occurrence the outbox replay does not skip
    (``_Outbox.should_skip``).  Only complete lines are consumed — a
    torn tail is retried whole.  Ends on the close sentinel (queue
    closed: the engine loop finishes and exits 0), on a drain closing
    the queue under us, or on ``stop_event``."""
    pos = 0
    while not stop_event.is_set():
        if not os.path.exists(path):
            time.sleep(0.02)
            continue
        with open(path) as fh:
            fh.seek(pos)
            chunk = fh.read()
        consumed = chunk.rfind("\n") + 1
        pos += consumed
        for line in chunk[:consumed].splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                spec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(spec, dict):
                continue
            if spec.get("close"):
                queue.close()
                return
            uid = spec.get("uid")
            if uid is None or outbox.should_skip(uid):
                continue
            req = request_cls(
                prompt=spec["prompt"],
                max_new_tokens=int(spec["max_new_tokens"]),
                temperature=float(spec.get("temperature", 0.0)),
                top_k=int(spec.get("top_k", 0)),
                eos_id=spec.get("eos_id"),
                tenant=spec.get("tenant", "default"),
                priority=int(spec.get("priority", 0)),
                deadline_s=spec.get("deadline_s"),
                deadline_step=spec.get("deadline_step"),
                uid=uid)
            try:
                queue.submit(req)
            except RuntimeError:
                return                  # drain closed the queue
        if consumed == 0:
            time.sleep(0.02)


def run_serve(args):
    """Build, restore, drive — and drain gracefully on SIGTERM/SIGUSR1.
    Returns (completions, summary_record, rc) — split from main() so
    tests can assert on the served tokens; rc is 75 (EX_TEMPFAIL) after
    a drain so a supervisor restarts rather than buries the server."""
    import jax
    import jax.numpy as jnp

    from apex_example_tpu import obs
    from apex_example_tpu.models.gpt import gpt_base, gpt_tiny
    from apex_example_tpu.models.granite_hybrid import (granite_4_0_h_micro,
                                                        granite_hybrid_tiny)
    from apex_example_tpu.models.lfm2 import lfm2_8b_a1b_cut, lfm2_tiny
    from apex_example_tpu.models.pangu_moe import (
        openpangu_ultra_moe_718b_cut, pangu_moe_tiny)
    from apex_example_tpu.models.trinity import (trinity_mini_cut,
                                                 trinity_tiny)
    from apex_example_tpu.models.xing4 import (xing4_29b_a4b_cut,
                                               xing4_tiny)
    from apex_example_tpu.parallel.mesh import (parse_serve_mesh,
                                                serve_mesh)
    from apex_example_tpu.resilience import (EX_TEMPFAIL, FaultPlan,
                                             PreemptionHandler)
    from apex_example_tpu.resilience.faults import (HANDOFF_KINDS,
                                                    SERVE_KINDS)
    from apex_example_tpu.serve import (FileTransport, Request,
                                        RequestQueue, ServeEngine,
                                        parse_range, run_decode_role,
                                        synthetic_requests)
    from apex_example_tpu.transformer import parallel_state
    from apex_example_tpu.utils.checkpoint import restore_params
    from apex_example_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    mesh = None
    dp = tp = 1
    if args.mesh:
        try:
            dp, tp = parse_serve_mesh(args.mesh)
            if dp * tp > 1:
                mesh = serve_mesh(dp, tp)
        except ValueError as e:
            raise SystemExit(str(e))
    # tp > 1 serves the Megatron-TP model (identical param tree — dense
    # checkpoints restore unchanged; the layers' constraint points do
    # the sharding).
    model = {"gpt_tiny": gpt_tiny, "gpt_base": gpt_base,
             "xing4_tiny": xing4_tiny,
             "xing4_29b_a4b_cut": xing4_29b_a4b_cut,
             "granite_hybrid_tiny": granite_hybrid_tiny,
             "granite_4_0_h_micro": granite_4_0_h_micro,
             "pangu_moe_tiny": pangu_moe_tiny,
             "openpangu_ultra_moe_718b_cut": openpangu_ultra_moe_718b_cut,
             "trinity_tiny": trinity_tiny,
             "trinity_mini_cut": trinity_mini_cut,
             "lfm2_tiny": lfm2_tiny,
             "lfm2_8b_a1b_cut": lfm2_8b_a1b_cut,
             }[args.arch](tensor_parallel=tp > 1)
    max_len = args.max_len
    if max_len is None:
        max_len = min(model.max_position, 128)
    prompt_len = parse_range(args.prompt_len, "prompt-len")
    max_new = parse_range(args.max_new, "max-new")
    if args.shared_prefix < 0:
        raise SystemExit(f"--shared-prefix must be >= 0, got "
                         f"{args.shared_prefix}")
    if prompt_len[1] + args.shared_prefix >= max_len:
        raise SystemExit(f"--prompt-len max {prompt_len[1]} plus "
                         f"--shared-prefix {args.shared_prefix} must be "
                         f"< --max-len {max_len}")
    if args.block_size < 1:
        raise SystemExit(f"--block-size must be >= 1, got "
                         f"{args.block_size}")
    if args.num_blocks is not None and args.num_blocks < 1:
        raise SystemExit(f"--num-blocks must be >= 1, got "
                         f"{args.num_blocks}")
    if args.flight_recorder and not args.metrics_jsonl:
        # Same guard as train.py: forensics need a stream to land in —
        # a silently-disarmed recorder is worse than an error.
        raise SystemExit("--flight-recorder requires --metrics-jsonl "
                         "(the crash_dump rides the metrics stream)")
    if args.cost_model and not args.metrics_jsonl:
        raise SystemExit("--cost-model requires --metrics-jsonl (the "
                         "compile_event/cost_model records ride the "
                         "metrics stream)")
    if args.trace and not args.metrics_jsonl:
        raise SystemExit("--trace requires --metrics-jsonl (the "
                         "trace_event records ride the metrics stream)")
    slo_spec = None
    if args.slo:
        if not args.metrics_jsonl:
            raise SystemExit("--slo requires --metrics-jsonl (the "
                             "slo_window/slo_breach records ride the "
                             "metrics stream)")
        from apex_example_tpu.obs.slo import parse_slo
        try:
            slo_spec = parse_slo(args.slo)
        except ValueError as e:
            raise SystemExit(f"--slo: {e}")
    if args.slo_window_s is not None and args.slo_window_s <= 0:
        raise SystemExit(f"--slo-window-s must be > 0, got "
                         f"{args.slo_window_s}")
    if args.slo_window_ticks < 0:
        raise SystemExit(f"--slo-window-ticks must be >= 0, got "
                         f"{args.slo_window_ticks}")
    if args.tick_profile and not args.metrics_jsonl:
        raise SystemExit("--tick-profile requires --metrics-jsonl (the "
                         "tick_profile/overhead_summary records ride "
                         "the metrics stream)")
    if args.tick_profile_every < 1:
        raise SystemExit(f"--tick-profile-every must be >= 1, got "
                         f"{args.tick_profile_every}")
    if args.speculate is not None and args.speculate < 0:
        raise SystemExit(f"--speculate must be >= 0, got "
                         f"{args.speculate}")
    if args.speculate and args.role != "both":
        raise SystemExit("--speculate needs the interleaved engine "
                         "(--role both): disaggregated roles keep "
                         "their own step geometries")
    if args.speculate and args.speculate + 1 > max_len:
        raise SystemExit(f"--speculate {args.speculate} exceeds "
                         f"--max-len {max_len} lanes")
    if args.draft_ngram < 1:
        raise SystemExit(f"--draft-ngram must be >= 1, got "
                         f"{args.draft_ngram}")
    tenant_specs = None
    if args.tenants:
        from apex_example_tpu.sched.tenants import parse_tenants
        try:
            tenant_specs = parse_tenants(args.tenants)
        except ValueError as e:
            raise SystemExit(str(e))
        if args.shared_prefix or args.burst != 1:
            raise SystemExit("--tenants makes --shared-prefix/--burst "
                             "per-tenant (spec keys shared_prefix= / "
                             "burst=); drop the global flags")
        for tsp in tenant_specs.values():
            if prompt_len[1] + tsp.shared_prefix >= max_len:
                raise SystemExit(
                    f"--prompt-len max {prompt_len[1]} plus tenant "
                    f"{tsp.name!r} shared_prefix {tsp.shared_prefix} "
                    f"must be < --max-len {max_len}")
    if args.advertise_prefixes < 0:
        raise SystemExit(f"--advertise-prefixes must be >= 0, got "
                         f"{args.advertise_prefixes}")
    replica_mode = bool(args.inbox or args.outbox)
    if args.role == "decode":
        # A decode worker's intake is the --handoff-dir spool, never an
        # inbox; its fleet surface is the outbox alone (terminal lines
        # out, so a router can harvest what the spool fed it).
        if args.inbox:
            raise SystemExit("--role decode takes no --inbox (its "
                             "intake is the --handoff-dir spool); give "
                             "it --outbox alone for the fleet protocol")
    elif replica_mode and not (args.inbox and args.outbox):
        raise SystemExit("--inbox and --outbox come together (the "
                         "fleet replica protocol: specs in, terminal "
                         "lines out)")
    if args.role != "both" and not args.handoff_dir:
        raise SystemExit("--role prefill/decode needs --handoff-dir "
                         "(the KV-handoff spool both roles share)")
    if args.handoff_dir and args.role == "both":
        raise SystemExit("--handoff-dir only means something for a "
                         "--role prefill or decode process")
    if args.handoff_lease <= 0:
        raise SystemExit(f"--handoff-lease must be > 0, got "
                         f"{args.handoff_lease}")
    if args.migrate_dir and args.role != "both":
        raise SystemExit("--migrate-dir needs the interleaved engine "
                         "(--role both): disaggregated roles keep the "
                         "prefill->decode spool as their only transport")
    if args.heartbeat_s <= 0:
        raise SystemExit(f"--heartbeat-s must be > 0, got "
                         f"{args.heartbeat_s}")
    fault = None
    if args.inject_fault:
        try:
            fault = FaultPlan.parse(args.inject_fault, kinds=SERVE_KINDS)
        except ValueError as e:
            raise SystemExit(str(e))
    # Handoff drills fire inside the transport / decode drive loop, not
    # the engine tick loop — route the plan there, and reject a drill
    # the process's role could never express (a silently-inert drill is
    # worse than an error).
    handoff_fault = None
    if fault is not None and fault.kind in HANDOFF_KINDS:
        need = "prefill" if fault.kind in ("handoff_torn",
                                           "sentinel_lost") else "decode"
        if args.role != need:
            raise SystemExit(f"--inject-fault {fault.kind} is a "
                             f"{need}-side drill (this process is "
                             f"--role {args.role})")
        handoff_fault, fault = fault, None

    if args.checkpoint_dir:
        params = restore_params(args.checkpoint_dir, args.checkpoint_step)
        source = f"checkpoint {args.checkpoint_dir}"
    else:
        params = model.init(
            jax.random.PRNGKey(args.seed),
            jnp.zeros((1, 4), jnp.int32))["params"]
        source = "random init (smoke mode)"

    # Quantization applies at RESTORE time (ISSUE 13): the engine's
    # compiled step receives the int8/fp8 leaves as arguments and
    # dequantizes in-trace — low-bit bytes are what HBM holds/streams.
    quant_stats = None
    if args.weight_quant != "none":
        from apex_example_tpu.amp.policy import get_quant_policy
        from apex_example_tpu.quant import quantize_params
        qpolicy = get_quant_policy(args.weight_quant, args.kv_quant)
        params, quant_stats = quantize_params(params, args.weight_quant)
        if not quant_stats["tensors"]:
            raise SystemExit(
                f"--weight-quant {args.weight_quant}: quantize_params found "
                f"no leaf it quantizes in --arch {args.arch}'s parameter "
                "tree; serving it would stream the weights as they are")
        source += f" -> {qpolicy.weight_dtype_name} weights"

    emitter = sink = recorder = None
    run_id = None
    # Clear any instance a previous in-process run leaked before this
    # run builds its engine (same hygiene as train.make_telemetry).
    obs.costmodel.set_default(None)
    obs.trace.set_default(None)
    if args.metrics_jsonl:
        sink = obs.JsonlSink(args.metrics_jsonl)
        emitter = obs.TelemetryEmitter(sink)
        emitter.run_header(config=vars(args), argv=sys.argv,
                           arch=args.arch)
        run_id = emitter.run_id
        if args.flight_recorder:
            recorder = obs.FlightRecorder(emitter, config=vars(args))
            recorder.install()
        if args.cost_model:
            # Process-default instance: the engine's decode step (and
            # any generate() call) picks it up without plumbing; the
            # finally below clears it.
            obs.costmodel.set_default(obs.CostModel(
                sink=sink, registry=emitter.registry, run_id=run_id))
        if args.trace:
            # Same process-default shape: the engine and the span
            # layer consult it; trace_id joins a supervising parent's
            # timeline via APEX_TRACE_ID (cross-restart continuity).
            obs.trace.set_default(obs.Tracer(sink, run_id=run_id))
        if quant_stats is not None:
            # schema v11: one quant_event per applied stratum — the
            # scale spread is the multiplier of every error bound
            # downstream tooling reasons about.  qpolicy is the policy
            # the restore block above actually APPLIED (one resolution,
            # one fp8-capability probe).
            rec = {"record": "quant_event", "time": time.time(),
                   "kind": "weights",
                   "dtype": qpolicy.weight_dtype_name,
                   "run_id": run_id}
            rec.update({k: quant_stats[k] for k in
                        ("tensors", "kept", "bytes_before",
                         "bytes_after", "scale_min", "scale_max",
                         "emulated")})
            sink.write(rec)
        if args.kv_quant:
            from apex_example_tpu.quant import kv as kv_quant_lib
            sink.write({"record": "quant_event", "time": time.time(),
                        "kind": "kv", "dtype": "int8",
                        "block_size": args.block_size,
                        "scale_dtype": str(jnp.dtype(
                            kv_quant_lib.KV_SCALE_DTYPE)),
                        "run_id": run_id})

    # The drain grace path (README "Serving resilience"): the handler
    # only sets a flag; the engine loop notices it at the next tick
    # boundary and run_serve runs the drain itself, outside signal
    # context — the same flag-and-handover shape as train.py's
    # --preempt-grace (the recorder releases SIGTERM/SIGUSR1 to us and
    # keeps excepthook/atexit for real crashes).
    preempt = None
    if not args.no_drain:
        preempt = PreemptionHandler(recorder=recorder)
        preempt.install()

    queue = RequestQueue(max_pending=args.max_pending,
                         shed_policy=args.shed_policy)

    def on_quarantine(uid, spool_name, error, nbytes):
        # A corrupt/truncated payload was parked at *.bad — the worker
        # keeps ticking; the stream records the disposition (schema
        # v13: kv_handoff direction "quarantine").
        print(f"WARNING: quarantined corrupt handoff {uid} "
              f"({spool_name}): {error}", file=sys.stderr)
        if sink is None:
            return
        sink.write({"record": "kv_handoff", "time": time.time(),
                    "request_id": uid, "direction": "quarantine",
                    "fill": 0, "blocks": 0,
                    "payload_bytes": int(nbytes),
                    "spool_file": spool_name,
                    "error": str(error)[:500], "run_id": run_id})

    transport = None
    if args.handoff_dir:
        transport = FileTransport(
            args.handoff_dir, worker=args.replica_id,
            lease_s=args.handoff_lease,
            fault=handoff_fault if args.role == "prefill" else None,
            on_quarantine=on_quarantine if args.role == "decode"
            else None)

    def on_mig_quarantine(uid, spool_name, error, nbytes):
        # Same disposition as a corrupt handoff, recorded on the v18
        # kv_migration stream: park, warn, keep serving.
        print(f"WARNING: quarantined corrupt migration {uid} "
              f"({spool_name}): {error}", file=sys.stderr)
        if sink is None:
            return
        sink.write({"record": "kv_migration", "time": time.time(),
                    "request_id": uid, "direction": "quarantine",
                    "fill": 0, "blocks": 0,
                    "payload_bytes": int(nbytes),
                    "spool_file": spool_name,
                    "error": str(error)[:500], "run_id": run_id})

    mig_transport = None
    if args.migrate_dir:
        mig_transport = FileTransport(
            args.migrate_dir, worker=args.replica_id,
            lease_s=args.handoff_lease,
            on_quarantine=on_mig_quarantine)
    # The mesh registers BEFORE the engine builds (construction shards
    # the restored — possibly quantized — params and the paged arenas
    # against it) and must STAY registered through the run: the TP
    # layers' constrain() points read it at trace time.  The run
    # section's finally clears it; a failure between here and that try
    # (engine construction, replica-mode setup) clears it on the way
    # out too, so an in-process caller (tests, supervisors) never
    # inherits a stale mesh.
    tickprof = None
    if args.tick_profile:
        from apex_example_tpu.obs.tickprof import TickProfiler
        tickprof = TickProfiler(kind="serve",
                                sample_every=args.tick_profile_every,
                                emit=sink.write if sink is not None
                                else None,
                                run_id=run_id)
    proposer = None
    if args.speculate and not getattr(model, "num_nextn_predict_layers", 0):
        # (a model with a next-token module drafts for itself)
        from apex_example_tpu.spec import get_proposer
        proposer = get_proposer(args.draft, ngram=args.draft_ngram)
    parallel_state.set_mesh(mesh)
    try:
        engine = ServeEngine(model, params, num_slots=args.slots,
                             max_len=max_len, block_size=args.block_size,
                             num_blocks=args.num_blocks,
                             rng=jax.random.PRNGKey(args.seed),
                             queue=queue, sink=sink, run_id=run_id,
                             fault=fault,
                             registry=emitter.registry if emitter
                             else None,
                             kv_quant=args.kv_quant,
                             weight_quant=args.weight_quant,
                             role=args.role,
                             handoff_sink=transport.send
                             if args.role == "prefill" else None,
                             slo=slo_spec,
                             slo_window_s=args.slo_window_s,
                             slo_window_ticks=args.slo_window_ticks,
                             tick_profiler=tickprof,
                             speculate=args.speculate,
                             proposer=proposer,
                             tenants=tenant_specs,
                             advertise_prefixes=args.advertise_prefixes)
        outbox = feeder_stop = on_tick = None
        idle_wait_s = 0.0
        if replica_mode:
            outbox = _Outbox(args.outbox)
            if args.role == "decode":
                # Crash-safe exactly-once across restarts: uids already
                # terminal in the outbox must never be served again —
                # the restarted worker replays the spool from its claim
                # set, and a handoff completed just before the crash
                # (terminal on disk, claim never acked) comes back as a
                # redelivery the seen-set turns into a duplicate-ack.
                engine.handoff_seen.update(outbox.done)
            else:
                feeder_stop = threading.Event()
                threading.Thread(
                    target=_feed_inbox,
                    args=(args.inbox, queue, outbox, feeder_stop,
                          Request),
                    name="inbox-feeder", daemon=True).start()
            idle_wait_s = 0.004             # wall-clock producer: don't spin

            def _beat(state: str) -> None:
                if sink is None:
                    return
                # v12: kv_bytes_live is the dtype-accurate gauge (int8
                # arenas count int8 bytes + scales) — what the fleet
                # router's least_kv policy prefers over the raw block
                # count when replicas mix precisions.  v13: the role
                # rides along so fleet tooling can tell a prefill
                # heartbeat from a decode one.
                rec = {"record": "replica_state", "time": time.time(),
                       "replica": args.replica_id, "state": state,
                       "role": args.role,
                       "tick": engine.step_count,
                       "pending": engine.unadmitted(),
                       "blocks_live": engine.pool.blocks_live(),
                       "kv_bytes_live": engine.pool.kv_bytes_live(),
                       "pid": os.getpid(), "run_id": run_id}
                # v14: with --slo the cumulative latency sketches ride
                # the heartbeat — the fleet router merges them into
                # fleet_rollup records (live cross-replica percentiles
                # without re-pooling raw samples).
                sk = engine.slo_sketch()
                if sk is not None:
                    rec["slo_sketch"] = sk
                # v15: with --tick-profile the cumulative host-overhead
                # fraction rides along — fleet_report ranks replicas by
                # it and names the worst.
                frac = engine.host_overhead_frac()
                if frac is not None:
                    rec["host_overhead_frac"] = round(frac, 6)
                # v17: with --advertise-prefixes the hot chain-key
                # digests + raw reuse counters ride along (the
                # prefix_affinity routing inputs); with --tenants the
                # per-tenant admitted-token totals do (fleet budget
                # accounting).  Both absent unarmed — heartbeats stay
                # byte-identical.
                adv = engine.prefix_advert()
                if adv is not None:
                    rec.update(adv)
                ta = engine.tenant_admitted()
                if ta is not None:
                    rec["tenant_admitted"] = ta
                sink.write(rec)

            last_beat = [0.0]

            def on_tick(eng) -> None:
                # With --slo, heartbeat BEFORE flushing new terminals:
                # the sketches already cover them (folded at slot
                # eviction), so the router can never tail the last
                # terminal without the matching sketch on disk — the
                # close-time fleet_rollup cannot race the child's exit.
                now = time.time()
                if (eng.slo is not None
                        and len(eng.completions) > outbox._consumed):
                    last_beat[0] = now
                    _beat("serving")
                outbox.flush_from(eng)
                if now - last_beat[0] >= args.heartbeat_s:
                    last_beat[0] = now
                    _beat("serving")
        elif args.role != "decode":
            # A decode-role engine's intake is the handoff transport, not a
            # workload of its own (run_decode_role closes the queue).
            if tenant_specs is not None:
                from apex_example_tpu.serve.loadgen import tenant_requests
                requests = tenant_requests(
                    args.requests, tenant_specs,
                    vocab_size=model.vocab_size, seed=args.seed,
                    prompt_len=prompt_len, max_new=max_new,
                    temperature=args.temperature, top_k=args.top_k,
                    eos_id=args.eos_id, stagger=args.stagger,
                    deadline_steps=args.deadline_steps,
                    deadline_s=args.deadline_s,
                    seed_substream=args.seed_substream,
                    repetitive=args.repetitive)
            else:
                requests = synthetic_requests(
                    args.requests, vocab_size=model.vocab_size,
                    seed=args.seed,
                    prompt_len=prompt_len, max_new=max_new,
                    temperature=args.temperature, top_k=args.top_k,
                    eos_id=args.eos_id, stagger=args.stagger,
                    burst=args.burst,
                    deadline_steps=args.deadline_steps,
                    deadline_s=args.deadline_s,
                    shared_prefix=args.shared_prefix,
                    seed_substream=args.seed_substream,
                    repetitive=args.repetitive)
            engine.queue.submit_all(requests)
            engine.queue.close()

        if mig_transport is not None:
            # Migration intake rides on_tick (same poll/renew/admit/ack
            # shape as run_decode_role's drive loop): deferred
            # admissions keep their claims renewed — a full pool must
            # not silently forfeit a live request to a peer.
            mig_pending: deque = deque()
            inner_on_tick = on_tick

            def on_tick(eng, _inner=inner_on_tick):
                polled = mig_transport.poll()
                if polled:
                    mig_pending.extend(polled)
                if mig_pending:
                    mig_transport.renew(mig_pending)
                while mig_pending and eng.admit_handoff(mig_pending[0]):
                    mig_transport.ack(mig_pending.popleft())
                if _inner is not None:
                    _inner(eng)

        pool = engine.pool
        if args.role == "decode":
            workload = f"decode role (handoffs from {args.handoff_dir})"
        elif replica_mode:
            workload = f"replica {args.replica_id} (inbox-fed)"
        else:
            workload = f"{args.requests} request(s)"
        shard = f"  mesh=data={dp},model={tp}" if mesh is not None else ""
        print(f"serve: {workload}  arch={args.arch}  role={args.role}  "
              f"slots={args.slots}  max_len={max_len}  "
              f"blocks={pool.num_blocks}x{pool.block_size}{shard}  "
              f"params from {source}")
    except BaseException:
        parallel_state.set_mesh(None)
        raise
    rc = 0
    try:
        if args.role == "decode":
            completions = run_decode_role(
                engine, transport,
                max_steps=args.steps or None,
                idle_wait_s=0.004,
                stop=(lambda: preempt.preempted) if preempt else None,
                on_tick=on_tick, fault=handoff_fault,
                idle_timeout_s=args.handoff_idle_timeout)
        else:
            completions = engine.run(
                max_steps=args.steps or None,
                idle_wait_s=idle_wait_s,
                stop=(lambda: preempt.preempted) if preempt else None,
                on_tick=on_tick)
        if preempt is not None and preempt.preempted:
            if feeder_stop is not None:
                feeder_stop.set()
            if replica_mode:
                _beat("draining")       # the router sees the drain start
            drain = engine.drain(preempt.signal_name,
                                 migrate=mig_transport.send
                                 if mig_transport is not None else None)
            completions = engine.completions
            migrated = (f"  migrated={drain['migrated']}"
                        if "migrated" in drain else "")
            print(f"drain ({drain['signal']}): admission stopped at tick "
                  f"{drain['step']}  in_flight={drain['in_flight']}  "
                  f"completed={drain['completed']}  "
                  f"evicted={drain['evicted']}  "
                  f"requeued={drain['requeued']}{migrated}; exiting "
                  f"{EX_TEMPFAIL} (resumable)")
            rc = EX_TEMPFAIL
        if args.role == "prefill" and rc == 0:
            # Close AFTER any drain: the drain's in-flight slots finish
            # by handing off, and the sentinel's count must cover them.
            # A DRAINED prefill (rc 75) writes no sentinel — the
            # supervisor restarts it to finish the stream, and an early
            # sentinel would let an idle decode worker exit while the
            # spool is only momentarily empty.
            transport.close()
        if outbox is not None:
            # Everything terminal — drained requeues included — must be
            # on disk before the summary: the restart-skip set and the
            # router's completion feed both read from here.
            outbox.flush_from(engine)
            # One last heartbeat AFTER the final terminals: the
            # cumulative SLO sketches and closing gauges land on disk
            # even when the run is shorter than the heartbeat cadence,
            # so the router's close-time fleet_rollup sees real data.
            _beat("serving")
        if tickprof is not None and sink is not None and tickprof.ticks:
            # The cumulative overhead fold closes just before the
            # serve_summary (same ordering contract as the SLO flush:
            # report tools read the stream tail).
            sink.write(tickprof.summary_record())
        summary = engine.summary_record()
        if transport is not None and transport.quarantined:
            summary["handoff_quarantined"] = transport.quarantined
        if sink is not None:
            sink.write(summary)
    finally:
        if feeder_stop is not None:
            feeder_stop.set()
        if outbox is not None:
            outbox.close()
        # Mirror train.close_telemetry: called while an exception is
        # unwinding (sys.exc_info live inside a finally — the crash
        # fault's path), route through the flight recorder (crash_dump +
        # aborted summary) before disarming; a drained/finished run is
        # not a crash and closes clean.
        exc = sys.exc_info()
        if recorder is not None and exc[0] is not None \
                and not issubclass(exc[0], SystemExit):
            recorder.crash_dump(f"exception:{exc[0].__name__}",
                                exc_info=exc)
        if recorder is not None:
            recorder.close()
        if preempt is not None:
            preempt.close()
        obs.costmodel.set_default(None)
        obs.trace.set_default(None)
        parallel_state.set_mesh(None)
        if sink is not None:
            sink.close()

    counts = engine.counts
    if args.role == "decode":
        # The decode role's workload is whatever the transport fed it
        # (replica mode included — its inbox IS the spool).  A --steps
        # cap can strand requests mid-flight AND leave un-acked
        # handoffs in the spool (claims and files survive —
        # re-servable by the next worker — but THIS run did not finish
        # them).
        stranded = len(engine.pool.live) + transport.pending_on_disk()
        n_expected = len(completions) + stranded
    elif replica_mode:
        # A --steps-capped replica can run out of ticks with inbox
        # requests still queued or mid-decode; they reached no terminal
        # status and no outbox line, so exiting 0 would hide the loss
        # (review finding, ISSUE 12).
        stranded = engine.queue.pending() + len(engine.pool.live)
        n_expected = len(completions) + stranded
    else:
        n_expected = args.requests
        stranded = n_expected - len(completions)
    print(f"done: {counts['ok']}/{n_expected} completed  "
          f"out_tokens={summary['output_tokens']}  "
          f"tok/s={summary['tokens_per_sec']}  "
          f"steps={summary['steps']}  "
          f"occupancy={summary.get('occupancy', 0.0)}")
    if "speculate_k" in summary:
        print(f"spec: K={summary['speculate_k']} "
              f"draft={summary['draft_kind']}  "
              f"accepted {summary['tokens_accepted']}"
              f"/{summary['tokens_drafted']} drafted "
              f"({summary['acceptance_rate']:.1%})  "
              f"tokens/tick={summary.get('tokens_per_tick', 0.0)}")
    nonsuccess = {k: v for k, v in counts.items() if k != "ok" and v}
    if nonsuccess:
        print("statuses: " + "  ".join(f"{k}={v}" for k, v in
                                       sorted(nonsuccess.items()))
              + f"  availability={summary['availability']}")
    for name in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
        d = summary.get(name)
        if d:
            print(f"{name:14s} p50 {d['p50']:.1f}  p95 {d['p95']:.1f}  "
                  f"max {d['max']:.1f}")
    if rc == 0 and stranded:
        rc = 1
        print(f"WARNING: {stranded} request(s) unfinished at the --steps "
              f"cap", file=sys.stderr)
    return completions, summary, rc


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    _, _, rc = run_serve(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
