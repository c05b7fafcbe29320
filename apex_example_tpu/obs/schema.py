"""The telemetry JSONL schema — pure stdlib, importable without jax.

Every line a sink emits is one JSON object tagged by ``record``.

Version 1 (the happy path):

``run_header``   one per run, first line — identifies the run (id, argv,
                 config snapshot, device topology, platform).
``step``         one per train step — loss, loss scale, grad norm, step
                 wall time, items/sec, overflow accounting, memory.
``run_summary``  one per run, last line — first-step vs steady-state
                 step-time delta (the compile-time estimate), totals.
``bench``        one per bench.py measurement (the stdout JSON line's
                 sink twin).
``accuracy``     one per accuracy.py (seed, opt_level) cell.

Version 2 adds the diagnostics stratum (the failure path):

``crash_dump``      emitted by the flight recorder (obs/flight.py) on
                    abnormal exit — reason, traceback / thread stacks,
                    the last-K step records, registry snapshot, device
                    memory, config + environment.
``stall``           emitted by the stall watchdog (obs/watchdog.py) when
                    no step completes within the deadline — all-thread
                    stacks, seconds since the last step.
``overflow_event``  emitted by the numerics monitor (obs/numerics.py) —
                    names the top-level module(s) whose grads went
                    non-finite, with per-module counts and norms.

plus ``aborted``/``abort_reason`` on ``run_summary`` (a crashed run's
summary carries ``aborted: true``).  v2 is a strict superset of v1:
every v1 stream validates unchanged.

Version 3 adds the serving stratum (serve.py / serve/):

``request_complete``  one per finished inference request — token counts,
                      TTFT/TPOT, finish reason, slot/step provenance.
``serve_summary``     one per serving run, last line — request/token
                      totals, throughput, latency percentile dicts,
                      slot occupancy.

v3 is again a strict superset: every v1/v2 stream validates unchanged
(a serving stream carries a ``run_header`` but no ``run_summary`` —
``serve_summary`` is its closing record).

Version 4 adds the resilience stratum (resilience/; the recover path):

``preemption``  emitted by a ``--preempt-grace`` run that caught
                SIGTERM/SIGUSR1, saved a final checkpoint at the next
                step boundary and exited 75 (EX_TEMPFAIL) — the
                graceful counterpart of ``crash_dump`` (the run summary
                stays un-aborted).
``restart``     emitted by the auto-resume supervisor
                (tools/supervise.py) into its OWN stream when a child
                exits restartably — attempt index, exit code, reason
                (``preemption``/``crash``/``stall``), backoff, the
                child's last step.
``resume``      emitted by the supervisor when a launch attempt is
                rewritten to ``--resume`` an existing checkpoint.

plus ``restart_count``/``exit_code`` on ``run_summary`` (the
supervisor's closing record).  v4 is once more a strict superset: every
v1–v3 stream validates unchanged.

Version 5 adds the serving-resilience stratum (ISSUE 5: deadlines,
admission control, drain, serve-path faults):

``request_failed``  one per non-success request termination — status
                    ``timeout`` (deadline expired, queued or mid-
                    flight), ``cancelled``, or ``failed`` (slot-level
                    exception / degenerate-token guard, with the
                    traceback digest).
``shed``            one per request rejected by admission control
                    (``RequestQueue(max_pending=...)`` overflow).
``serve_drain``     emitted by a SIGTERM/SIGUSR1'd serve.py that
                    stopped admission, finished or deadline-evicted its
                    in-flight slots, handed queued requests back for
                    requeueing, and exited 75 (EX_TEMPFAIL) — the
                    serving counterpart of ``preemption``.

plus per-status counts (``completed``/``timed_out``/``shed``/
``cancelled``/``failed``/``drained``) and an ``availability`` ratio on
``serve_summary``.  v5 is once more a strict superset: every v1–v4
stream validates unchanged.

Version 6 adds the compiled-graph cost stratum (obs/costmodel.py;
``--cost-model`` on train.py / bench.py / serve.py):

``compile_event``  one per XLA compilation of an instrumented function
                   — lower/compile wall time, the lowering hash (the
                   compile-cache identity), and the per-name compile
                   ordinal ``n_compiles`` the recompile-regression
                   guard counts (a healthy run compiles each function
                   exactly once).
``cost_model``     the harvested ``cost_analysis()`` /
                   ``memory_analysis()`` for one compiled executable —
                   flops, HBM bytes accessed, transcendentals, buffer
                   sizes — plus the analytic roofline position
                   (arithmetic intensity, compute vs HBM time at the
                   peak constants, the binding-side verdict, MFU
                   ceiling).  Fields a backend omits are ``null``, not
                   absent (the CPU rig reports no generated-code size;
                   some backends omit whole analyses).

plus measured compile totals (``compile_ms_total``/``compile_events``)
on ``run_summary`` and the paged-KV waste baseline on
``serve_summary`` (``kv_bytes_reserved``/``kv_bytes_live``/
``slot_occupancy``/``kv_waste_pct``).  v6 is once more a strict
superset: every v1–v5 stream validates unchanged.
``tools/cost_report.py`` is the jax-free thin client that joins
``cost_model`` records against measured step times.

Version 7 adds the block-paged KV stratum (serve/slots.py; ISSUE 8) —
no new record types, new ``serve_summary`` fields:

``block_size``/``blocks_total``  the arena geometry (tokens per block,
                                 blocks per layer arena),
``blocks_live``                  per-tick histogram of arena blocks
                                 physically held by live slots,
``kv_bytes_committed``           per-tick histogram of admission-
                                 committed bytes (held + worst-case
                                 reserved blocks),
``prefix_hit_rate``              shared prompt tokens / total prompt
                                 tokens over every admission,
``cow_copies``                   copy-on-write block copies performed,
``rejected``                     requests terminated at admission as
                                 unservable (zero output budget) —
                                 ``request_failed`` gains the matching
                                 ``rejected`` status.

``kv_waste_pct`` becomes block-accurate (held-block bytes vs logically
live bytes; the dense layout's fixed full-page reservation measured
~92% on the smoke workload, the paged layout <= 40%).  v7 is once more
a strict superset: every v1–v6 stream validates unchanged.

Version 8 adds the static-analysis stratum's one record field
(tools/graftlint; ISSUE 9) — no new record types:

``recompile_cause``  on ``compile_event``, set from the second compile
                     of one name onward: the first structurally
                     divergent op between this lowering and the one it
                     replaced (graftlint's jax-free StableHLO diff), or
                     an explicit note that the programs are identical
                     (a dispatch-cache miss, not a graph change).  The
                     ``cost_report --fail-on-recompile`` gate prints it,
                     turning the recompile tally into a diagnosis.

v8 is once more a strict superset: every v1–v7 stream validates
unchanged.

Version 9 adds the trace-event stratum (obs/trace.py; ``--trace`` on
serve.py / train.py — README "Request tracing"):

``trace_event``  one timeline event: ``ph`` B/E (begin/end of a nested
                 region, matched stack-wise per ``tid`` row), X (a
                 complete span with ``dur``), or i (an instant);
                 ``ts``/``dur`` are MONOTONIC ``perf_counter`` seconds
                 — never wall-clock; ``span_id``/``parent_id`` build
                 the span tree, ``trace_id`` groups streams (a
                 supervised restart's attempt streams share one, via
                 the ``APEX_TRACE_ID`` env handoff).
``clock_sync``   exactly one per traced stream: a ``perf_counter``
                 reading (``ts``) paired with a back-to-back
                 ``time.time()`` (``time``) — the anchor
                 tools/trace_export.py uses to place streams (and an
                 xprof device trace) on one wall-clock axis.

Without ``--trace`` neither record is emitted — streams are
byte-identical to v8 runs.  v9 is once more a strict superset: every
v1–v8 stream validates unchanged.

Version 10 adds the fleet stratum (apex_example_tpu/fleet/; ``fleet.py``
— a jax-free router over N supervised serve replicas, README "Fleet
serving & chaos scenarios"):

``route``          one per router dispatch decision — which replica a
                   request was handed to, under which policy, on which
                   attempt, and why (``reason``: the initial dispatch,
                   a deadline-aware ``retry`` after a replica died, a
                   ``requeue_drain`` after a replica exited 75 and
                   handed its queued requests back, or a ``backlog``
                   drain once capacity returned).
``replica_state``  a replica health/lifecycle observation.  Emitted
                   from BOTH sides of the fence: a serve.py replica
                   (``--inbox`` mode) heartbeats its own
                   tick/pending/blocks_live/pid, and the router records
                   the transitions it acts on (healthy / stalled /
                   crashed / restarting / stopped), carrying the
                   supervisor's exit ``classification`` when one is
                   known.
``fleet_summary``  one per fleet run, last line of the router's stream
                   — request totals per terminal status, retry/requeue
                   accounting, ``lost`` (uids that never reached a
                   terminal status — the rolling-restart acceptance
                   pins this at 0), the fleet ``availability`` ratio
                   (ok / non-drained terminal across all replicas),
                   the per-replica breakdown and the routing-balance
                   stats, plus the scenario name + verdict when a
                   scripted chaos scenario drove the run.

plus ``classification`` on ``restart`` (the supervisor's verdict on how
the child died: ``preempted`` / ``crashed`` / ``stall_killed`` — so
fleet tooling distinguishes drains from crashes without re-parsing
child streams).  v10 is once more a strict superset: every v1–v9
stream validates unchanged.

Version 11 adds the quantization stratum (apex_example_tpu/quant/;
ISSUE 13 — ``--weight-quant``/``--kv-quant`` on serve.py,
``--quantized-allreduce`` on train.py):

``quant_event``  one per quantization application at startup — which
                 stratum quantized (``kind``: weights | kv), the
                 storage dtype, tensor/byte accounting and the scale
                 spread (the number the error bound is a multiple of).

plus precision fields on ``serve_summary``: ``kv_dtype`` /
``weight_dtype`` (the arena payload and weight storage dtypes — so
``kv_bytes_committed``/``kv_bytes_live`` are now interpretable as
DTYPE-ACCURATE bytes), and ``kv_bytes_per_token`` /
``kv_bytes_per_token_bf16`` (the actual vs bf16-equivalent per-token
cost; their ratio is the compression the serve_report QUANT line
renders and the ci_gate ``--quant-stream`` floor enforces).  v11 is
once more a strict superset: every v1–v10 stream validates unchanged.

Version 12 adds the sharded/disaggregated-serving stratum
(serve/disagg.py; ``--mesh dp,tp`` and ``--role prefill|decode`` on
serve.py):

``kv_handoff``  one per KV-cache handoff side: a prefill worker that
                chunk-prefilled a prompt into its paged arena and
                shipped the request's blocks (payload + int8 scales +
                fill level) emits ``direction: "out"``; the decode
                worker that scattered them into its own arena and
                took over decoding emits ``direction: "in"`` (with
                ``handoff_ms``, the out-stamp -> admission wall-clock
                transit, and ``requeued``, the times admission was
                deferred for free blocks).

plus sharding/role fields on ``serve_summary``: ``role`` (prefill /
decode / both), ``mesh`` / ``dp`` / ``tp`` (the registered serve mesh,
weights and KV arenas head-sharded over ``model``), and the handoff
accounting (``handoffs_out`` / ``handoffs_in`` / ``handoff_requeued``
/ ``handoff_bytes`` / ``handoff_ms`` percentiles); ``replica_state``
heartbeats gain ``kv_bytes_live`` (the dtype-accurate byte gauge the
fleet router's ``least_kv`` policy prefers over the raw block count).
v12 is once more a strict superset: every v1–v11 stream validates
unchanged.

Version 13 adds the crash-safe handoff stratum (ISSUE 15 —
serve/disagg.py's leased spool protocol and the disagg fleet
scenarios); no new record types:

``kv_handoff`` grows the lease/redelivery story: ``direction`` gains
the value "quarantine" (a corrupt/truncated payload parked at
``*.bad`` — the worker stays alive; ``spool_file``/``error`` name the
evidence), ``redelivered`` counts deliveries from a reclaimed or
adopted lease, and ``duplicate: true`` marks an idempotent re-admission
(the decode engine had already admitted the uid — the ack-crash window
— so nothing was scattered twice).  ``serve_summary`` gains
``handoff_duplicates`` / ``handoff_redelivered`` /
``handoff_quarantined``; ``replica_state`` heartbeats gain ``role``;
``fleet_summary`` gains the disagg topology + spool accounting
(``prefill_replicas`` / ``decode_replicas`` / ``handoffs`` /
``handoff_redelivered`` / ``in_spool``).  v13 is once more a strict
superset: every v1–v12 stream validates unchanged.

Version 14 adds the streaming SLO stratum (obs/slo.py; ``--slo`` on
serve.py / fleet.py — README "SLO monitoring"):

``slo_window``   one per closed tumbling window (every
                 ``--slo-window-s`` wall seconds or
                 ``--slo-window-ticks`` engine ticks on serve.py; every
                 ``--slo-window`` terminal events on the fleet router)
                 — good/bad event counts scored against the ``--slo``
                 spec, the window's error-budget ``burn_rate``
                 (bad fraction / (1 - availability)), per-status
                 counts, TTFT/TPOT/queue-wait percentile estimates
                 from the window's log-bucket sketch (relative-error
                 bound ``alpha``), and the latest
                 blocks_live/kv_bytes_live/occupancy gauge snapshot.
``slo_breach``   one per window whose burn rate exceeds 1.0 — the
                 window spent more than its whole error budget; names
                 the window and its burn/good/bad/budget numbers so an
                 alerting tail never needs the full stream.
``fleet_rollup``  the router's live cross-replica aggregation, one per
                 rollup interval: replica heartbeat sketches
                 (``replica_state.slo_sketch``) merged by bucket-count
                 addition into fleet-wide TTFT/TPOT percentiles, plus
                 the per-replica p50 breakdown, the max/median p50
                 ``skew`` and the worst replica's name (``straggler``)
                 — the live form of what fleet_report finds post-hoc.

plus ``slo_sketch`` on ``replica_state`` heartbeats (the compact
serialized cumulative sketch the rollup merges), an ``slo`` dict on
``serve_summary`` (spec, window/breach totals, worst burn, cumulative
sketch percentiles), and the fleet verdict fields on ``fleet_summary``
(``slo_verdict`` pass|fail, ``slo_windows`` / ``slo_breaches`` /
``slo_worst_burn`` / ``slo_worst_window``) the chaos scenarios score.
Without ``--slo`` none of these are emitted — streams are
byte-identical to v13 runs.  v14 is once more a strict superset: every
v1–v13 stream validates unchanged.

Version 15 adds the hot-path overhead stratum (obs/tickprof.py;
``--tick-profile`` on serve.py / train.py — README "Hot-path
profiling"):

``tick_profile``      one per sampled tick/step (every
                      ``--tick-profile-every``-th) — the tick's phase
                      decomposition in milliseconds (serve: admit /
                      dispatch_enqueue / device_wait / harvest /
                      spool_io / telemetry; train: data_wait /
                      dispatch / device / checkpoint / telemetry), the
                      measured wall time, and ``host_gap_ms`` = wall
                      minus the device phase.  Carries a perf_counter
                      ``ts`` so trace_export renders a host-gap
                      counter track.
``overhead_summary``  one per run — per-phase cumulative totals +
                      log-bucket sketch summaries, cumulative wall /
                      device / host-gap milliseconds and the
                      ``host_overhead_frac`` tools/perf_ledger.py
                      regression-gates against PERF_BASELINE.json.

plus idle-spin accounting on ``serve_summary`` (``idle_ticks`` /
``idle_wait_ms`` — producer-driven runs that sleep in ``engine.run``
now show how much wall time was idle) and ``host_overhead_frac`` on
``serve_summary`` and ``replica_state`` heartbeats (fleet_report names
the worst-overhead replica).  Without ``--tick-profile`` only the idle
counters are new; v15 is once more a strict superset: every v1–v14
stream validates unchanged.

Version 16 adds the speculative-decoding ledger on ``serve_summary``
(apex_example_tpu/spec/; ``--speculate K`` on serve.py — README
"Speculative decoding"): ``speculate_k`` / ``draft_kind`` name the
armed configuration, ``tokens_drafted`` / ``tokens_accepted`` /
``tokens_sampled`` count draft lanes fed, draft lanes verified-and-kept
and model-sampled tokens (bonus lanes + plain-path samples), and
``acceptance_rate`` / ``tokens_per_tick`` are the derived headline
ratios (accepted/drafted; output_tokens/compute_steps — the decode-side
metric that breaks the one-token-per-tick wall).  Conservation is
checkable from the summary alone: ``tokens_accepted <= tokens_drafted``
and ``output_tokens == tokens_accepted + tokens_sampled`` (ci_gate
``--spec-stream``).  Emitted ONLY when speculation is armed — an
unarmed run's stream is byte-identical to v15 output, and v16 is once
more a strict superset: every v1–v15 stream validates unchanged.

Version 17 adds the multi-tenant scheduling stratum
(apex_example_tpu/sched/; ``--tenants`` on serve.py / fleet.py —
README "Multi-tenant scheduling & prefix-affinity routing"):

- ``tenant`` on ``request_complete`` / ``request_failed`` / ``shed``
  names the lane the request was filed under;
- ``tenants`` on ``serve_summary`` is the engine's per-tenant
  scheduling ledger (weight, slo_class, admitted_tokens, budget,
  per-status counts), and on ``fleet_summary`` the router's
  per-tenant verdict block (per-status counts, availability, an
  ``slo_verdict`` per tenant with an SLO spec, admitted_tokens /
  budget folded from replica heartbeats);
- ``prefix_keys`` / ``prefix_shared_tokens`` / ``prefix_prompt_tokens``
  on ``replica_state`` advertise the replica's hottest prefix
  chain-key hashes (sched/prefix.py digests, top-N by block refcount)
  and its raw prefix-reuse counters (``--advertise-prefixes``), the
  inputs to the ``prefix_affinity`` router policy;
- ``tenant_admitted`` on ``replica_state`` carries the engine's
  per-tenant admitted-token totals so the router can account budgets
  fleet-wide;
- ``prefix_hit_rate`` on ``fleet_summary`` is the exact fleet-level
  ratio (sum of advertised shared tokens / sum of prompt tokens).

All emitted ONLY when tenancy / prefix advertisement is armed — an
unarmed run's stream is byte-identical to v16 output, and v17 is once
more a strict superset: every v1–v16 stream validates unchanged.

Version 18 adds the live-migration + elastic-pool stratum (ISSUE 20 —
``ServeEngine.extract_live``/``admit_migrated``, drain-without-eviction
and the fleet autoscaler):

``kv_migration``  one per live-migration side: the source engine that
                  snapshotted a MID-FLIGHT request (arena blocks at the
                  committed cursor, generated tokens, sampler state)
                  emits ``direction: "out"`` with ``tokens_generated``;
                  the destination that scattered the payload and
                  resumed decoding emits ``direction: "in"`` (with
                  ``migration_ms`` transit, ``requeued`` deferral
                  episodes, and the same ``redelivered``/``duplicate``
                  lease-crash provenance ``kv_handoff`` carries — the
                  payloads ride the identical leased spool protocol).

plus the migration ledger on ``serve_summary`` (``migrations_out`` /
``migrations_in`` / ``migration_requeued`` / ``migration_duplicates``
/ ``migration_redelivered`` / ``migration_bytes`` / ``migration_ms``
percentiles), ``migrated`` on ``serve_drain`` (a migrating drain ships
its in-flight slots instead of ticking them out — evictions stay 0),
and the fleet-side counters on ``fleet_summary`` (``migrations`` /
``migration_completed`` — uids shipped mid-flight and their eventual
terminals — and ``scale_up_events`` / ``scale_down_events`` from the
elastic pool controller).  All emitted ONLY when migration/autoscale
traffic actually happened — a migration-free run's stream is
byte-identical to v17 output, and v18 is once more a strict superset:
every v1–v17 stream validates unchanged.

``validate_record`` is the single source of truth consumed by
``tools/metrics_lint.py`` and the tier-1 smoke test; extending the schema
means extending the tables here, nowhere else.  (The supervisor carries
a hard-coded copy of SCHEMA_VERSION — resilience/supervisor.py is
jax-free by contract and must not import the package.)
"""

from __future__ import annotations

from typing import Any, Dict, List

SCHEMA_VERSION = 18

_NUM = (int, float)
# v6 cost fields degrade to null where a backend omits the analysis —
# the record still lands, consumers see an explicit null, and a typo'd
# field name is still rejected (unknown fields stay errors).
_NUM_OR_NULL = (int, float, type(None))

# record type -> {field: allowed python types}; None in OPTIONAL means any.
REQUIRED: Dict[str, Dict[str, Any]] = {
    "run_header": {
        "record": str,
        "schema": int,
        "time": _NUM,
        "run_id": str,
        "num_devices": int,
        "process_index": int,
        "platform": str,
        "config": dict,
    },
    "step": {
        "record": str,
        "step": int,
        "epoch": int,
        "loss": _NUM,
        "scale": _NUM,
        "step_time_ms": _NUM,
        "items_per_sec": _NUM,
    },
    "run_summary": {
        "record": str,
        "steps": int,
        "overflow_count": int,
    },
    "bench": {
        "record": str,
        "metric": str,
        "value": _NUM,
        "unit": str,
    },
    "accuracy": {
        "record": str,
        "opt_level": str,
        "top1": _NUM,
    },
    # --- schema v2: diagnostics records (failure-path observability) ---
    "crash_dump": {
        "record": str,
        "time": _NUM,
        "reason": str,
    },
    "stall": {
        "record": str,
        "time": _NUM,
        "seconds_since_step": _NUM,
    },
    "overflow_event": {
        "record": str,
        "time": _NUM,
        "step": int,
        "modules": list,
    },
    # --- schema v3: serving records (serve.py / serve/engine.py) ---
    "request_complete": {
        "record": str,
        "time": _NUM,
        "request_id": str,
        "prompt_tokens": int,
        "output_tokens": int,
        "ttft_ms": _NUM,
        "tpot_ms": _NUM,
        "finish_reason": str,
    },
    "serve_summary": {
        "record": str,
        "time": _NUM,
        "requests": int,
        "output_tokens": int,
        "tokens_per_sec": _NUM,
    },
    # --- schema v4: resilience records (the recover path) ---
    "preemption": {
        "record": str,
        "time": _NUM,
        "signal": str,
        "step": int,
    },
    "restart": {
        "record": str,
        "time": _NUM,
        "attempt": int,
        "exit_code": int,
        "reason": str,
    },
    "resume": {
        "record": str,
        "time": _NUM,
        "attempt": int,
    },
    # --- schema v5: serving-resilience records (serve.py / serve/) ---
    "request_failed": {
        "record": str,
        "time": _NUM,
        "request_id": str,
        "status": str,          # timeout | cancelled | failed | rejected
    },
    "shed": {
        "record": str,
        "time": _NUM,
        "request_id": str,
        "reason": str,          # queue_full
    },
    "serve_drain": {
        "record": str,
        "time": _NUM,
        "signal": str,
    },
    # --- schema v6: compiled-graph cost records (obs/costmodel.py) ---
    "compile_event": {
        "record": str,
        "time": _NUM,
        "name": str,            # the instrumented function's name
        "compile_ms": _NUM,
    },
    "cost_model": {
        "record": str,
        "time": _NUM,
        "name": str,
    },
    # --- schema v9: trace-event records (obs/trace.py; --trace) ---
    "trace_event": {
        "record": str,
        "ph": str,              # B | E | X | i
        "name": str,
        "ts": _NUM,             # perf_counter seconds (monotonic)
    },
    "clock_sync": {
        "record": str,
        "time": _NUM,           # wall clock (time.time())
        "ts": _NUM,             # perf_counter taken back-to-back
    },
    # --- schema v10: fleet records (apex_example_tpu/fleet/; fleet.py) ---
    "route": {
        "record": str,
        "time": _NUM,
        "request_id": str,
        "replica": str,         # the replica the request was handed to
    },
    "replica_state": {
        "record": str,
        "time": _NUM,
        "replica": str,
        "state": str,           # serving|draining|healthy|stalled|
    },                          #   crashed|restarting|stopped
    "fleet_summary": {
        "record": str,
        "time": _NUM,
        "replicas": int,
        "requests": int,
        "availability": _NUM,   # ok / non-drained terminal, fleet-wide
    },
    # --- schema v11: quantization records (apex_example_tpu/quant/) ---
    "quant_event": {
        "record": str,
        "time": _NUM,
        "kind": str,            # weights | kv
        "dtype": str,           # int8 | float8_e4m3 | fp8_e4m3_emulated
    },
    # --- schema v12: disaggregated-serving records (serve/disagg.py) ---
    "kv_handoff": {
        "record": str,
        "time": _NUM,
        "request_id": str,
        "direction": str,       # out (prefill -> transport) | in
        "fill": int,            # tokens of KV in the payload
        "blocks": int,          # arena blocks in the payload
        "payload_bytes": int,   # payload + scale bytes, dtype-accurate
    },
    # --- schema v18: live-migration records (ISSUE 20) ---
    "kv_migration": {
        "record": str,
        "time": _NUM,
        "request_id": str,
        "direction": str,       # out (source -> transport) | in
        "fill": int,            # tokens of committed KV in the payload
        "blocks": int,          # arena blocks in the payload
        "payload_bytes": int,   # payload + scale bytes, dtype-accurate
    },
    # --- schema v14: streaming SLO records (obs/slo.py; --slo) ---
    "slo_window": {
        "record": str,
        "time": _NUM,
        "window": int,          # tumbling-window ordinal, 0-based
        "requests": int,        # terminal events folded this window
        "good": int,            # ok AND every spec'd latency in target
        "bad": int,             # everything else the server owned
        "burn_rate": _NUM,      # bad fraction / (1 - availability)
    },
    "slo_breach": {
        "record": str,
        "time": _NUM,
        "window": int,          # the slo_window that overspent
        "burn_rate": _NUM,      # > 1.0 by definition
        "requests": int,
        "bad": int,
    },
    "fleet_rollup": {
        "record": str,
        "time": _NUM,
        "replicas": int,        # replicas contributing a sketch
        "count": int,           # merged TTFT observations, fleet-wide
    },
    # --- schema v15: hot-path overhead records (obs/tickprof.py) ---
    "tick_profile": {
        "record": str,
        "time": _NUM,
        "ts": _NUM,             # perf_counter at tick start (trace
        "kind": str,            #   clock domain); serve | train
        "tick": int,            # engine tick / train step ordinal
        "wall_ms": _NUM,        # independently measured tick wall time
        "host_gap_ms": _NUM,    # wall - device phase
        "phases": dict,         # phase -> milliseconds (sum == wall
    },                          #   within 1%; perf_ledger enforces)
    "overhead_summary": {
        "record": str,
        "time": _NUM,
        "kind": str,            # serve | train
        "ticks": int,           # ticks folded (every tick, not sampled)
        "wall_ms": _NUM,        # cumulative
        "device_ms": _NUM,      # cumulative device-phase time
        "host_gap_ms": _NUM,    # wall_ms - device_ms
        "host_overhead_frac": _NUM,   # host_gap_ms / wall_ms
        "phases": dict,         # phase -> {count,p50,p90,p99,min,max,
    },                          #   total_ms} sketch summaries
}

OPTIONAL: Dict[str, Dict[str, Any]] = {
    "run_header": {"argv": list, "num_processes": int, "arch": str},
    "step": {
        "grad_norm": _NUM,
        "grads_finite": _NUM,
        "overflow_count": int,
        "top1": _NUM,
        "ppl": _NUM,
        "masked_acc": _NUM,
        "lr": _NUM,
        # rows the masked-LM head ran on (engine.make_train_step, a loss
        # over rows); only such a step's records carry it
        "head_rows": _NUM,
        "time": _NUM,
        "memory": dict,
        "spans": dict,
    },
    "run_summary": {
        "first_step_ms": _NUM,
        "steady_step_ms": _NUM,
        "compile_est_ms": _NUM,
        "items_per_sec": _NUM,
        "time": _NUM,
        "spans": dict,
        # v2: a crashed/killed run's summary is marked, not absent.
        "aborted": bool,
        "abort_reason": str,
        # v4: the supervisor's closing record (tools/supervise.py).
        "restart_count": int,
        "exit_code": int,
        # v6: measured compile totals (obs/costmodel.py) — the
        # first-vs-steady compile_est_ms above becomes a cross-check,
        # not the only source.
        "compile_events": int,
        "compile_ms_total": _NUM,
    },
    "bench": {"vs_baseline": _NUM, "mfu_pct": _NUM, "time": _NUM,
              "config": dict},
    "accuracy": {"seed": int, "eval_loss": _NUM, "final_train_loss": _NUM,
                 "train_seconds": _NUM, "time": _NUM},
    "crash_dump": {
        "run_id": str,
        "step": int,            # last completed step at dump time
        "traceback": str,       # uncaught-exception path
        "thread_stacks": str,   # signal path: all-thread stack dump
        "last_steps": list,     # the flight recorder's bounded ring
        "registry": dict,       # MetricsRegistry.snapshot()
        "memory": dict,         # device_memory_stats() subset
        "env": dict,            # python/platform/jax versions, argv
        "config": dict,         # JSON-safe argparse snapshot
    },
    "stall": {
        "run_id": str,
        "step": int,            # last completed step before the stall
        "deadline_s": _NUM,
        "thread_stacks": str,
        "trace_dir": str,       # set when a one-shot profiler window armed
    },
    "overflow_event": {
        "run_id": str,
        "module_stats": dict,   # {module: {nonfinite, grad_norm}}
        "scale": _NUM,
        "loss": _NUM,
        "mode": str,            # the --numerics-check mode that fired
    },
    "request_complete": {
        "run_id": str,
        "slot": int,            # the slot the request decoded in
        "queue_wait_ms": _NUM,  # arrival -> admission
        "e2e_ms": _NUM,         # arrival -> completion
        "admitted_step": int,   # engine tick provenance (interleaving
        "finished_step": int,   #   audits key on these)
        "temperature": _NUM,
        "top_k": int,
        "tenant": str,          # v17: the scheduling lane (--tenants)
    },
    "serve_summary": {
        "run_id": str,
        "steps": int,           # engine ticks (incl. idle virtual-time)
        "compute_steps": int,   # ticks that ran the decode program
        "slots": int,
        "max_len": int,
        "duration_s": _NUM,
        "occupancy": _NUM,      # mean live-slot fraction per compute step
        "ttft_ms": dict,        # {p50, p95, max} nearest-rank
        "tpot_ms": dict,
        "queue_wait_ms": dict,
        "aborted": bool,
        "abort_reason": str,
        # v5: per-status accounting ("requests" stays the terminal total)
        "completed": int,       # status ok
        "timed_out": int,       # deadline expired (queued or mid-flight)
        "shed": int,            # rejected by admission control
        "cancelled": int,
        "failed": int,          # slot-level exception / token guard
        "drained": int,         # requeued by a graceful drain
        "availability": _NUM,   # ok / every status the server owned
        # v6: KV occupancy — arena-lifetime reserved bytes vs what live
        # requests actually fill, per compute tick.
        "kv_bytes_reserved": int,   # full arena allocation (constant)
        "kv_bytes_live": dict,      # per-tick filled-bytes histogram
        "slot_occupancy": dict,     # per-tick live-slot histogram
        "kv_waste_pct": _NUM,       # v7: 100 * (1 - live / held-block
                                    #   bytes), block-accurate
        # v7: the block-paged KV stratum (serve/slots.py; ISSUE 8)
        "block_size": int,          # tokens per arena block
        "blocks_total": int,        # blocks per layer arena
        "blocks_live": dict,        # per-tick held-blocks histogram
        "kv_bytes_committed": dict,  # per-tick held+reserved bytes
        "prefix_hit_rate": _NUM,    # shared / total prompt tokens
        "cow_copies": int,          # copy-on-write block copies
        "rejected": int,            # unservable, rejected at admission
        # v11: the precision story (quant stratum, ISSUE 13) — the
        # byte gauges above are dtype-accurate against these fields.
        "kv_dtype": str,            # arena payload dtype ("int8" armed)
        "weight_dtype": str,        # weight storage mode/dtype
        "kv_bytes_per_token": int,  # actual (scales included)
        "kv_bytes_per_token_bf16": int,  # bf16-equivalent baseline
        # v12: sharded + disaggregated serving (serve/disagg.py)
        "role": str,                # both | prefill | decode
        "mesh": str,                # "data=D,model=T" when sharded
        "dp": int,                  # mesh data-axis size
        "tp": int,                  # mesh model-axis size
        "handoffs_out": int,        # prefill: requests handed off
        "handoffs_in": int,         # decode: handoffs admitted
        "handoff_requeued": int,    # decode: handoffs that had to wait
                                    #   for free slots/blocks (episodes,
                                    #   not retry attempts)
        "handoff_bytes": int,       # payload bytes moved, this role
        "handoff_ms": dict,         # decode: transit percentiles
        # v13: the crash-safe leased-spool story (ISSUE 15)
        "handoff_duplicates": int,   # idempotent re-admissions acked
        "handoff_redelivered": int,  # uids admitted from a reclaimed
                                     #   or adopted lease
        "handoff_quarantined": int,  # corrupt payloads parked at *.bad
        # v14: the streaming SLO fold (obs/slo.py; --slo) — spec,
        # window/breach totals, worst burn, cumulative sketch
        # percentiles.  Absent without --slo.
        "slo": dict,
        # v15: idle-spin accounting (engine.run idle_wait_s sleeps are
        # now observed) + the cumulative host-overhead fraction from
        # the armed tick profiler (absent without --tick-profile).
        "idle_ticks": int,          # step() calls with nothing live
        "idle_wait_ms": _NUM,       # wall time slept between them
        "host_overhead_frac": _NUM,  # (wall - device) / wall, run-wide
        # v16: the speculative-decoding ledger (spec/; --speculate K).
        # Absent unless speculation armed — unarmed streams stay
        # byte-identical to v15.  Conservation: accepted <= drafted and
        # output_tokens == tokens_accepted + tokens_sampled.
        "speculate_k": int,         # armed draft depth K
        "draft_kind": str,          # proposer name (ngram | none | ...)
        "tokens_drafted": int,      # draft lanes fed for verification
        "tokens_accepted": int,     # draft lanes verified and kept
        "tokens_sampled": int,      # model-sampled tokens (bonus lanes
                                    #   + plain/sampled-path tokens)
        "acceptance_rate": _NUM,    # accepted / drafted (0.0 if none)
        "tokens_per_tick": _NUM,    # output_tokens / compute_steps
        # v17: the per-tenant scheduling ledger (sched/; --tenants).
        # Absent unless tenancy armed — unarmed streams stay
        # byte-identical to v16.
        "tenants": dict,            # name -> {weight, slo_class,
                                    #   admitted_tokens, budget?,
                                    #   per-status counts}
        # v18: the live-migration ledger (ISSUE 20).  Every field gated
        # on actual migration traffic — migration-free streams stay
        # byte-identical to v17.
        "migrations_out": int,      # live slots shipped mid-flight
        "migrations_in": int,       # migrated requests resumed here
        "migration_requeued": int,  # deferred-admission episodes
        "migration_duplicates": int,   # idempotent re-admissions acked
        "migration_redelivered": int,  # uids admitted from a reclaimed
                                       #   or adopted lease
        "migration_bytes": int,     # payload bytes moved, both sides
        "migration_ms": dict,       # in side: transit percentiles
        # lane packing's token budget (ops/lane_pack.py): present only
        # where the engine of a model with packed lanes left a chunk
        # waiting — every other stream is byte-identical.
        "prefill_chunks_deferred": int,  # chunks left waiting a tick
        "prefill_ticks_deferring": int,  # ticks that left any waiting
        # hand-offs between the tick's host thread and the runtime (the
        # key, the put, the step's call, fetches), mean a tick that ran
        "runtime_handoffs_per_tick": _NUM,
    },
    "preemption": {
        "run_id": str,
        "checkpoint_step": int,  # step of the grace-path final save
        "saved": bool,           # False: no --checkpoint-dir to save to
    },
    "restart": {
        "run_id": str,
        "backoff_s": _NUM,
        "last_step": int,        # tailed from the child's metrics JSONL
        "checkpoint_step": int,  # latest checkpoint at restart time
        # v10: how the child died, as the supervisor saw it — fleet
        # tooling keys on this instead of re-parsing child streams.
        "classification": str,   # preempted | crashed | stall_killed
    },
    "resume": {
        "run_id": str,
        "checkpoint_step": int,  # the step the attempt resumes from
        "resume_dir": str,
    },
    "request_failed": {
        "run_id": str,
        "slot": int,             # only when the request was admitted
        "admitted_step": int,
        "failed_step": int,      # engine tick of the termination
        "prompt_tokens": int,
        "output_tokens": int,    # partial output kept at eviction
        "queue_wait_ms": _NUM,
        "e2e_ms": _NUM,
        "error": str,            # traceback digest (status "failed")
        "tenant": str,           # v17: the scheduling lane (--tenants)
    },
    "shed": {
        "run_id": str,
        "step": int,             # engine tick of the rejection
        "pending": int,          # ARRIVED backlog after the shed (what
        "max_pending": int,      #   the tripped bound actually counts)
        "tenant": str,           # v17: the scheduling lane (--tenants)
    },
    "serve_drain": {
        "run_id": str,
        "step": int,             # tick the drain began
        "in_flight": int,        # live slots at drain start
        "completed": int,        # in-flight that finished during drain
        "evicted": int,          # in-flight deadline-evicted/failed
        "requeued": int,         # queued handed back (status "drained")
        "requeued_ids": list,
        "migrated": int,         # v18: in-flight shipped mid-flight by
                                 #   a migrating drain (evictions == 0)
    },
    "compile_event": {
        "run_id": str,
        "lower_ms": _NUM,        # trace+lower wall time (compile_ms is
        "n_compiles": int,       #   the XLA backend compile alone)
        "lowering_hash": str,    # StableHLO digest: the compile-cache
        "platform": str,         #   identity recompile forensics join on
        # v8: the recompile-cause diff (graftlint HLO stratum) — only on
        # n_compiles >= 2 events: the first divergent op vs the previous
        # lowering of the same name.
        "recompile_cause": str,
    },
    "cost_model": {
        "run_id": str,
        "lowering_hash": str,          # joins to its compile_event
        # cost_analysis(); null where the backend omits the analysis
        "flops": _NUM_OR_NULL,
        "bytes_accessed": _NUM_OR_NULL,
        "transcendentals": _NUM_OR_NULL,
        # memory_analysis(); null where omitted (CPU backend)
        "argument_bytes": _NUM_OR_NULL,
        "output_bytes": _NUM_OR_NULL,
        "temp_bytes": _NUM_OR_NULL,
        "generated_code_bytes": _NUM_OR_NULL,
        # the roofline position at the peak constants below
        "peak_flops": _NUM,
        "hbm_gbps": _NUM,
        "arithmetic_intensity": _NUM,  # flops / bytes_accessed
        "ridge_flops_per_byte": _NUM,  # peak_flops / (hbm_gbps * 1e9)
        "compute_ms": _NUM,            # flops / peak_flops
        "hbm_ms": _NUM,                # bytes_accessed / bandwidth
        "analytic_min_ms": _NUM,       # max(compute_ms, hbm_ms)
        "roofline": str,               # compute-bound | hbm-bound
        "mfu_ceiling_pct": _NUM,       # MFU the intensity admits
    },
    "trace_event": {
        "run_id": str,
        "dur": _NUM,            # X only: span length, perf seconds
        "cat": str,             # coarse category (tick/request/span)
        "tid": str,             # logical thread row within the stream
        "span_id": str,         # stream-local span identity
        "parent_id": str,       # span tree edge (same stream)
        "trace_id": str,        # groups streams into one timeline
        "args": dict,           # slot / blocks / status annotations
    },
    "clock_sync": {
        "run_id": str,
        "trace_id": str,
    },
    # --- schema v10: fleet records (apex_example_tpu/fleet/) ---
    "route": {
        "run_id": str,
        "policy": str,           # round_robin | least_pending | least_kv
        "attempt": int,          # 0 = first dispatch of this uid
        "reason": str,           # dispatch | retry | requeue_drain |
        "from_replica": str,     #   backlog; the replica being left on
    },                           #   a retry/requeue
    "replica_state": {
        "run_id": str,
        "tick": int,             # the replica's engine tick counter
        "pending": int,          # its queued-request backlog
        "blocks_live": int,      # KV arena blocks held (least_kv input)
        "kv_bytes_live": int,    # v12: dtype-accurate KV bytes live —
                                 #   what least_kv prefers when present
        "role": str,             # v13: both | prefill | decode
        "pid": int,              # serve-child pid (chaos scripts signal it)
        "attempt": int,          # supervisor attempt index, when known
        "exit_code": int,        # with state crashed/restarting
        "classification": str,   # preempted | crashed | stall_killed
        "detail": str,
        "slo_sketch": dict,      # v14: compact serialized cumulative
                                 #   TTFT/TPOT sketches (--slo armed) —
                                 #   what fleet_rollup merges
        "host_overhead_frac": _NUM,  # v15: the replica's cumulative
                                     #   host-overhead fraction
                                     #   (--tick-profile armed) —
                                     #   fleet_report ranks these
        # v17: prefix-cache advertisement (--advertise-prefixes) — the
        # hot chain-key digests prefix_affinity routing scores against,
        # plus the raw reuse counters the fleet hit rate is exact over.
        "prefix_keys": list,         # top-N sched/prefix.py digests,
                                     #   hottest (highest refcount) first
        "prefix_shared_tokens": int,  # prompt tokens served from the
                                      #   prefix index, cumulative
        "prefix_prompt_tokens": int,  # prompt tokens admitted, cumulative
        "tenant_admitted": dict,      # v17: tenant -> admitted tokens
                                      #   (--tenants armed)
    },
    # --- schema v11: quantization records (apex_example_tpu/quant/) ---
    "quant_event": {
        "run_id": str,
        "tensors": int,          # leaves quantized (weights kind)
        "kept": int,             # leaves kept high-precision
        "bytes_before": int,
        "bytes_after": int,
        "scale_min": _NUM,       # per-channel/block scale spread —
        "scale_max": _NUM,       #   the error bound's multiplier
        "emulated": bool,        # fp8 without native jnp support
        "block_size": int,       # kv kind: scale granularity (tokens)
        "scale_dtype": str,      # kv kind: block-scale storage dtype
    },
    # --- schema v12: disaggregated-serving records (serve/disagg.py) ---
    "kv_handoff": {
        "run_id": str,
        "kv_dtype": str,         # arena payload dtype in the payload
        "prompt_tokens": int,
        "first_token": int,      # the prefill-side sampled first token
        "ttft_ms": _NUM,         # out only: the REAL first-token
                                 #   latency (measured where the first
                                 #   token was sampled — the decode
                                 #   side's request_complete can only
                                 #   see its own clock domain)
        "queue_wait_ms": _NUM,   # out only: prefill-side queue wait
        "src": str,              # role/replica ids, when known
        "dst": str,
        "handoff_ms": _NUM,      # in only: out-stamp -> admission wall
        "requeued": int,         # in only: deferred-admission count
        # v13 (ISSUE 15): the leased-spool crash-safety story
        "redelivered": int,      # in only: delivery came from a
                                 #   reclaimed/adopted lease
        "duplicate": bool,       # in only: uid already admitted — the
                                 #   ack-crash window closing (acked,
                                 #   nothing scattered twice)
        "spool_file": str,       # quarantine only: the parked payload
        "error": str,            # quarantine only: why it failed
    },
    "fleet_summary": {
        "run_id": str,
        "policy": str,
        "scenario": str,         # rolling_restart | crash_storm | ...
        "verdict": str,          # pass | fail (the scenario's score)
        "duration_s": _NUM,
        "completed": int,        # per-status fleet totals ("requests"
        "failed": int,           #   stays the submitted total)
        "timed_out": int,
        "shed": int,
        "cancelled": int,
        "rejected": int,
        "drained_requeued": int,  # requeue-on-drain handoffs performed
        "retries": int,           # deadline-aware re-dispatches
        "duplicates": int,        # late/duplicate terminal reports ignored
        "lost": int,              # uids with NO terminal status (must be 0)
        "per_replica": dict,      # name -> per-status breakdown
        "routing": dict,          # dispatch counts + balance skew
        # v13 (ISSUE 15): disagg topology + leased-spool accounting
        "prefill_replicas": int,  # role=prefill handles in the fleet
        "decode_replicas": int,   # role=decode handles in the fleet
        "handoffs": int,          # uids parked on the KV spool
        "handoff_redelivered": int,  # terminals from redelivered
                                     #   handoff admissions
        "in_spool": int,          # uids still on the spool at close
                                  #   (counted in lost; must be 0)
        # v14 (ISSUE 16): the fleet SLO verdict — event-count tumbling
        # windows over the router's terminal feed, scored against the
        # --slo spec.  Absent without --slo.
        "slo_verdict": str,       # pass | fail (any breached window)
        "slo_windows": int,       # windows scored (trailing partial in)
        "slo_breaches": int,      # windows with burn_rate > 1.0
        "slo_worst_burn": _NUM,   # max window burn rate
        "slo_worst_window": int,  # its 0-based index (first on ties)
        # v17 (ISSUE 19): the multi-tenant verdict block + fleet-level
        # prefix reuse.  Absent unless tenancy / prefix advertisement
        # is armed.
        "tenants": dict,          # name -> {per-status counts,
                                  #   availability, slo_verdict?,
                                  #   admitted_tokens?, budget?}
        "prefix_hit_rate": _NUM,  # sum advertised shared / prompt
                                  #   tokens across replicas
        # v18 (ISSUE 20): live migration + elastic pools.  Absent
        # unless migrations/autoscaling actually happened.
        "migrations": int,        # uids shipped mid-flight (out events)
        "migration_completed": int,  # migrated uids that reached a
                                     #   terminal status afterwards
        "migration_redelivered": int,  # terminals from redelivered
                                       #   migration admissions
        "rebalance_migrations": int,  # migrations the router's
                                      #   KV-pressure policy asked for
        "scale_up_events": int,   # elastic-pool replica spawns
        "scale_down_events": int,  # elastic-pool replica retirements
    },
    # --- schema v14: streaming SLO records (obs/slo.py; --slo) ---
    "slo_window": {
        "run_id": str,
        "counts": dict,          # terminal counts by status (drained
                                 #   included — outside good/bad)
        "ttft_ms": dict,         # window sketch percentile estimates
        "tpot_ms": dict,         #   ({count,p50,p90,p99,min,max}),
        "queue_wait_ms": dict,   #   ok completions only
        "ticks": int,            # engine ticks folded (serve side)
        "occupancy": _NUM,       # mean live-slot fraction over ticks
        "blocks_live": int,      # latest KV gauge snapshot in-window
        "kv_bytes_live": int,
    },
    "slo_breach": {
        "run_id": str,
        "good": int,
        "budget": _NUM,          # the error budget (1 - availability)
    },
    "fleet_rollup": {
        "run_id": str,
        "ttft_ms": dict,         # merged-sketch percentile estimates
        "tpot_ms": dict,
        "per_replica": dict,     # name -> {count, p50}
        "skew": _NUM,            # max p50 / median p50 (>= 2 replicas)
        "straggler": str,        # the max-p50 replica's name
    },
    # --- schema v18: live-migration records (ISSUE 20) ---
    "kv_migration": {
        "run_id": str,
        "kv_dtype": str,         # arena payload dtype in the payload
        "prompt_tokens": int,
        "tokens_generated": int,  # generated tokens riding the payload
                                  #   (0: a mid-prefill migration)
        "src": str,              # role/replica ids, when known
        "dst": str,
        "migration_ms": _NUM,    # in only: out-stamp -> admission wall
        "requeued": int,         # in only: deferred-admission count
        "redelivered": int,      # in only: delivery came from a
                                 #   reclaimed/adopted lease
        "duplicate": bool,       # in only: uid already admitted — the
                                 #   ack-crash window closing (acked,
                                 #   nothing scattered twice)
        "tenant": str,           # the scheduling lane, when tagged
        "spool_file": str,       # quarantine only: the parked payload
        "error": str,            # quarantine only: why it failed
    },
    # --- schema v15: hot-path overhead records (obs/tickprof.py) ---
    "tick_profile": {
        "run_id": str,
    },
    "overhead_summary": {
        "run_id": str,
        "sample_every": int,     # tick_profile sampling stride
        "sampled": int,          # tick_profile records emitted
        "wall": dict,            # per-tick wall-time sketch summary
        "host_gap": dict,        # per-tick host-gap sketch summary
    },
}


def validate_record(rec: Any) -> List[str]:
    """Errors for one parsed JSONL record (empty list == valid).

    Unknown fields are rejected: the schema is the contract log-scraping
    tools depend on, and a silently-passing typo'd field would fork it.
    """
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, expected object"]
    kind = rec.get("record")
    if kind not in REQUIRED:
        return [f"unknown record type {kind!r} "
                f"(expected one of {sorted(REQUIRED)})"]
    errors = []
    required, optional = REQUIRED[kind], OPTIONAL.get(kind, {})
    for field, types in required.items():
        if field not in rec:
            errors.append(f"{kind}: missing required field {field!r}")
        elif not isinstance(rec[field], types) or isinstance(rec[field],
                                                             bool):
            errors.append(f"{kind}: field {field!r} is "
                          f"{type(rec[field]).__name__}, expected "
                          f"{types}")
    for field, value in rec.items():
        if field in required:
            continue
        if field not in optional:
            errors.append(f"{kind}: unknown field {field!r}")
        elif optional[field] is not None and not isinstance(value,
                                                            optional[field]):
            errors.append(f"{kind}: field {field!r} is "
                          f"{type(value).__name__}, expected "
                          f"{optional[field]}")
    return errors


def validate_stream(records) -> List[str]:
    """Validate an iterable of parsed records as one run's stream: per-
    record checks plus the stream invariants (header first, at most one
    header/summary)."""
    errors: List[str] = []
    headers = summaries = 0
    for n, rec in enumerate(records):
        for e in validate_record(rec):
            errors.append(f"line {n + 1}: {e}")
        kind = rec.get("record") if isinstance(rec, dict) else None
        if kind == "run_header":
            headers += 1
            if n != 0:
                errors.append(f"line {n + 1}: run_header must be the first "
                              "record")
        elif kind == "run_summary":
            summaries += 1
    if headers > 1:
        errors.append(f"{headers} run_header records (expected at most 1)")
    if summaries > 1:
        errors.append(f"{summaries} run_summary records (expected at most 1)")
    return errors
