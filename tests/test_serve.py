"""Continuous-batching inference subsystem (serve/, serve.py; ISSUE 3)
and its resilience layer (ISSUE 5):

- the tier-1 acceptance smoke: 8 staggered mixed-length requests through
  a 4-slot engine — greedy outputs token-identical to one-shot
  generate(), completions interleaving across admission waves, the
  emitted JSONL passing metrics_lint and serve_report,
- per-slot top-k sampling (determinism under a fixed rng; top_k=1 ==
  greedy),
- checkpoint -> serve round trip (CheckpointManager save, template-free
  restore in serve.py, served == generate() on the restored params),
- request lifecycle hardening: deadlines (queued expiry + mid-flight
  evict), bounded admission with deterministic shedding, cancellation,
- failure isolation: slot_fail fails exactly one request with every
  other greedy output token-identical to the fault-free run; the
  degenerate-token guard on the nan fault,
- graceful drain: run_serve + sigterm@tick => serve_drain record,
  un-aborted serve_summary with per-status counts, exit EX_TEMPFAIL,
- schema v3/v5 records + v1-v4 back-compat,
- queue/slot-pool/loadgen unit coverage and the serve.py CLI surface.

All engine tests share one slot geometry (SLOTS=4, MAX_LEN=32, the
default 8-token blocks) and one generate() max_len so the compiled
decode programs are built once per session — the suite rides tier-1 and
must stay cheap.  The KV cache is block-paged as of ISSUE 8
(tests/test_paged_kv.py holds the allocator/prefix-sharing/chunked-
prefill coverage; this file keeps the serving + resilience contract).
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import serve as serve_mod
from apex_example_tpu import obs
from apex_example_tpu.models.gpt import generate, gpt_tiny
from apex_example_tpu.obs import schema as obs_schema
from apex_example_tpu.resilience import EX_TEMPFAIL, FaultPlan
from apex_example_tpu.resilience.faults import SERVE_KINDS
from apex_example_tpu.serve import (STATUSES, BlockPool, Request,
                                    RequestQueue, ServeEngine, parse_range,
                                    synthetic_requests)

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, MAX_LEN = 4, 32


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt_tiny()
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _run_engine(model, params, requests, rng_seed=0, sink=None,
                run_id=None, max_steps=2000):
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(rng_seed), sink=sink,
                      run_id=run_id)
    eng.queue.submit_all(requests)
    eng.queue.close()
    comps = eng.run(max_steps=max_steps)
    return eng, comps


# ------------------------------------------- tier-1 acceptance smoke

def test_continuous_batching_smoke(model_and_params, tmp_path, capsys):
    """The acceptance bar: >= 8 synthetic requests, staggered arrivals,
    mixed prompt/output lengths, SLOTS=4 — greedy outputs token-identical
    to one-shot generate(), completions interleaved across admission
    waves, JSONL lints, serve_report shows nonzero TTFT/TPOT.

    Runs WITH --trace armed (ISSUE 11): the same smoke also proves the
    trace stratum is a pure observer — token identity holds, the stream
    exports to valid Chrome JSON, the structural lint passes, and the
    per-request critical-path components sum to each request's e2e
    latency within 1%."""
    from apex_example_tpu.obs import trace as trace_lib
    model, params = model_and_params
    path = str(tmp_path / "serve.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={"slots": SLOTS, "max_len": MAX_LEN},
                       arch="gpt_tiny")
    reqs = synthetic_requests(8, vocab_size=model.vocab_size, seed=3,
                              prompt_len=(3, 8), max_new=(3, 12),
                              stagger=4)
    # mixed lengths actually present
    assert len({len(r.prompt) for r in reqs}) > 1
    assert len({r.max_new_tokens for r in reqs}) > 1
    trace_lib.set_default(obs.Tracer(sink, run_id=emitter.run_id))
    try:
        eng, comps = _run_engine(model, params, reqs, sink=sink,
                                 run_id=emitter.run_id)
    finally:
        trace_lib.set_default(None)
    sink.write(eng.summary_record())
    sink.close()
    assert len(comps) == 8

    # (a) token-identical to the one-shot decode path: generate() at the
    # shared max_len, compared on the request's output budget prefix.
    by_uid = {c.request.uid: c for c in comps}
    for r in reqs:
        c = by_uid[r.uid]
        P = len(r.prompt)
        n = len(c.tokens)
        assert n == min(r.max_new_tokens, MAX_LEN - P)
        ref = generate(model, params, jnp.asarray([r.prompt], jnp.int32),
                       max_len=MAX_LEN)
        np.testing.assert_array_equal(np.asarray(ref)[0, P:P + n],
                                      np.asarray(c.tokens, np.int32),
                                      err_msg=r.uid)

    # (b) continuous batching actually happened: some request was
    # admitted while an earlier-admitted one was still decoding, and
    # slots were reused across admission waves.
    assert any(a.admitted_step < b.admitted_step <= a.finished_step
               for a in comps for b in comps)
    slot_uses = [c.slot for c in comps]
    assert len(slot_uses) > len(set(slot_uses))      # some slot reused
    assert eng.pool.free_count == SLOTS              # all evicted

    # (c) the stream is schema-valid and the report derives nonzero
    # latency percentiles from it.
    lint = _load_tool("metrics_lint")
    code, errors = lint.lint(path)
    assert code == 0, errors
    records = obs.read_jsonl(path)
    reqs_rec = [r for r in records if r["record"] == "request_complete"]
    assert len(reqs_rec) == 8
    assert all(r["ttft_ms"] > 0 and r["tpot_ms"] > 0 for r in reqs_rec)
    summary = records[-1]
    assert summary["record"] == "serve_summary"
    assert summary["requests"] == 8
    assert summary["ttft_ms"]["p50"] > 0
    assert summary["tpot_ms"]["p50"] > 0
    report = _load_tool("serve_report")
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "ttft_ms" in out and "tpot_ms" in out
    assert "finish reasons: length x8" in out
    assert "kv blocks:" in out                   # v7 block line rendered

    # (d) the ISSUE 8 acceptance bar: block-accurate kv_waste_pct on
    # THIS smoke workload drops from the dense layout's ~92% to <= 40%
    # (blocks are allocated as sequences grow and freed at completion,
    # so held-block bytes track live bytes to within block rounding).
    assert summary["kv_waste_pct"] <= 40.0
    assert summary["blocks_total"] == SLOTS * (MAX_LEN // 8)
    assert 0 < summary["blocks_live"]["max"] <= summary["blocks_total"]

    # (e) the ISSUE 11 acceptance bar: the traced stream exports to
    # valid Chrome trace JSON, passes the structural lint, carries the
    # per-tick + per-request span vocabulary, and serve_report's
    # critical-path components sum to each request's e2e within 1%.
    evs = [r for r in records if r["record"] == "trace_event"]
    assert evs and sum(1 for r in records
                       if r["record"] == "clock_sync") == 1
    names = {e["name"] for e in evs}
    assert {"tick", "admit", "dispatch", "harvest", "request", "queued",
            "prefill", "decode", "first_token", "ok"} <= names
    # these requests are all arrival_step-GATED: mature() re-stamps
    # t_submit with t_arrival, so no "submit" span may appear — the
    # deliberate stagger must not masquerade as client handoff
    # (review regression)
    assert "submit" not in names
    # one request root per request, each with its lifecycle children
    roots = [e for e in evs if e["name"] == "request"]
    assert len(roots) == 8
    assert all(e["args"]["status"] == "ok" and e["args"]["blocks"] > 0
               and e["args"]["slot"] >= 0 for e in roots)
    export = _load_tool("trace_export")
    assert export.main(["--check", path]) == 0
    out_json = str(tmp_path / "trace.json")
    assert export.main([path, "-o", out_json]) == 0
    doc = json.loads(open(out_json).read())      # valid JSON
    assert any(e.get("ph") == "s" for e in doc["traceEvents"])  # flows
    rows = report.critical_path(records)
    assert len(rows) == 8
    for row in rows:
        total = row["queue_ms"] + row["prefill_ms"] \
            + row["decode_ms"] + row["stall_ms"]
        assert total == pytest.approx(row["e2e_ms"], rel=0.01), row
    capsys.readouterr()                          # drop the tool stdout


# ------------------------------------------------- per-slot sampling

def test_topk_sampling_deterministic_and_topk1_greedy(model_and_params):
    """Satellite: per-slot top-k — fixed rng => identical streams;
    top_k=1 collapses to greedy regardless of temperature."""
    model, params = model_and_params
    mk = lambda k, t: synthetic_requests(
        4, vocab_size=model.vocab_size, seed=5, prompt_len=(3, 6),
        max_new=(4, 8), temperature=t, top_k=k, stagger=2)
    _, c1 = _run_engine(model, params, mk(3, 1.0), rng_seed=11)
    _, c2 = _run_engine(model, params, mk(3, 1.0), rng_seed=11)
    toks = lambda comps: [c.tokens for c in
                          sorted(comps, key=lambda c: c.request.uid)]
    assert toks(c1) == toks(c2)                      # deterministic
    _, ck = _run_engine(model, params, mk(1, 1.5), rng_seed=11)
    _, cg = _run_engine(model, params, mk(0, 0.0), rng_seed=7)
    assert toks(ck) == toks(cg)                      # top_k=1 == greedy


def test_eos_finishes_request(model_and_params):
    model, params = model_and_params
    prompt = [5, 9, 13]
    ref = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_len=MAX_LEN)
    first = int(np.asarray(ref)[0, len(prompt)])
    req = Request(prompt=prompt, max_new_tokens=10, eos_id=first)
    _, comps = _run_engine(model, params, [req])
    assert len(comps) == 1
    assert comps[0].finish_reason == "eos"
    assert comps[0].tokens == [first]


# -------------------------------------- checkpoint -> serve round trip

def test_checkpoint_serve_round_trip(model_and_params, tmp_path, capsys):
    """Satellite: save a tiny trained GPT state with CheckpointManager,
    restore in serve.py (template-free), served greedy outputs match
    direct generate() on the restored params."""
    import optax

    from apex_example_tpu import amp
    from apex_example_tpu.data import lm_batch
    from apex_example_tpu.engine import create_train_state, make_train_step
    from apex_example_tpu.utils.checkpoint import (CheckpointManager,
                                                   restore_params)
    from apex_example_tpu.workloads import lm_loss

    model, _ = model_and_params
    V = model.vocab_size
    policy, scaler = amp.initialize("O0")
    toks = lm_batch(jnp.asarray(0, jnp.int32), batch_size=4, seq_len=16,
                    vocab_size=V, seed=0)
    batch = (toks[:, :-1], toks[:, 1:])
    state = create_train_state(jax.random.PRNGKey(0), model,
                               optax.adam(1e-3), batch[0][:1], policy,
                               scaler)
    step_fn = jax.jit(make_train_step(model, optax.adam(1e-3), policy,
                                      loss_fn=lm_loss,
                                      compute_accuracy=False))
    for _ in range(2):                       # "trained", cheaply
        state, _metrics = step_fn(state, batch)
    ckpt_dir = str(tmp_path / "ckpt")
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(state)
    mgr.close()

    restored = restore_params(ckpt_dir)
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    argv = ["--arch", "gpt_tiny", "--checkpoint-dir", ckpt_dir,
            "--requests", "4", "--slots", str(SLOTS), "--max-len",
            str(MAX_LEN), "--prompt-len", "3:6", "--max-new", "4:8",
            "--stagger", "2", "--seed", "9"]
    comps, summary, rc = serve_mod.run_serve(
        serve_mod.build_parser().parse_args(argv))
    assert rc == 0 and len(comps) == 4
    assert "checkpoint" in capsys.readouterr().out
    for c in comps:
        P = len(c.request.prompt)
        n = len(c.tokens)
        ref = generate(model, restored,
                       jnp.asarray([c.request.prompt], jnp.int32),
                       max_len=MAX_LEN)
        np.testing.assert_array_equal(np.asarray(ref)[0, P:P + n],
                                      np.asarray(c.tokens, np.int32))


# -------------------------------------------------- serve.py CLI

def test_serve_cli_smoke(tmp_path, capsys):
    """Random-init smoke from the CLI: rc 0, JSONL lints, report runs."""
    path = str(tmp_path / "cli.jsonl")
    rc = serve_mod.main(["--requests", "6", "--slots", str(SLOTS),
                         "--max-len", str(MAX_LEN), "--prompt-len", "3:8",
                         "--max-new", "3:12", "--stagger", "3",
                         "--metrics-jsonl", path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "6/6 completed" in out and "ttft_ms" in out
    lint = _load_tool("metrics_lint")
    code, errors = lint.lint(path)
    assert code == 0, errors
    records = obs.read_jsonl(path)
    assert records[0]["record"] == "run_header"
    assert records[0]["schema"] == obs_schema.SCHEMA_VERSION
    assert records[-1]["record"] == "serve_summary"
    # --trace off: not a single trace-stratum record in the stream
    # (the v9 contract — byte-identical streams without the flag)
    assert not any(r["record"] in ("trace_event", "clock_sync")
                   for r in records)


def test_serve_cli_steps_cap(tmp_path, capsys):
    """A --steps cap that strands requests exits 1 and says so."""
    rc = serve_mod.main(["--requests", "4", "--slots", str(SLOTS),
                         "--max-len", str(MAX_LEN), "--prompt-len", "4",
                         "--max-new", "8", "--steps", "3"])
    captured = capsys.readouterr()
    assert rc == 1
    assert "unfinished" in captured.err


def test_serve_cli_rejects_prompt_longer_than_cache():
    with pytest.raises(SystemExit):
        serve_mod.main(["--prompt-len", "40", "--max-len", "32"])
    with pytest.raises(SystemExit, match="shared-prefix"):
        serve_mod.main(["--prompt-len", "3:8", "--max-len", "32",
                        "--shared-prefix", "30"])
    with pytest.raises(SystemExit, match="block-size"):
        serve_mod.main(["--block-size", "0"])
    with pytest.raises(SystemExit, match="num-blocks"):
        serve_mod.main(["--num-blocks", "0"])


# ------------------------------------------------------- schema v3

def test_schema_v3_serving_records_validate():
    req = {"record": "request_complete", "time": 1.0, "request_id": "r-1",
           "prompt_tokens": 5, "output_tokens": 7, "ttft_ms": 12.5,
           "tpot_ms": 1.5, "finish_reason": "length", "slot": 2,
           "queue_wait_ms": 3.0, "e2e_ms": 25.0, "admitted_step": 4,
           "finished_step": 11, "temperature": 0.0, "top_k": 0,
           "run_id": "x"}
    summ = {"record": "serve_summary", "time": 1.0, "requests": 8,
            "output_tokens": 64, "tokens_per_sec": 100.0, "steps": 40,
            "compute_steps": 39, "slots": 4, "max_len": 32,
            "duration_s": 1.0, "occupancy": 0.6,
            "ttft_ms": {"p50": 1.0, "p95": 2.0, "max": 2.0},
            "tpot_ms": {"p50": 1.0, "p95": 2.0, "max": 2.0},
            "queue_wait_ms": {"p50": 0.0, "p95": 1.0, "max": 1.0}}
    header = {"record": "run_header", "schema": 3, "time": 0.0,
              "run_id": "x", "num_devices": 1, "process_index": 0,
              "platform": "cpu", "config": {}}
    assert obs.validate_record(req) == []
    assert obs.validate_record(summ) == []
    assert obs_schema.validate_stream([header, req, summ]) == []
    # malformed: missing required field / unknown field still rejected
    assert obs.validate_record({"record": "request_complete"})
    assert obs.validate_record(dict(summ, typo=1))


def test_schema_v1_v2_streams_still_validate():
    """v3 is a strict superset: pre-PR streams keep validating."""
    v1 = [{"record": "run_header", "schema": 1, "time": 0.0, "run_id": "r",
           "num_devices": 1, "process_index": 0, "platform": "cpu",
           "config": {}},
          {"record": "step", "step": 1, "epoch": 0, "loss": 1.0,
           "scale": 1.0, "step_time_ms": 5.0, "items_per_sec": 10.0},
          {"record": "run_summary", "steps": 1, "overflow_count": 0}]
    assert obs_schema.validate_stream(v1) == []
    v2 = [dict(v1[0], schema=2), v1[1],
          {"record": "crash_dump", "time": 1.0, "reason": "signal:SIGTERM"},
          {"record": "run_summary", "steps": 1, "overflow_count": 0,
           "aborted": True, "abort_reason": "signal:SIGTERM"}]
    assert obs_schema.validate_stream(v2) == []


# ------------------------------------------------ queue + slot pool

def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request(prompt=[], max_new_tokens=1)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(prompt=[1], max_new_tokens=0)
    with pytest.raises(ValueError, match="temperature"):
        Request(prompt=[1], max_new_tokens=1, temperature=-1.0)
    with pytest.raises(ValueError, match="top_k"):
        Request(prompt=[1], max_new_tokens=1, top_k=-1)


def test_queue_fifo_and_arrival_gating():
    q = RequestQueue()
    a = Request(prompt=[1], max_new_tokens=1, arrival_step=0)
    b = Request(prompt=[2], max_new_tokens=1, arrival_step=5)
    c = Request(prompt=[3], max_new_tokens=1)      # ungated, behind b
    q.submit_all([a, b, c])
    assert q.pop(0) is a
    assert q.pop(3) is None        # b's gate holds the line (FIFO)
    assert q.pending() == 2
    assert q.pop(5) is b
    assert q.pop(5) is c
    assert q.pop(5) is None
    assert not q.drained()
    q.close()
    assert q.drained()
    with pytest.raises(RuntimeError, match="closed"):
        q.submit(a)


def test_block_pool_admit_evict(model_and_params):
    model, _ = model_and_params
    pool = BlockPool(model, num_slots=2, max_len=16, block_size=8)
    assert pool.num_blocks == 4                  # dense-capacity default
    r = lambda: Request(prompt=[1, 2, 3], max_new_tokens=4)
    s0 = pool.admit(r(), step=0)
    s1 = pool.admit(r(), step=0)
    assert {s0, s1} == {0, 1} and pool.free_count == 0
    with pytest.raises(RuntimeError, match="no free slot"):
        pool.admit(r(), step=1)
    pool.evict(s0)
    assert pool.free_count == 1 and pool.live == [s1]
    with pytest.raises(RuntimeError, match="already free"):
        pool.evict(s0)
    with pytest.raises(ValueError, match="prompt length"):
        pool.admit(Request(prompt=list(range(16)), max_new_tokens=1),
                   step=2)
    # output budget clamps to the slot's logical capacity
    assert pool.max_new_for(Request(prompt=[1] * 10,
                                    max_new_tokens=50)) == 6
    with pytest.raises(ValueError, match="position table"):
        BlockPool(model, num_slots=1, max_len=model.max_position + 1)


def test_parse_range():
    assert parse_range("8", "x") == (8, 8)
    assert parse_range("4:12", "x") == (4, 12)
    for bad in ("a", "4:2", "0:3", "1:2:3"):
        with pytest.raises(ValueError):
            parse_range(bad, "x")


# ============= cost observability + KV gauges (ISSUE 7) =============

def test_cost_model_decode_compiles_once_and_kv_gauges(
        model_and_params, tmp_path, compile_events):
    """The serving half of the ISSUE 7 recompile guard, on the PAGED
    decode step (ISSUE 8): block tables, fill levels, COW pairs and
    chunk widths are all DATA, so the program still compiles exactly
    once per geometry (a second compile_event is the regression — and
    ``compile_events.gate`` runs the actual cost_report
    --fail-on-recompile CI command over the stream).  Also checks the
    serve_summary KV gauges, v6 + the v7 block stratum.  Rides the
    session's SLOTS=4/MAX_LEN=32 decode geometry.

    --trace rides along (ISSUE 11): tracing is host-only, so the ONE
    compile_event is also the proof that arming the tracer adds ZERO
    compiled programs — the decode step is untouched.

    --slo rides along too (ISSUE 16): the streaming SLO plane is the
    same kind of host-only fold, so the ONE compile_event doubles as
    its zero-new-programs proof — and the summary's ONLINE sketch
    percentiles are checked against the EXACT percentiles recomputed
    from the raw request_complete records (the declared relative-error
    bound, asserted on the tier-1 smoke)."""
    from apex_example_tpu.obs import costmodel
    from apex_example_tpu.obs import trace as trace_lib
    model, params = model_and_params
    path = str(tmp_path / "cm_serve.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={"slots": SLOTS, "max_len": MAX_LEN},
                       arch="gpt_tiny")
    costmodel.set_default(obs.CostModel(
        sink=sink, registry=emitter.registry, run_id=emitter.run_id))
    trace_lib.set_default(obs.Tracer(sink, run_id=emitter.run_id))
    try:
        reqs = synthetic_requests(6, vocab_size=model.vocab_size, seed=5,
                                  prompt_len=(3, 6), max_new=(3, 6),
                                  stagger=2)
        eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                          rng=jax.random.PRNGKey(0), sink=sink,
                          run_id=emitter.run_id,
                          registry=emitter.registry,
                          slo={"ttft_ms": 60_000.0, "tpot_ms": 60_000.0,
                               "availability": 0.5},
                          slo_window_ticks=8)
        eng.queue.submit_all(reqs)
        eng.queue.close()
        comps = eng.run(max_steps=2000)
    finally:
        costmodel.set_default(None)
        trace_lib.set_default(None)
    sink.write(eng.summary_record())
    sink.close()
    assert len(comps) == 6

    records = obs.read_jsonl(path)
    assert obs_schema.validate_stream(records) == []
    # recompile guard: one engine, one decode program, one compilation —
    # asserted on the counter AND through the CI gate command itself.
    # The tracer was armed for the whole run: ZERO new compiled
    # programs with tracing on.
    assert compile_events(records) == {"serve_decode_step": 1}
    assert compile_events.gate(path) == 0
    assert any(r["record"] == "trace_event" for r in records)
    cm = next(r for r in records if r["record"] == "cost_model")
    assert cm["name"] == "serve_decode_step"
    assert cm["flops"] > 0 and cm["bytes_accessed"] > 0

    # KV accounting: per-token cost is layers x (K+V) x hidden x 4B;
    # the default arena reserves exactly the dense layout's capacity
    per_token = 2 * model.num_layers * model.hidden_size * 4
    assert eng.pool.kv_bytes_per_token() == per_token
    reserved = SLOTS * MAX_LEN * per_token
    summary = records[-1]
    assert summary["record"] == "serve_summary"
    assert summary["kv_bytes_reserved"] == reserved
    kv = summary["kv_bytes_live"]
    assert 0 < kv["max"] <= reserved
    assert kv["max"] % per_token == 0         # whole cached tokens
    occ = summary["slot_occupancy"]
    assert 0 < occ["max"] <= SLOTS
    assert 0 <= summary["kv_waste_pct"] <= 100
    # v7 block stratum: held blocks never exceed the arena, committed
    # bytes cover what admission reserved, and this no-shared-prefix
    # workload neither hits the prefix index nor copies a block
    blk = summary["blocks_live"]
    assert 0 < blk["max"] <= summary["blocks_total"] == SLOTS * MAX_LEN // 8
    assert summary["block_size"] == 8
    assert summary["kv_bytes_committed"]["max"] <= reserved
    assert summary["kv_bytes_committed"]["min"] >= kv["min"]
    assert summary["prefix_hit_rate"] == 0.0
    assert summary["cow_copies"] == 0 and summary["rejected"] == 0
    # per-tick registry gauges saw the run (last tick: pool drained)
    snap = emitter.registry.snapshot()
    assert snap["serve.slots_live"] == 0
    assert snap["serve.kv_bytes_live"] == 0
    assert snap["serve.blocks_live"] == 0
    # v14 SLO plane: every terminal landed in some tumbling window
    # (the trailing partial closes at summary time), the generous spec
    # passes, and the online sketch is honest — each percentile within
    # the declared relative-error bound alpha of the exact nearest-rank
    # percentile over the raw per-request records (same rank
    # convention; +0.01 ms absolute slack for the records' 3-decimal
    # rounding).
    slo_windows = [r for r in records if r["record"] == "slo_window"]
    assert slo_windows and all(w["requests"] >= 1 for w in slo_windows)
    assert sum(w["requests"] for w in slo_windows) == 6
    slo = summary["slo"]
    assert slo["verdict"] == "pass" and slo["breaches"] == 0
    assert slo["good"] == 6 and slo["bad"] == 0
    assert slo["windows"] == len(slo_windows)
    assert not any(r["record"] == "slo_breach" for r in records)
    exact = sorted(r["ttft_ms"] for r in records
                   if r["record"] == "request_complete")
    sk = slo["ttft_ms"]
    assert sk["count"] == len(exact) == 6
    for q in (50, 90, 99):
        rank = min(max(-(-q * len(exact) // 100), 1), len(exact))
        ex = exact[rank - 1]
        assert abs(sk[f"p{q}"] - ex) <= slo["alpha"] * ex + 0.01, q


# ==================== serving resilience (ISSUE 5) ====================

def _run_engine_res(model, params, requests, queue=None, fault=None,
                    sink=None, run_id=None, max_steps=2000):
    """Engine helper for the resilience tests — same shared slot
    geometry as _run_engine so the decode program compiles once."""
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0), queue=queue, sink=sink,
                      run_id=run_id, fault=fault)
    eng.queue.submit_all(requests)
    eng.queue.close()
    eng.run(max_steps=max_steps)
    return eng


def _by_order(engine):
    """Completions in submission order (uids are a monotonic counter
    within one process, so sorting aligns two runs' streams)."""
    return sorted(engine.completions, key=lambda c: c.request.uid)


# ------------------------------------------------ deadlines / timeout

def test_deadline_expires_queued_request_without_admitting(
        model_and_params):
    """A queued request whose deadline passes before a slot frees up
    terminates with status "timeout", slot -1, never admitted — the
    hogs are untouched."""
    model, params = model_and_params
    hogs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=20)
            for i in range(SLOTS)]
    late = Request(prompt=[5, 6], max_new_tokens=4, deadline_step=5)
    eng = _run_engine_res(model, params, hogs + [late])
    assert eng.counts == {s: 0 for s in STATUSES} | {"ok": SLOTS,
                                                     "timeout": 1}
    comp = next(c for c in eng.completions if c.request is late)
    assert comp.status == "timeout" and comp.finish_reason == "timeout"
    assert comp.slot == -1 and comp.admitted_step == -1
    assert comp.tokens == [] and comp.ttft_s is None


def test_deadline_evicts_decoding_slot_midflight(model_and_params,
                                                 tmp_path):
    """A decoding request hitting its deadline is evicted mid-flight:
    partial tokens kept, request_failed emitted, stream lints."""
    model, params = model_and_params
    path = str(tmp_path / "t.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={}, arch="gpt_tiny")
    req = Request(prompt=[1, 2, 3], max_new_tokens=20, deadline_step=6)
    eng = _run_engine_res(model, params, [req], sink=sink,
                          run_id=emitter.run_id)
    sink.write(eng.summary_record())
    sink.close()
    comp = eng.completions[0]
    assert comp.status == "timeout" and comp.slot == 0
    # one chunked-prefill tick then decode: fewer tokens than asked,
    # more than 0 by the deadline
    assert 0 < len(comp.tokens) < 20
    recs = obs.read_jsonl(path)
    assert obs_schema.validate_stream(recs) == []
    failed = next(r for r in recs if r["record"] == "request_failed")
    assert failed["status"] == "timeout"
    assert failed["output_tokens"] == len(comp.tokens)
    assert failed["slot"] == 0
    summary = recs[-1]
    assert summary["timed_out"] == 1 and summary["completed"] == 0
    assert summary["availability"] == 0.0
    lint = _load_tool("metrics_lint")
    assert lint.lint(path)[0] == 0


# ------------------------------------------- admission control / shed

def test_bounded_queue_sheds_newest_deterministically(model_and_params,
                                                      tmp_path):
    """A burst past max_pending sheds the newest arrivals (reject-newest
    default), deterministically: same uids shed on every run, shed
    records emitted, availability reflects the loss."""
    model, params = model_and_params
    path = str(tmp_path / "s.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={}, arch="gpt_tiny")
    mk = lambda: synthetic_requests(
        10, vocab_size=model.vocab_size, seed=4, prompt_len=(3, 5),
        max_new=(3, 5), stagger=0)
    reqs = mk()
    eng = _run_engine_res(model, params, reqs,
                          queue=RequestQueue(max_pending=4), sink=sink,
                          run_id=emitter.run_id)
    sink.write(eng.summary_record())
    sink.close()
    assert eng.counts["shed"] == 6 and eng.counts["ok"] == 4
    shed_uids = [c.request.uid for c in eng.completions
                 if c.status == "shed"]
    # reject-NEWEST: the last 6 submitted are the ones shed
    assert shed_uids == [r.uid for r in reqs[4:]]
    # deterministic: a rerun sheds the same submission indices
    reqs2 = mk()
    eng2 = _run_engine_res(model, params, reqs2,
                           queue=RequestQueue(max_pending=4))
    assert [c.request.uid for c in eng2.completions
            if c.status == "shed"] == [r.uid for r in reqs2[4:]]
    assert eng2.counts == eng.counts
    recs = obs.read_jsonl(path)
    assert obs_schema.validate_stream(recs) == []
    shed_recs = [r for r in recs if r["record"] == "shed"]
    assert len(shed_recs) == 6
    assert all(r["reason"] == "queue_full" and r["max_pending"] == 4
               for r in shed_recs)
    summary = recs[-1]
    assert summary["shed"] == 6 and summary["completed"] == 4
    assert summary["availability"] == 0.4


def test_shed_record_pending_is_arrived_backlog(model_and_params,
                                                tmp_path):
    """A shed record's ``pending`` counts the ARRIVED backlog (what the
    bound actually limits), not the whole deque — future-gated waves
    must not make admission control look broken (pending > bound)."""
    model, params = model_and_params
    wave1 = [Request(prompt=[i + 1, 2, 3], max_new_tokens=3)
             for i in range(6)]
    wave2 = [Request(prompt=[i + 1, 3, 4], max_new_tokens=3,
                     arrival_step=100) for i in range(8)]
    path = str(tmp_path / "p.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    eng = _run_engine_res(model, params, wave1 + wave2,
                          queue=RequestQueue(max_pending=2), sink=sink)
    sink.close()
    shed_recs = [r for r in obs.read_jsonl(path) if r["record"] == "shed"]
    assert shed_recs
    assert all(r["pending"] <= r["max_pending"] == 2 for r in shed_recs)


def test_sink_failure_is_engine_level_not_slot_mislabel(model_and_params):
    """A sink whose write() raises inside _finish must surface as an
    ENGINE-level error (it would hit every record), not be caught by
    the slot-isolation try — which would re-terminate the already-
    evicted slot and mislabel an IO fault as a request failure."""
    model, params = model_and_params

    class BrokenSink:
        def write(self, rec):
            raise OSError("disk full")

    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0), sink=BrokenSink())
    eng.queue.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    eng.queue.close()
    with pytest.raises(OSError, match="disk full"):
        eng.run()
    # the completion itself was recorded exactly once, slot freed
    assert eng.counts["ok"] == 1 and eng.counts["failed"] == 0
    assert len(eng.completions) == 1
    assert eng.pool.free_count == SLOTS


def test_expired_queued_requests_free_capacity_before_shed(
        model_and_params):
    """Expiry runs before the bound check: a backlog of already-dead
    requests must not get a healthy arrival shed over capacity that
    frees this very tick."""
    model, params = model_and_params
    # hogs arrive in bound-respecting waves of 2 and fill every slot
    hogs = [Request(prompt=[i + 1, 2, 3], max_new_tokens=12,
                    arrival_step=i // 2) for i in range(SLOTS)]
    # two queued requests whose deadline passes at tick 5...
    dead = [Request(prompt=[7, 8], max_new_tokens=2, arrival_step=2,
                    deadline_step=5) for _ in range(2)]
    # ...and a healthy arrival AT tick 5, into a bound of 2: the old
    # shed-before-expire order counted the dead pair and shed it
    fresh = Request(prompt=[9, 9, 9], max_new_tokens=2, arrival_step=5)
    eng = _run_engine_res(model, params, hogs + dead + [fresh],
                          queue=RequestQueue(max_pending=2))
    st = {c.request.uid: c.status for c in eng.completions}
    assert st[fresh.uid] == "ok"                  # NOT shed
    assert all(st[d.uid] == "timeout" for d in dead)
    assert eng.counts["shed"] == 0


def test_shed_policy_oldest_drops_head(model_and_params):
    model, params = model_and_params
    reqs = [Request(prompt=[i + 1, 2, 3], max_new_tokens=3)
            for i in range(6)]
    eng = _run_engine_res(model, params, reqs,
                          queue=RequestQueue(max_pending=2,
                                             shed_policy="oldest"))
    shed_uids = {c.request.uid for c in eng.completions
                 if c.status == "shed"}
    assert shed_uids == {r.uid for r in reqs[:4]}   # head dropped


# ------------------------------------------------------- cancellation

def test_cancel_queued_and_inflight(model_and_params):
    model, params = model_and_params
    a = Request(prompt=[1, 2, 3], max_new_tokens=8)
    hogs = [Request(prompt=[2 + i, 3, 4], max_new_tokens=8)
            for i in range(SLOTS - 1)]
    b = Request(prompt=[9, 9], max_new_tokens=8, arrival_step=30)
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0))
    eng.queue.submit_all([a] + hogs + [b])
    eng.queue.close()
    eng.step()
    eng.step()
    assert eng.cancel(b.uid)            # still queued (gated): immediate
    assert eng.cancel(a.uid)            # decoding: evicted mid-flight
    assert not eng.cancel(a.uid)        # already terminal
    assert not eng.cancel("req-unknown")
    eng.run()
    assert eng.counts["cancelled"] == 2 and eng.counts["ok"] == len(hogs)
    ca = next(c for c in eng.completions if c.request is a)
    cb = next(c for c in eng.completions if c.request is b)
    assert ca.slot >= 0 and cb.slot == -1
    assert ca.status == cb.status == "cancelled"


# ------------------------------------------------- failure isolation

def test_slot_fail_isolates_one_request(model_and_params, tmp_path):
    """The acceptance bar: slot_fail@tick fails exactly one request
    (request_failed with the injected traceback digest) while every
    other request's greedy output is token-identical to the fault-free
    run — the engine keeps ticking."""
    model, params = model_and_params
    mk = lambda: synthetic_requests(
        6, vocab_size=model.vocab_size, seed=5, prompt_len=(3, 6),
        max_new=(4, 8), stagger=2)
    ref = _run_engine_res(model, params, mk())
    assert ref.counts["ok"] == 6
    path = str(tmp_path / "f.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={}, arch="gpt_tiny")
    eng = _run_engine_res(model, params, mk(),
                          fault=FaultPlan("slot_fail", 6,
                                          kinds=SERVE_KINDS),
                          sink=sink, run_id=emitter.run_id)
    sink.write(eng.summary_record())
    sink.close()
    assert eng.counts["failed"] == 1 and eng.counts["ok"] == 5
    for c_ref, c in zip(_by_order(ref), _by_order(eng)):
        assert len(c_ref.request.prompt) == len(c.request.prompt)
        if c.status == "ok":
            assert c.tokens == c_ref.tokens, c.request.uid
    failed = next(c for c in eng.completions if c.status == "failed")
    assert "injected slot_fail at tick 6" in failed.error
    recs = obs.read_jsonl(path)
    assert obs_schema.validate_stream(recs) == []
    frec = next(r for r in recs if r["record"] == "request_failed")
    assert frec["status"] == "failed"
    assert frec["request_id"] == failed.request.uid
    assert "FaultInjected" in frec["error"]
    summary = recs[-1]
    assert summary["failed"] == 1 and summary["completed"] == 5
    assert summary["availability"] == round(5 / 6, 3)


def test_fault_on_idle_tick_still_fires(model_and_params):
    """A drill scheduled in an idle gap between arrival waves must not
    be silently skipped: engine-level kinds fire on the idle tick
    itself, slot-level kinds defer to the next tick that can express
    them (FaultPlan.due is >=)."""
    model, params = model_and_params
    # wave 1 (ticks 0..~6), idle gap, wave 2 arrives at tick 20
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=3),
            Request(prompt=[4, 5, 6], max_new_tokens=3, arrival_step=20)]
    fault = FaultPlan("slot_fail", 12, kinds=SERVE_KINDS)  # idle tick
    eng = _run_engine_res(model, params, reqs, fault=fault)
    assert fault.fired
    assert eng.counts["failed"] == 1 and eng.counts["ok"] == 1
    failed = next(c for c in eng.completions if c.status == "failed")
    assert failed.request is reqs[1]              # fired on wave 2


def test_nan_fault_fires_on_first_token_keeping_tick(model_and_params):
    """The nan drill is only consumed on a tick some slot KEEPS a
    token.  Under chunked prefill a 5-token prompt completes inside
    tick 1's chunk, so nan@1 fires immediately and poisons the first
    kept token; a drill landing on a tick whose chunks all stop short
    of their prompt end still defers (FaultPlan.due is >=)."""
    model, params = model_and_params
    req = Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=4)
    fault = FaultPlan("nan", 1, kinds=SERVE_KINDS)
    eng = _run_engine_res(model, params, [req], fault=fault)
    assert fault.fired
    assert eng.counts["failed"] == 1 and eng.counts["ok"] == 0
    failed = eng.completions[0]
    assert "degenerate sampled token" in failed.error
    assert failed.tokens == []                    # first kept token poisoned
    # the defer path proper: a 20-token prompt needs ticks 1-3 of pure
    # prefill (block chunks of 8), so nan@1 must wait for tick 3's
    # prompt-crossing chunk instead of burning on a discarded output
    req2 = Request(prompt=list(range(1, 21)), max_new_tokens=4)
    fault2 = FaultPlan("nan", 1, kinds=SERVE_KINDS)
    eng2 = _run_engine_res(model, params, [req2], fault=fault2)
    assert fault2.fired
    failed2 = eng2.completions[0]
    assert failed2.status == "failed" and failed2.tokens == []
    assert failed2.finished_step == 2             # tick 3, 0-based step 2


def test_real_nan_params_trip_nonfinite_logits_guard(model_and_params):
    """Not just the drill: actually-poisoned params produce NaN logits,
    and argmax over NaN yields an IN-RANGE token — the per-slot finite
    mask (computed inside the compiled step) must catch it, fail the
    slot, and never feed the garbage token onward as status ok."""
    model, params = model_and_params
    bad = jax.tree_util.tree_map(lambda x: jnp.full_like(x, jnp.nan),
                                 params)
    eng = _run_engine_res(model, bad,
                          [Request(prompt=[1, 2, 3], max_new_tokens=4)])
    assert eng.counts == {s: 0 for s in STATUSES} | {"failed": 1}
    comp = eng.completions[0]
    assert comp.status == "failed" and comp.tokens == []
    assert "non-finite logits" in comp.error


def test_nan_fault_trips_degenerate_token_guard(model_and_params):
    """The nan serve fault degenerates the tick's sampled tokens; the
    guard fails the affected slots instead of feeding garbage into the
    cache, and later arrivals still complete."""
    model, params = model_and_params
    reqs = synthetic_requests(6, vocab_size=model.vocab_size, seed=5,
                              prompt_len=(3, 6), max_new=(4, 8),
                              stagger=4)
    eng = _run_engine_res(model, params, reqs,
                          fault=FaultPlan("nan", 6, kinds=SERVE_KINDS))
    assert eng.counts["failed"] >= 1
    assert eng.counts["ok"] + eng.counts["failed"] == 6
    assert eng.counts["ok"] >= 1                  # engine kept serving
    for c in eng.completions:
        if c.status == "failed":
            assert "degenerate sampled token" in c.error
            # failed during decode of tick 6 (1-based)
            assert c.finished_step == 5


# --------------------------------------------------- graceful drain

def test_sigterm_drain_graceful_exit(model_and_params, tmp_path, capsys):
    """run_serve + sigterm@tick: admission stops, in-flight requests
    resolve, queued ones are requeued (status drained), the stream
    closes serve_drain -> un-aborted serve_summary, rc == EX_TEMPFAIL,
    and serve_report renders the drain."""
    path = str(tmp_path / "drain.jsonl")
    argv = ["--requests", "8", "--slots", str(SLOTS), "--max-len",
            str(MAX_LEN), "--prompt-len", "3:6", "--max-new", "6:10",
            "--stagger", "3", "--seed", "3", "--metrics-jsonl", path,
            "--inject-fault", "sigterm@6"]
    comps, summary, rc = serve_mod.run_serve(
        serve_mod.build_parser().parse_args(argv))
    assert rc == EX_TEMPFAIL == 75
    assert len(comps) == 8                        # every request terminal
    recs = obs.read_jsonl(path)
    assert obs_schema.validate_stream(recs) == []
    drain = next(r for r in recs if r["record"] == "serve_drain")
    assert drain["signal"] == "SIGTERM"
    assert drain["requeued"] == len(drain["requeued_ids"]) > 0
    assert drain["in_flight"] == drain["completed"] + drain["evicted"]
    # no admission after the drain began
    assert all(c.admitted_step <= drain["step"] for c in comps
               if c.admitted_step >= 0)
    assert {c.status for c in comps} <= {"ok", "timeout", "drained"}
    last = recs[-1]
    assert last["record"] == "serve_summary" and "aborted" not in last
    assert last["drained"] == drain["requeued"]
    assert last["completed"] + last["timed_out"] + last["drained"] == 8
    out = capsys.readouterr().out
    assert "drain (SIGTERM)" in out and "exiting 75" in out
    lint = _load_tool("metrics_lint")
    assert lint.lint(path)[0] == 0
    report = _load_tool("serve_report")
    assert report.main([path]) == 0
    rep = capsys.readouterr().out
    assert "DRAIN: SIGTERM" in rep
    assert "drained x" in rep


def test_serve_cli_overload_shed_and_deadlines(tmp_path, capsys):
    """CLI overload drill: burst past slots+bound sheds, tight virtual
    deadlines time out — all deterministic, availability reported."""
    path = str(tmp_path / "over.jsonl")
    rc = serve_mod.main(["--requests", "12", "--slots", str(SLOTS),
                         "--max-len", str(MAX_LEN), "--prompt-len", "3:5",
                         "--max-new", "3:6", "--stagger", "0",
                         "--burst", "12", "--max-pending", "5",
                         "--deadline-steps", "25",
                         "--metrics-jsonl", path])
    assert rc == 0                        # resolved != stranded
    out = capsys.readouterr().out
    assert "shed=" in out and "availability=" in out
    recs = obs.read_jsonl(path)
    assert obs_schema.validate_stream(recs) == []
    summary = recs[-1]
    # the bound is evaluated at arrival, before the tick's admissions:
    # a 12-burst against max_pending 5 sheds 7 on the spot
    assert summary["shed"] == 12 - 5
    assert summary["completed"] + summary["timed_out"] \
        + summary["shed"] == 12
    assert 0 < summary["availability"] < 1


def test_serve_cli_rejects_bad_fault():
    with pytest.raises(SystemExit):
        serve_mod.main(["--inject-fault", "bogus@3"])
    with pytest.raises(SystemExit):
        serve_mod.main(["--inject-fault", "slot_fail"])
    with pytest.raises(SystemExit, match="flight-recorder"):
        serve_mod.main(["--flight-recorder"])     # needs --metrics-jsonl
    with pytest.raises(SystemExit, match="trace"):
        serve_mod.main(["--trace"])               # needs --metrics-jsonl


# ------------------------------------------------------- schema v5

def test_schema_v5_serving_resilience_records_validate():
    failed = {"record": "request_failed", "time": 1.0, "request_id": "r-1",
              "status": "timeout", "slot": 2, "admitted_step": 3,
              "failed_step": 9, "prompt_tokens": 4, "output_tokens": 2,
              "queue_wait_ms": 1.0, "e2e_ms": 20.0, "error": "x",
              "run_id": "x"}
    shed = {"record": "shed", "time": 1.0, "request_id": "r-2",
            "reason": "queue_full", "step": 4, "pending": 5,
            "max_pending": 4, "run_id": "x"}
    drain = {"record": "serve_drain", "time": 1.0, "signal": "SIGTERM",
             "step": 12, "in_flight": 2, "completed": 1, "evicted": 1,
             "requeued": 3, "requeued_ids": ["a", "b", "c"],
             "run_id": "x"}
    summ = {"record": "serve_summary", "time": 1.0, "requests": 8,
            "output_tokens": 64, "tokens_per_sec": 100.0,
            "completed": 4, "timed_out": 1, "shed": 2, "cancelled": 0,
            "failed": 1, "drained": 0, "availability": 0.5}
    header = {"record": "run_header", "schema": 5, "time": 0.0,
              "run_id": "x", "num_devices": 1, "process_index": 0,
              "platform": "cpu", "config": {}}
    for rec in (failed, shed, drain, summ):
        assert obs.validate_record(rec) == [], rec["record"]
    assert obs_schema.validate_stream(
        [header, failed, shed, drain, summ]) == []
    # malformed still rejected
    assert obs.validate_record({"record": "request_failed", "time": 1.0})
    assert obs.validate_record(dict(shed, typo=1))
    assert obs.validate_record(dict(drain, signal=7))


def test_schema_v1_v4_streams_still_validate():
    """v5 is a strict superset: pre-PR streams keep validating."""
    header = {"record": "run_header", "schema": 1, "time": 0.0,
              "run_id": "r", "num_devices": 1, "process_index": 0,
              "platform": "cpu", "config": {}}
    step = {"record": "step", "step": 1, "epoch": 0, "loss": 1.0,
            "scale": 1.0, "step_time_ms": 5.0, "items_per_sec": 10.0}
    v1 = [header, step,
          {"record": "run_summary", "steps": 1, "overflow_count": 0}]
    v2 = [dict(header, schema=2), step,
          {"record": "crash_dump", "time": 1.0, "reason": "signal:SIGTERM"},
          {"record": "run_summary", "steps": 1, "overflow_count": 0,
           "aborted": True, "abort_reason": "signal:SIGTERM"}]
    v3 = [dict(header, schema=3),
          {"record": "request_complete", "time": 1.0, "request_id": "r-0",
           "prompt_tokens": 4, "output_tokens": 6, "ttft_ms": 10.0,
           "tpot_ms": 1.5, "finish_reason": "length"},
          {"record": "serve_summary", "time": 2.0, "requests": 1,
           "output_tokens": 6, "tokens_per_sec": 50.0}]
    v4 = [dict(header, schema=4), step,
          {"record": "preemption", "time": 1.0, "signal": "SIGTERM",
           "step": 1, "saved": True, "checkpoint_step": 1},
          {"record": "run_summary", "steps": 1, "overflow_count": 0}]
    for stream in (v1, v2, v3, v4):
        assert obs_schema.validate_stream(stream) == []


# --------------------------------------- queue / loadgen resilience

def test_queue_bounds_and_deadline_validation():
    with pytest.raises(ValueError, match="max_pending"):
        RequestQueue(max_pending=0)
    with pytest.raises(ValueError, match="shed_policy"):
        RequestQueue(shed_policy="bogus")
    with pytest.raises(ValueError, match="deadline_s"):
        Request(prompt=[1], max_new_tokens=1, deadline_s=0.0)
    with pytest.raises(ValueError, match="deadline_step"):
        Request(prompt=[1], max_new_tokens=1, deadline_step=0)


def test_queue_expire_shed_drain_cancel():
    q = RequestQueue(max_pending=2)
    a = Request(prompt=[1], max_new_tokens=1)
    b = Request(prompt=[2], max_new_tokens=1, deadline_step=3)
    c = Request(prompt=[3], max_new_tokens=1)
    d = Request(prompt=[4], max_new_tokens=1, arrival_step=50)
    q.submit_all([a, b, c, d])
    # bound counts ARRIVED requests only: a, b, c arrived; d is future
    shed = q.shed_overflow(0)
    assert shed == [c]                       # reject-newest
    assert q.expire(0, 0.0) == []
    assert q.expire(3, 0.0) == [b]           # deadline_step hit
    assert q.cancel(a.uid) is a
    assert q.cancel(a.uid) is None
    assert q.pending() == 1                  # d, still gated
    left = q.drain()
    assert left == [d] and q.closed
    with pytest.raises(RuntimeError, match="closed"):
        q.submit(a)


def test_loadgen_burst_and_deadlines():
    reqs = synthetic_requests(6, vocab_size=100, seed=0, stagger=4,
                              burst=3, deadline_steps=10)
    assert [r.arrival_step for r in reqs] == [0, 0, 0, 4, 4, 4]
    assert [r.deadline_step for r in reqs] == [10, 10, 10, 14, 14, 14]
    reqs = synthetic_requests(2, vocab_size=100, seed=0, stagger=0,
                              deadline_s=1.5)
    assert all(r.arrival_step is None and r.deadline_s == 1.5
               and r.deadline_step is None for r in reqs)
    with pytest.raises(ValueError, match="burst"):
        synthetic_requests(2, vocab_size=100, burst=0)
    with pytest.raises(ValueError, match="deadline_steps"):
        synthetic_requests(2, vocab_size=100, deadline_steps=0)


# ------------------------------ lane packing's adapt path is inert here

@pytest.mark.parametrize("what", ["marshal", "program"])
def test_a_model_without_packed_lanes_keeps_its_marshal_and_its_program(
        model_and_params, monkeypatch, what):
    """The engine budgets prefill chunks for a model that declares
    ``packed_lanes`` (ops/lane_pack.py).  GPT declares nothing: every slot
    inside its prompt is fed ``min(C, prompt left)`` lanes every tick,
    however many ask at once, and its program never meets the lane maps."""
    from apex_example_tpu.ops import lane_pack
    from apex_example_tpu.serve.engine import _slot_step
    model, params = model_and_params
    assert not hasattr(model, "packed_lanes")

    def never(*a, **k):
        raise AssertionError("the lane maps were asked for")
    monkeypatch.setattr(lane_pack, "LaneMap", never)
    monkeypatch.setattr(lane_pack, "groups", never)
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0))
    assert eng._chunk_budget is None
    reqs = synthetic_requests(6, vocab_size=model.vocab_size, seed=5,
                              prompt_len=(17, 24), max_new=(2, 4))
    eng.queue.submit_all(reqs)
    eng.queue.close()
    if what == "program":
        eng.step()
        dec = eng.pool.dec
        S, C = SLOTS, eng.chunk
        text = str(jax.make_jaxpr(
            _slot_step(dec, eng.tick_args).__wrapped__)(
                params, eng.pool.cache,
                jnp.asarray(eng.tick_args.blank(S)[0]),
                jax.random.PRNGKey(0)))
        assert "lane_pack" not in text
        assert f"i32[{S},{C}]" in text          # tok, lane for lane
        return
    C, seen, step = eng.chunk, [], eng._step_fn

    def keeping(*a):
        # the marshal the engine has always made, from the slots as the
        # tick found them
        want_n = np.zeros((SLOTS,), np.int32)
        want_fill = np.zeros((SLOTS,), np.int32)
        want_tok = np.zeros((SLOTS, C), np.int32)
        for i, s in enumerate(eng.pool.slots):
            if s is None:
                continue
            n = min(C, s.n_prompt - s.cursor) if s.prefilling else 1
            want_n[i], want_fill[i] = n, s.cursor
            want_tok[i, :n] = s.tokens[s.cursor:s.cursor + n]
        got = eng.tick_args.fields(np.asarray(a[2]))
        np.testing.assert_array_equal(got["n_new"], want_n)
        np.testing.assert_array_equal(got["fill"], want_fill)
        np.testing.assert_array_equal(got["tok"], want_tok)
        seen.append(int((want_n > 1).sum()))
        return step(*a)
    eng._step_fn = keeping
    comps = eng.run(max_steps=500)
    assert len(comps) == 6 and all(c.status == "ok" for c in comps)
    assert max(seen) == SLOTS         # every slot fed a chunk in one tick
    assert eng.prefill_chunks_deferred == 0
    assert "prefill_chunks_deferred" not in eng.summary_record()


# ----------------------- ISSUE 41: paged attention through one op, two forms

def test_both_forms_of_paged_attention_serve_the_same_tokens(
        model_and_params, step_traced_with):
    """The float arena is read by ``ops.attention.paged_gqa_attention``:
    its kernel (the interpreter here, Mosaic on the TPU) and its XLA gather
    form (``FORCE_XLA``, a plain CPU drive) serve the same tokens."""
    model, params = model_and_params
    tokens = {}
    for form in ("kernel", "xla"):
        with step_traced_with(xla=form == "xla"):
            _, comps = _run_engine(model, params, synthetic_requests(
                6, vocab_size=model.vocab_size, seed=5, prompt_len=(3, 12),
                max_new=(3, 10), stagger=2))
        tokens[form] = {tuple(c.request.prompt): list(c.tokens)
                        for c in comps}
        assert {c.status for c in comps} == {"ok"}
    assert tokens["kernel"] == tokens["xla"] and len(tokens["xla"]) == 6


@pytest.mark.parametrize("held,kernel_calls", [
    ({}, 2), ({"kv_quant": True}, 0), ({"tensor_parallel": True}, 0)],
    ids=["float", "kv_quant", "tensor_parallel"])
def test_the_arena_the_module_holds_chooses_the_form(held, kernel_calls):
    """A float arena held whole: the op's kernel once a layer.  int8 rows
    with a scale table and heads sharded over 'model' keep the gathered
    form (no kernel in the traced tick), whatever the dispatch allows."""
    from apex_example_tpu.ops import _config as ops_config
    assert ops_config.use_pallas()
    tp = {k: v for k, v in held.items() if k == "tensor_parallel"}
    dec = gpt_tiny(**tp).clone(
        decode=True, slot_decode=True, fused_attention=False,
        kv_num_blocks=SLOTS * 4, kv_block_size=8,
        kv_quant=held.get("kv_quant", False))
    tok = jnp.zeros((SLOTS, 8), jnp.int32)
    variables = jax.eval_shape(dec.init, jax.random.PRNGKey(0),
                               jnp.zeros((SLOTS, MAX_LEN), jnp.int32))
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    paged = dict(block_table=jnp.zeros((SLOTS, 4), jnp.int32), fill=zeros,
                 n_new=zeros + 1, cow_src=zeros, cow_dst=zeros - 1)
    text = str(jax.make_jaxpr(lambda v: dec.apply(
        v, tok, train=False, paged=paged, mutable=["cache"]))(variables))
    assert text.count("name=_paged_gqa_pallas") == kernel_calls
