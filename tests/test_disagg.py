"""Sharded + disaggregated serving (ISSUE 14): TP-sharded decode on a
mesh, prefill/decode role split with paged-KV handoff.

- TP-sharded serving: under a registered (data=2, model=4) mesh the
  engine shards weights and per-layer paged-KV arenas over heads on
  'model' (block tables/admission stay host-side) and greedy served
  output is token-identical to dense generate() — fp AND int8
  (weights + KV), with the compile-once gate intact.
- Disaggregation: a prefill-role engine chunk-prefills, samples the
  first token and ships KV blocks; a decode-role engine scatters them
  into its own arena and decodes with a [SLOTS, 1]-wide step.  On a
  mixed long-prompt/short-decode workload the decode role's TPOT p99
  beats the interleaved baseline at comparable total ticks, outputs
  stay token-identical, and zero handoffs are lost.
- Handoff edge cases: COW-shared prefix blocks ship as deep copies
  with refcounts consistent on both sides; a decode worker short on
  slots/blocks requeues deterministically (never crashes), and an
  unservable handoff terminates first-class as "rejected".
- Transport + tools: FileTransport round-trips int8 payloads
  byte-exactly; ci_gate --disagg-stream enforces handoff conservation
  over the checked-in prefill+decode fixture pair; serve_report
  renders the HANDOFF line; trace_export joins a prefill-worker
  request span with its decode-worker continuation across streams.

All in-process engines ride the session's SLOTS=4 / MAX_LEN=32 / BS=8
geometry (the [4, 8] step is shared with test_serve via the lru
cache); the new compiled programs this file adds are the [4, 1]
decode-role step and the TP-sharded variants.  The one new subprocess
e2e is the serve.py --role prefill / --role decode pair.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu import obs
from apex_example_tpu.models.gpt import generate, gpt_tiny
from apex_example_tpu.obs import schema as obs_schema
from apex_example_tpu.obs import trace as trace_lib
from apex_example_tpu.obs.metrics import nearest_rank
from apex_example_tpu.parallel.mesh import parse_serve_mesh, serve_mesh
from apex_example_tpu.resilience.faults import (SERVE_KINDS,
                                                FaultInjected, FaultPlan)
from apex_example_tpu.serve import (FileTransport, KvHandoff,
                                    QueueTransport, Request, ServeEngine,
                                    run_decode_role, run_disagg,
                                    run_prefill_role)
from apex_example_tpu.transformer import parallel_state

pytestmark = pytest.mark.disagg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, MAX_LEN = 4, 32          # the session serve geometry (test_serve)
FIXTURES = os.path.join(REPO, "tests", "fixtures", "disagg")


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


def _mixed_requests(n_long=3, n_short=6, seed=5, stagger=0):
    """The disagg acceptance workload: long prompts (3 prefill chunks)
    mixed with short prompts that mostly decode."""
    rs = np.random.RandomState(seed)
    reqs = []
    for i in range(n_long):
        reqs.append(Request(
            prompt=[int(t) for t in rs.randint(0, 256, 22 + i)],
            max_new_tokens=6,
            arrival_step=None if not stagger else i * stagger))
    for i in range(n_short):
        reqs.append(Request(
            prompt=[int(t) for t in rs.randint(0, 256, 3 + (i % 3))],
            max_new_tokens=16,
            arrival_step=None if not stagger
            else (i % n_long) * stagger))
    return reqs


def _clone(requests):
    """Fresh Request objects (same prompts/budgets, new uids) so each
    engine run owns un-stamped arrival state."""
    return [Request(prompt=list(r.prompt),
                    max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature, top_k=r.top_k,
                    eos_id=r.eos_id, arrival_step=r.arrival_step)
            for r in requests]


def _assert_ref_tokens(model, params, comps, err=""):
    """Every ok completion's greedy tokens == dense generate() at the
    shared MAX_LEN, on the request's clamped output budget."""
    for c in comps:
        assert c.status == "ok", (err, c.request.uid, c.status)
        P = len(c.request.prompt)
        n = len(c.tokens)
        assert n == min(c.request.max_new_tokens, MAX_LEN - P)
        ref = generate(model, params,
                       jnp.asarray([c.request.prompt], jnp.int32),
                       max_len=MAX_LEN)
        np.testing.assert_array_equal(
            np.asarray(ref)[0, P:P + n],
            np.asarray(c.tokens, np.int32),
            err_msg=f"{err} {c.request.uid}")


# ------------------------------------------------------ mesh plumbing


def test_parse_serve_mesh():
    assert parse_serve_mesh("2,4") == (2, 4)
    assert parse_serve_mesh("1,1") == (1, 1)
    for bad in ("", "8", "2,4,1", "a,b", "0,4", "2,-1"):
        with pytest.raises(ValueError):
            parse_serve_mesh(bad)


def test_serve_mesh_shape(devices8):
    mesh = serve_mesh(2, 4, devices=devices8)
    assert mesh.shape["data"] == 2 and mesh.shape["model"] == 4
    with pytest.raises(ValueError):
        serve_mesh(4, 4, devices=devices8)      # needs 16 devices


def test_engine_rejects_mesh_model_mismatch(devices8, model_and_params):
    """A nontrivial 'model' axis demands a tensor_parallel model (and
    vice versa) — the same early guard the training mesh has."""
    model, params = model_and_params
    parallel_state.set_mesh(serve_mesh(2, 4, devices=devices8))
    try:
        with pytest.raises(ValueError, match="tensor_parallel"):
            ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN)
    finally:
        parallel_state.set_mesh(None)


# ------------------------------------------------- TP-sharded serving


def test_tp_sharded_serving_token_identity(devices8, model_and_params,
                                           tmp_path, compile_events):
    """The acceptance bar (fp): greedy output of the TP-sharded engine
    on the (data=2, model=4) virtual mesh is token-identical to dense
    generate(); weights AND arenas are really distributed; the decode
    program compiles exactly once with GSPMD shardings."""
    from apex_example_tpu.ops import _config as ops_config
    model, params = model_and_params
    path = str(tmp_path / "tp.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    parallel_state.set_mesh(serve_mesh(2, 4, devices=devices8))
    obs.costmodel.set_default(obs.CostModel(sink=sink))
    try:
        eng = ServeEngine(gpt_tiny(tensor_parallel=True), params,
                          num_slots=SLOTS, max_len=MAX_LEN)
        assert eng.tp == 4 and eng.dp == 2
        # a head-sharded param really is distributed under the mesh
        q = eng.params["layer_0"]["attention"]["query"]["kernel"]
        assert q.addressable_shards[0].data.shape[1] == q.shape[1] // 4
        # ... and so is the KV arena: [NB, BS, H*D] sharded over heads
        ck = next(leaf for p, leaf in
                  jax.tree_util.tree_flatten_with_path(eng.pool.cache)[0]
                  if "cached_key" in str(p[-1]) and "scale" not in str(p[-1]))
        assert ck.addressable_shards[0].data.shape[2] == ck.shape[2] // 4
        reqs = _mixed_requests(stagger=2)
        eng.queue.submit_all(reqs)
        eng.queue.close()
        comps = eng.run(max_steps=2000)
        assert len(comps) == len(reqs)
        _assert_ref_tokens(model, params, comps, err="tp-fp")
        summ = eng.summary_record()
        assert summ["mesh"] == "data=2,model=4"
        assert summ["tp"] == 4 and summ["dp"] == 2
        assert summ["role"] == "both"
        assert not obs_schema.validate_record(summ)
    finally:
        obs.costmodel.set_default(None)
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)
    sink.close()
    # generate() ran under the same armed instance for the refs, so its
    # loop shows up too — every instrumented program compiled ONCE.
    counts = compile_events(path)
    assert counts["serve_decode_step"] == 1, counts
    assert all(v == 1 for v in counts.values()), counts


def test_tp_quant_serving_token_identity(devices8, model_and_params):
    """Quantized serving UNDER TP (the ISSUE 13 'remaining ambition'):
    int8 weights + int8 paged KV on the sharded mesh produce exactly
    the tokens the unsharded quant engine produces."""
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.quant import quantize_params
    model, params = model_and_params
    qparams, _stats = quantize_params(params, "int8")
    reqs = _mixed_requests(n_long=2, n_short=4, seed=9)

    def run(m, p):
        eng = ServeEngine(m, p, num_slots=SLOTS, max_len=MAX_LEN,
                          kv_quant=True, weight_quant="int8")
        eng.queue.submit_all(_clone(reqs))
        eng.queue.close()
        comps = eng.run(max_steps=2000)
        assert {c.status for c in comps} == {"ok"}
        return {tuple(c.request.prompt): c.tokens for c in comps}

    base = run(model, qparams)              # unsharded quant serving
    parallel_state.set_mesh(serve_mesh(2, 4, devices=devices8))
    try:
        tp = run(gpt_tiny(tensor_parallel=True), qparams)
    finally:
        ops_config.set_force_xla(False)
        parallel_state.set_mesh(None)
    assert base == tp


# --------------------------------------------- disaggregated serving


def test_disagg_token_identity_and_tpot_win(model_and_params):
    """The perf acceptance bar: on a mixed long-prompt/short-decode
    workload, the disaggregated pair (prefill role + [SLOTS, 1]-wide
    decode role) serves token-identical output with ZERO lost
    handoffs, at comparable total ticks — and the decode role's TPOT
    p99 is strictly better than the interleaved baseline's, because
    decode ticks stop running the [SLOTS, block_size] prefill
    geometry.  (Wall-clock assertion on the CPU rig: the 8x per-tick
    FLOP gap gives it margin.)"""
    model, params = model_and_params
    reqs = _mixed_requests(stagger=0)

    # Warm BOTH compiled programs (the [4, 8] interleaved step and the
    # [4, 1] decode-role step) so neither side pays its one-time XLA
    # compile inside the measured TPOT — the lru-cached step functions
    # make every later engine at this geometry reuse these programs.
    warm = [Request(prompt=[1, 2, 3], max_new_tokens=2)]
    w = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN)
    w.queue.submit_all(_clone(warm))
    w.queue.close()
    w.run(max_steps=50)
    wt = QueueTransport()
    wp = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="prefill", handoff_sink=wt.send)
    wd = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="decode")
    run_disagg(wp, wd, _clone(warm))

    # interleaved baseline
    base = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN)
    base_reqs = _clone(reqs)
    base.queue.submit_all(base_reqs)
    base.queue.close()
    base_comps = base.run(max_steps=4000)
    _assert_ref_tokens(model, params, base_comps, err="baseline")

    # Disaggregated pair over an in-process transport, driven as the
    # deployment actually runs: each role OWNS its worker — the decode
    # engine's ticks are never interleaved with prefill work on the
    # same thread (run_disagg's lockstep driver is the convergence
    # harness; here each engine's wall-clock tick cost must be what a
    # dedicated worker would pay).
    transport = QueueTransport()
    pe = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="prefill", handoff_sink=transport.send)
    de = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="decode")
    assert de.chunk == 1 and pe.chunk == pe.pool.block_size
    pe.queue.submit_all(_clone(reqs))
    pe.queue.close()
    p_comps = run_prefill_role(pe, transport, max_steps=4000)
    d_comps = run_decode_role(de, transport, max_steps=4000)

    # conservation: every handoff terminated ok on the decode side
    handed = {c.request.uid for c in p_comps if c.status == "handoff"}
    done = {c.request.uid for c in d_comps}
    assert handed == done and len(handed) == len(reqs)
    assert pe.counts["handoff"] == len(reqs)
    assert de.handoffs_in == len(reqs)
    _assert_ref_tokens(model, params, d_comps, err="disagg")

    # comparable total ticks (the decode role does NOT win by just
    # spending more scheduler rounds)
    total = pe.step_count + de.step_count
    assert total <= base.step_count * 1.5 + 4, (total, base.step_count)

    # the perf claim: decode-role TPOT p99 strictly beats the
    # interleaved baseline's on the same workload
    def tpot_p99(comps):
        vals = sorted(c.tpot_s * 1e3 for c in comps
                      if c.status == "ok" and len(c.tokens) > 1)
        assert vals
        return nearest_rank(vals, 99)

    base_p99 = tpot_p99(base_comps)
    disagg_p99 = tpot_p99(d_comps)
    if not disagg_p99 < base_p99:
        # One re-measure before failing: wall-clock p99 on a loaded
        # 2-CPU CI box can eat the ~1.7x per-tick margin in a single
        # unlucky scheduling window.  Both sides re-run, same compiled
        # programs.
        base2 = ServeEngine(model, params, num_slots=SLOTS,
                            max_len=MAX_LEN)
        base2.queue.submit_all(_clone(reqs))
        base2.queue.close()
        base_p99 = tpot_p99(base2.run(max_steps=4000))
        t2 = QueueTransport()
        pe2 = ServeEngine(model, params, num_slots=SLOTS,
                          max_len=MAX_LEN, role="prefill",
                          handoff_sink=t2.send)
        de2 = ServeEngine(model, params, num_slots=SLOTS,
                          max_len=MAX_LEN, role="decode")
        pe2.queue.submit_all(_clone(reqs))
        pe2.queue.close()
        run_prefill_role(pe2, t2, max_steps=4000)
        disagg_p99 = tpot_p99(run_decode_role(de2, t2, max_steps=4000))
    assert disagg_p99 < base_p99, (disagg_p99, base_p99)


def test_handoff_cow_shared_prefix_deep_copy(model_and_params):
    """Handoff of requests whose prefix blocks are COW-shared: the
    payload is a deep copy (mutating it never touches the prefill
    arena), refcounts stay consistent on the prefill side (the shared
    block survives for the sibling and parks reusable at the end),
    and the decode side still produces exactly generate()'s tokens."""
    model, params = model_and_params
    rs = np.random.RandomState(2)
    # 24-token prompts: a 20-token shared prefix + 4 divergent tokens.
    # The first request's 3rd block fills during its own prefill (24 is
    # block-aligned), so later arrivals chain-match 2 full blocks AND
    # partially overlap into the 3rd — mapped immutable, so their first
    # divergent write COWs it inside the compiled step.  Arrivals are
    # staggered so each handoff completes (and registers its blocks)
    # before the next request admits.
    prefix = [int(t) for t in rs.randint(0, 256, 20)]
    reqs = [Request(prompt=prefix + [int(t) for t in rs.randint(0, 256,
                                                                4)],
                    max_new_tokens=6, arrival_step=i * 5)
            for i in range(3)]

    transport = QueueTransport()
    pe = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="prefill", handoff_sink=transport.send)
    de = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="decode")
    pe.queue.submit_all(reqs)
    pe.queue.close()
    pe.run(max_steps=200)
    handoffs = transport.poll()
    assert len(handoffs) == 3
    # prefix sharing AND a copy-on-write actually happened on the
    # prefill side: the later requests mapped the first one's blocks
    # (2 full + a partial overlap) and COW'd the partial block at
    # their first divergent write.
    assert pe.pool.prefix_hit_rate() > 0
    assert pe.pool.cow_copies >= 1
    # every slot evicted; the shared prefix blocks parked REUSABLE
    # (refcount 0 but indexed), nothing still mapped
    assert pe.pool.free_count == SLOTS
    assert pe.pool.alloc.blocks_in_use == 0
    assert all(r == 0 for r in pe.pool.alloc.refcount)

    # deep copy: corrupting one handoff's payload in place must not
    # leak into the prefill arena or into a SIBLING handoff that
    # shared the same prefix blocks
    h0, h1 = handoffs[0], handoffs[1]
    key = next(k for k in h0.payload if "cached_key" in k
               and "scale" not in k)
    before_arena = np.asarray(
        next(leaf for p, leaf in
             jax.tree_util.tree_flatten_with_path(pe.pool.cache)[0]
             if "cached_key" in str(p[-1])
             and "scale" not in str(p[-1])))
    before_sibling = h1.payload[key].copy()
    h0.payload[key][:] = 0
    after_arena = np.asarray(
        next(leaf for p, leaf in
             jax.tree_util.tree_flatten_with_path(pe.pool.cache)[0]
             if "cached_key" in str(p[-1])
             and "scale" not in str(p[-1])))
    np.testing.assert_array_equal(before_arena, after_arena)
    np.testing.assert_array_equal(before_sibling, h1.payload[key])

    # the UNtouched handoffs decode to generate()'s tokens (h0 was
    # deliberately corrupted above, so it is excluded)
    transport.close()
    for h in handoffs[1:]:
        assert de.admit_handoff(h)
    while de.pool.any_live():
        de.step()
    _assert_ref_tokens(model, params, de.completions, err="cow-handoff")
    assert len(de.completions) == 2


def test_handoff_reject_and_requeue(model_and_params):
    """Decode-side admission control: a handoff that can NEVER fit
    terminates first-class as "rejected" (consumed, no crash); one
    that exceeds the free capacity right now is requeued with no
    state left behind and admits cleanly after space frees."""
    model, params = model_and_params
    de = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="decode")

    def fake_handoff(prompt_len, max_new, fill=None):
        rs = np.random.RandomState(prompt_len)
        req = Request(prompt=[int(t) for t in rs.randint(0, 256,
                                                         prompt_len)],
                      max_new_tokens=max_new)
        fill = prompt_len if fill is None else fill
        n_blocks = -(-fill // de.pool.block_size)
        payload = {}
        for p, leaf in jax.tree_util.tree_flatten_with_path(
                de.pool.cache)[0]:
            name = str(p[-1])
            if "cached_" in name:
                key = "/".join(getattr(x, "key", str(x)) for x in p)
                payload[key] = np.zeros(
                    (n_blocks,) + tuple(leaf.shape[1:]),
                    dtype=np.asarray(leaf[:0]).dtype)
        return KvHandoff(
            uid=req.uid, request=req,
            tokens=[int(t) for t in req.prompt] + [0],
            fill=fill, block_size=de.pool.block_size,
            kv_dtype=de.pool.kv_dtype, payload=payload,
            payload_bytes=sum(int(a.nbytes) for a in payload.values()),
            t_out_wall=0.0, src="test")

    # (a) unservable: the prompt fills the whole cache, so the output
    # budget is zero -> rejected first-class, consumed, no state
    h_bad = fake_handoff(8, 4)
    h_bad.request = Request(prompt=[1] * MAX_LEN, max_new_tokens=4)
    assert de.admit_handoff(h_bad) is True
    assert de.counts["rejected"] == 1
    assert de.pool.free_count == SLOTS          # nothing left behind

    # (b) transient pressure: fill every slot, then one more handoff
    # defers (False, requeued once) and admits after an eviction
    live = [fake_handoff(8 + i, 6) for i in range(SLOTS)]
    for h in live:
        assert de.admit_handoff(h) is True
    extra = fake_handoff(20, 6)
    assert de.admit_handoff(extra) is False
    assert de.admit_handoff(extra) is False     # deterministic retry
    assert extra.requeued == 1                  # one episode, not two
    assert de.handoff_requeued == 1
    de.pool.evict(0)                            # space frees
    assert de.admit_handoff(extra) is True
    assert de.handoffs_in == SLOTS + 1
    # drop the live slots without stepping (host-side teardown)
    for i in de.pool.live:
        de.pool.evict(i)


def test_mismatched_geometry_handoff_raises(model_and_params):
    model, params = model_and_params
    de = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="decode")
    h = KvHandoff(uid="x", request=Request(prompt=[1, 2],
                                           max_new_tokens=2),
                  tokens=[1, 2, 3], fill=2, block_size=4,
                  kv_dtype="float32", payload={}, payload_bytes=0,
                  t_out_wall=0.0)
    with pytest.raises(ValueError, match="block_size"):
        de.admit_handoff(h)


def test_file_transport_round_trip_int8(model_and_params, tmp_path):
    """FileTransport ships int8 payload + bf16 scales byte-exactly:
    the decode side's tokens match the in-process int8 interleaved
    engine's, through a spool directory and process-shaped load."""
    model, params = model_and_params
    reqs = _mixed_requests(n_long=1, n_short=3, seed=13)

    base = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                       kv_quant=True)
    base.queue.submit_all(_clone(reqs))
    base.queue.close()
    base_map = {tuple(c.request.prompt): c.tokens
                for c in base.run(max_steps=2000)}

    spool = str(tmp_path / "spool")
    tx = FileTransport(spool)
    pe = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="prefill", handoff_sink=tx.send, kv_quant=True)
    pe.queue.submit_all(_clone(reqs))
    pe.queue.close()
    run_prefill_role(pe, tx)
    assert os.path.exists(os.path.join(spool, tx.SENTINEL))

    rx = FileTransport(spool)
    de = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="decode", kv_quant=True)
    comps = run_decode_role(de, rx)
    assert {c.status for c in comps} == {"ok"}
    assert {tuple(c.request.prompt): c.tokens for c in comps} == base_map
    # spool fully consumed
    assert not [n for n in os.listdir(spool) if n.endswith(".npz")]
    # int8 payloads were really what moved
    assert de.handoffs_in == len(reqs)
    summ = de.summary_record()
    assert summ["kv_dtype"] == "int8"
    assert summ["handoffs_in"] == len(reqs)
    assert "handoff_ms" in summ
    assert not obs_schema.validate_record(summ)


# ------------------------------------------------------- schema v12


def test_schema_v12_records_validate():
    assert obs_schema.SCHEMA_VERSION >= 12
    good = [
        {"record": "kv_handoff", "time": 1.0, "request_id": "r1",
         "direction": "out", "fill": 24, "blocks": 3,
         "payload_bytes": 9216, "kv_dtype": "int8",
         "prompt_tokens": 24, "first_token": 7, "src": "prefill",
         "run_id": "x"},
        {"record": "kv_handoff", "time": 1.0, "request_id": "r1",
         "direction": "in", "fill": 24, "blocks": 3,
         "payload_bytes": 9216, "handoff_ms": 1.25, "requeued": 1,
         "dst": "decode"},
        {"record": "serve_summary", "time": 1.0, "requests": 4,
         "output_tokens": 40, "tokens_per_sec": 10.0,
         "role": "decode", "mesh": "data=2,model=4", "dp": 2, "tp": 4,
         "handoffs_in": 4, "handoff_requeued": 1,
         "handoff_bytes": 36864,
         "handoff_ms": {"p50": 1.0, "p95": 2.0, "max": 2.0}},
        {"record": "replica_state", "time": 1.0, "replica": "r0",
         "state": "serving", "kv_bytes_live": 8448},
    ]
    for rec in good:
        assert not obs_schema.validate_record(rec), rec
    bad = [
        {"record": "kv_handoff", "time": 1.0, "request_id": "r1",
         "direction": "out"},                      # missing fill/blocks
        {"record": "kv_handoff", "time": 1.0, "request_id": "r1",
         "direction": "out", "fill": 1, "blocks": 1,
         "payload_bytes": 2, "surprise": True},    # unknown field
    ]
    for rec in bad:
        assert obs_schema.validate_record(rec), rec
    # v11 streams (no role/mesh/handoff fields) still validate
    assert not obs_schema.validate_record(
        {"record": "serve_summary", "time": 1.0, "requests": 1,
         "output_tokens": 2, "tokens_per_sec": 1.0})


# ------------------------------------------------- trace continuation


def test_trace_export_joins_handoff_across_streams(model_and_params,
                                                   tmp_path):
    """The satellite bugfix: a prefill-worker request span and its
    decode-worker continuation join into one timeline via the handoff
    uid — a cross-stream flow arrow pair (cat "handoff"), on a merged
    export that stays --check clean."""
    model, params = model_and_params
    p_path = str(tmp_path / "p.jsonl")
    d_path = str(tmp_path / "d.jsonl")
    p_sink = obs.JsonlSink(p_path, rank=0)
    d_sink = obs.JsonlSink(d_path, rank=0)
    reqs = _mixed_requests(n_long=1, n_short=2, seed=21)

    transport = QueueTransport()
    # each engine snapshots the process-default tracer at construction:
    # two engines, two sinks, two streams — the cross-process shape,
    # in-process.
    trace_lib.set_default(obs.Tracer(p_sink, run_id="pre"))
    pe = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="prefill", handoff_sink=transport.send,
                     sink=p_sink, run_id="pre")
    trace_lib.set_default(obs.Tracer(d_sink, run_id="dec"))
    de = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="decode", sink=d_sink, run_id="dec")
    trace_lib.set_default(None)
    p_comps, d_comps = run_disagg(pe, de, reqs)
    p_sink.close()
    d_sink.close()
    assert len(d_comps) == len(reqs)

    trace_export = _load_tool("trace_export")
    assert trace_export.main(["--check", p_path]) == 0
    assert trace_export.main(["--check", d_path]) == 0
    merged = trace_export.export(
        [(p_path, trace_export.read_stream(p_path)),
         (d_path, trace_export.read_stream(d_path))])
    evs = merged["traceEvents"]
    flows_s = [e for e in evs if e.get("ph") == "s"
               and e.get("cat") == "handoff"]
    flows_f = [e for e in evs if e.get("ph") == "f"
               and e.get("cat") == "handoff"]
    assert len(flows_s) == len(reqs) and len(flows_f) == len(reqs)
    # the arrow really crosses processes (prefill pid -> decode pid)
    pids = {(s["pid"], f["pid"]) for s, f in zip(flows_s, flows_f)}
    assert all(a != b for a, b in pids)
    # arrows bind by id, end-of-prefill-root -> start-of-decode-root
    by_id = {}
    for e in flows_s + flows_f:
        by_id.setdefault(e["id"], []).append(e)
    assert all(len(v) == 2 for v in by_id.values())


# ------------------------------------------------------ tools + gate


def _read_fixture(name):
    with open(os.path.join(FIXTURES, name)) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def test_ci_gate_disagg_fixture_pair(tmp_path):
    """The checked-in recorded prefill+decode pair passes the gate;
    a lost handoff (terminal record removed) fails it."""
    ci_gate = _load_tool("ci_gate")
    pre = os.path.join(FIXTURES, "prefill.jsonl")
    dec = os.path.join(FIXTURES, "decode.jsonl")
    assert ci_gate.main(["--disagg-stream", pre,
                         "--disagg-stream", dec]) == 0

    # tamper: drop one decode-side request_complete -> LOST -> exit 1
    records = _read_fixture("decode.jsonl")
    dropped = False
    tampered = []
    for r in records:
        if not dropped and r.get("record") == "request_complete":
            dropped = True
            continue
        tampered.append(r)
    assert dropped
    bad = str(tmp_path / "decode_lost.jsonl")
    with open(bad, "w") as fh:
        for r in tampered:
            fh.write(json.dumps(r) + "\n")
    assert ci_gate.main(["--disagg-stream", pre,
                         "--disagg-stream", bad]) == 1


def test_serve_report_handoff_line(capsys):
    serve_report = _load_tool("serve_report")
    assert serve_report.main([os.path.join(FIXTURES,
                                           "decode.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "HANDOFF:" in out
    assert "transit p50" in out and "p99" in out
    assert serve_report.main([os.path.join(FIXTURES,
                                           "prefill.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "HANDOFF:" in out and "out /" in out


def test_metrics_lint_fixture_streams():
    lint = _load_tool("metrics_lint")
    for name in ("prefill.jsonl", "decode.jsonl"):
        code, errors = lint.lint(os.path.join(FIXTURES, name))
        assert code == 0, errors


# ------------------------------------- leased handoff crash safety


def _reqs(n, seed, max_new=5):
    rs = np.random.RandomState(seed)
    return [Request(prompt=[int(t) for t in rs.randint(0, 256,
                                                       4 + i % 4)],
                    max_new_tokens=max_new) for i in range(n)]


def _spool_prefill(model, params, spool, reqs, sink=None, fault=None):
    """Chunk-prefill ``reqs`` into ``spool`` (sentinel written unless
    the fault eats it); returns the prefill engine."""
    tx = FileTransport(spool, worker="prefill", fault=fault)
    pe = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                     role="prefill", handoff_sink=tx.send, sink=sink,
                     rng=jax.random.PRNGKey(0))
    pe.queue.submit_all(reqs)
    pe.queue.close()
    run_prefill_role(pe, tx, max_steps=500)
    return pe


def _decode_engine(model, params, sink=None):
    return ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                       role="decode", sink=sink,
                       rng=jax.random.PRNGKey(0))


def _header(sink):
    obs.TelemetryEmitter(sink).run_header(
        config={"slots": SLOTS, "max_len": MAX_LEN}, argv=["serve.py"],
        arch="gpt_tiny")


def test_lease_claim_reclaim_and_adopt(model_and_params, tmp_path):
    """The lease protocol at the transport level: claims are exclusive
    while the lease holds, an expired claim is reclaimed by ANY peer
    (redelivered=1), and ack-by-delete drains the spool for the
    directory-wide finished()."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    _spool_prefill(model, params, spool, _reqs(2, seed=31))
    a = FileTransport(spool, worker="a", lease_s=0.05)
    got = a.poll()
    assert len(got) == 2 and all(h.redelivered == 0 for h in got)
    assert a.pending_on_disk() == 2         # claims still on disk
    b = FileTransport(spool, worker="b", lease_s=30.0)
    assert b.poll() == []                   # a's lease still holds
    time.sleep(0.08)                        # ...until it expires
    got_b = b.poll()
    assert len(got_b) == 2 and all(h.redelivered == 1 for h in got_b)
    assert b.reclaimed == 2
    for h in got_b:
        b.ack(h)
    assert b.pending_on_disk() == 0 and b.finished()


def test_lease_renewal_keeps_deferred_claims(model_and_params,
                                             tmp_path):
    """Review fix (ISSUE 15): a live worker whose admissions are
    deferred past the lease must RENEW its claims — without renewal a
    peer would reclaim and double-serve work the holder still owns."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    _spool_prefill(model, params, spool, _reqs(2, seed=41))
    a = FileTransport(spool, worker="a", lease_s=0.1)
    pending = a.poll()
    assert len(pending) == 2
    b = FileTransport(spool, worker="b", lease_s=30.0)
    for _ in range(4):                  # well past the original lease
        time.sleep(0.06)
        a.renew(pending)                # the drive loop's per-tick call
        assert b.poll() == []           # the peer never gets them
    for h in pending:
        a.ack(h)                        # renewal tracked the renamed
    assert a.pending_on_disk() == 0     #   claim files correctly


def test_lease_adopts_own_claims_without_wait(model_and_params,
                                              tmp_path):
    """A worker coming back under its OWN id (supervised restart)
    adopts its pre-crash claims immediately — no lease wait."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    _spool_prefill(model, params, spool, _reqs(1, seed=32))
    a1 = FileTransport(spool, worker="a", lease_s=60.0)
    assert len(a1.poll()) == 1              # claimed, never acked
    a2 = FileTransport(spool, worker="a", lease_s=60.0)
    got = a2.poll()
    assert len(got) == 1 and got[0].redelivered == 1


def test_ack_crash_window_exactly_once(model_and_params, tmp_path):
    """Satellite (ISSUE 15): kill the decode worker between
    ``admit_handoff`` and ``ack``.  The claim survives on disk; the
    restarted worker adopts it, the engine's seen-set detects the
    redelivery as a duplicate (acked, nothing scattered twice), and
    every request completes exactly once with tokens identical to the
    fault-free run — the recorded pair passing the v13
    ci_gate --disagg-stream."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    p_path = str(tmp_path / "prefill.jsonl")
    d_path = str(tmp_path / "decode.jsonl")
    reqs = _reqs(5, seed=33)
    p_sink = obs.JsonlSink(p_path, rank=0)
    _header(p_sink)
    pe = _spool_prefill(model, params, spool, reqs, sink=p_sink)
    p_sink.write(pe.summary_record())
    p_sink.close()

    d_sink = obs.JsonlSink(d_path, rank=0)
    _header(d_sink)
    de = _decode_engine(model, params, sink=d_sink)
    rx = FileTransport(spool, worker="d0")
    fault = FaultPlan("handoff_crash_preack", 2, kinds=SERVE_KINDS)
    with pytest.raises(FaultInjected):
        run_decode_role(de, rx, max_steps=500, fault=fault)
    assert rx.pending_on_disk() >= 1        # the unacked claim survived

    rx2 = FileTransport(spool, worker="d0")  # the restarted worker
    comps = run_decode_role(de, rx2, max_steps=500)
    assert len(comps) == len(reqs)
    assert {c.status for c in comps} == {"ok"}
    uids = [c.request.uid for c in comps]
    assert len(set(uids)) == len(reqs)      # exactly once, every uid
    assert de.handoff_duplicates == 1       # the redelivered admit-2
    assert de.handoffs_in == len(reqs)      # dup not double-counted
    _assert_ref_tokens(model, params, comps, err="ack-crash")
    summ = de.summary_record()
    assert summ["handoff_duplicates"] == 1
    assert summ.get("handoff_redelivered", 0) >= 1
    assert not obs_schema.validate_record(summ)
    d_sink.write(summ)
    d_sink.close()
    assert rx2.finished()                   # spool fully drained
    ci_gate = _load_tool("ci_gate")
    assert ci_gate.main(["--disagg-stream", p_path,
                         "--disagg-stream", d_path]) == 0


def test_torn_payload_quarantined_worker_alive(model_and_params,
                                               tmp_path, capsys):
    """Satellite bugfix (ISSUE 15): a truncated/corrupt spool payload
    must quarantine to *.bad with a warn record — the decode worker
    keeps ticking and finishes everything else; the stream stays
    v13-valid and passes the disagg gate."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    p_path = str(tmp_path / "prefill.jsonl")
    d_path = str(tmp_path / "decode.jsonl")
    reqs = _reqs(4, seed=34)
    p_sink = obs.JsonlSink(p_path, rank=0)
    _header(p_sink)
    fault = FaultPlan("handoff_torn", 2, kinds=SERVE_KINDS)
    pe = _spool_prefill(model, params, spool, reqs, sink=p_sink,
                        fault=fault)
    p_sink.write(pe.summary_record())
    p_sink.close()

    d_sink = obs.JsonlSink(d_path, rank=0)
    _header(d_sink)
    de = _decode_engine(model, params, sink=d_sink)
    quarantined = []

    def on_quarantine(uid, spool_name, error, nbytes):
        # The serve.py wiring, in miniature: record the disposition.
        quarantined.append(uid)
        d_sink.write({"record": "kv_handoff", "time": time.time(),
                      "request_id": uid, "direction": "quarantine",
                      "fill": 0, "blocks": 0,
                      "payload_bytes": int(nbytes),
                      "spool_file": spool_name,
                      "error": str(error)[:200]})

    rx = FileTransport(spool, worker="d0", on_quarantine=on_quarantine)
    comps = run_decode_role(de, rx, max_steps=500)
    assert len(comps) == len(reqs) - 1      # the torn one never admits
    assert {c.status for c in comps} == {"ok"}
    assert rx.quarantined == 1 and len(quarantined) == 1
    assert any(n.endswith(".bad") for n in os.listdir(spool))
    assert rx.finished()                    # .bad is a disposition
    _assert_ref_tokens(model, params, comps, err="torn")
    summ = de.summary_record()
    summ["handoff_quarantined"] = rx.quarantined   # the serve.py merge
    assert not obs_schema.validate_record(summ)
    d_sink.write(summ)
    d_sink.close()
    ci_gate = _load_tool("ci_gate")
    assert ci_gate.main(["--disagg-stream", p_path,
                         "--disagg-stream", d_path]) == 0
    serve_report = _load_tool("serve_report")
    assert serve_report.main([d_path]) == 0
    out = capsys.readouterr().out
    assert "REDELIVERY:" in out and "1 payload(s) quarantined" in out


def test_duplicate_delivery_drill(model_and_params, tmp_path):
    """The handoff_dup drill: the same payload delivered twice is
    detected against the seen-set, acked without a second scatter, and
    the request still completes exactly once."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    reqs = _reqs(3, seed=35)
    _spool_prefill(model, params, spool, reqs)
    de = _decode_engine(model, params)
    rx = FileTransport(spool, worker="d0")
    fault = FaultPlan("handoff_dup", 1, kinds=SERVE_KINDS)
    comps = run_decode_role(de, rx, max_steps=500, fault=fault)
    assert len(comps) == len(reqs)
    assert {c.status for c in comps} == {"ok"}
    assert de.handoff_duplicates == 1
    assert de.handoffs_in == len(reqs)
    assert rx.finished()
    _assert_ref_tokens(model, params, comps, err="dup")


def test_sentinel_lost_idle_timeout(model_and_params, tmp_path):
    """The sentinel_lost drill: the producer dies without closing the
    stream.  A decode worker with an idle timeout finishes what is
    spooled and exits instead of spinning forever."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    reqs = _reqs(3, seed=36)
    fault = FaultPlan("sentinel_lost", 1, kinds=SERVE_KINDS)
    _spool_prefill(model, params, spool, reqs, fault=fault)
    assert not os.path.exists(os.path.join(spool,
                                           FileTransport.SENTINEL))
    de = _decode_engine(model, params)
    rx = FileTransport(spool, worker="d0")
    comps = run_decode_role(de, rx, max_steps=2000,
                            idle_wait_s=0.01, idle_timeout_s=0.3)
    assert len(comps) == len(reqs)          # everything spooled served
    assert {c.status for c in comps} == {"ok"}
    assert not rx.finished()                # the stream never closed


def test_handoff_drill_requires_matching_role(tmp_path):
    """serve.py rejects a handoff drill on the wrong role (a silently
    inert drill is worse than an error)."""
    import serve as serve_cli
    args = serve_cli.build_parser().parse_args(
        ["--role", "decode", "--handoff-dir", str(tmp_path / "s"),
         "--inject-fault", "handoff_torn@1"])
    with pytest.raises(SystemExit, match="prefill-side"):
        serve_cli.run_serve(args)
    args = serve_cli.build_parser().parse_args(
        ["--role", "decode", "--handoff-dir", str(tmp_path / "s"),
         "--inbox", str(tmp_path / "in.jsonl")])
    with pytest.raises(SystemExit, match="no --inbox"):
        serve_cli.run_serve(args)


def test_schema_v13_records_validate():
    assert obs_schema.SCHEMA_VERSION >= 13
    good = [
        {"record": "kv_handoff", "time": 1.0, "request_id": "r1",
         "direction": "in", "fill": 24, "blocks": 3,
         "payload_bytes": 9216, "handoff_ms": 1.0, "requeued": 0,
         "redelivered": 1, "dst": "decode"},
        {"record": "kv_handoff", "time": 1.0, "request_id": "r1",
         "direction": "in", "fill": 24, "blocks": 0,
         "payload_bytes": 9216, "duplicate": True, "redelivered": 1,
         "dst": "decode"},
        {"record": "kv_handoff", "time": 1.0, "request_id": "r2",
         "direction": "quarantine", "fill": 0, "blocks": 0,
         "payload_bytes": 123, "spool_file": "handoff-000002-r2.npz",
         "error": "corrupt npz"},
        {"record": "serve_summary", "time": 1.0, "requests": 4,
         "output_tokens": 40, "tokens_per_sec": 10.0, "role": "decode",
         "handoffs_in": 4, "handoff_duplicates": 1,
         "handoff_redelivered": 2, "handoff_quarantined": 1},
        {"record": "replica_state", "time": 1.0, "replica": "d0",
         "state": "serving", "role": "decode", "kv_bytes_live": 64},
        {"record": "fleet_summary", "time": 1.0, "replicas": 3,
         "requests": 10, "availability": 1.0, "prefill_replicas": 1,
         "decode_replicas": 2, "handoffs": 10,
         "handoff_redelivered": 1, "in_spool": 0},
    ]
    for rec in good:
        assert not obs_schema.validate_record(rec), rec
    bad = [
        {"record": "kv_handoff", "time": 1.0, "request_id": "r1",
         "direction": "in", "fill": 1, "blocks": 1,
         "payload_bytes": 2, "redelivered": "yes"},   # wrong type
        {"record": "fleet_summary", "time": 1.0, "replicas": 1,
         "requests": 1, "availability": 1.0, "spool_leak": 1},
    ]
    for rec in bad:
        assert obs_schema.validate_record(rec), rec


def test_ci_gate_rejects_unflagged_double_admission(tmp_path):
    """The v13 conservation rule: redelivery episodes are tolerated,
    but two PLAIN admissions of one uid (no redelivered/duplicate
    provenance) mean two workers silently double-served it — the gate
    must fail."""
    ci_gate = _load_tool("ci_gate")
    records = _read_fixture("decode.jsonl")
    plain = next(r for r in records
                 if r.get("record") == "kv_handoff"
                 and r.get("direction") == "in"
                 and not r.get("duplicate") and not r.get("redelivered"))
    doubled = []
    for r in records:
        doubled.append(r)
        if r is plain:
            doubled.append(dict(plain))     # a second plain admission
    bad = str(tmp_path / "decode_double.jsonl")
    with open(bad, "w") as fh:
        for r in doubled:
            fh.write(json.dumps(r) + "\n")
    pre = os.path.join(FIXTURES, "prefill.jsonl")
    assert ci_gate.main(["--disagg-stream", pre,
                         "--disagg-stream", bad]) == 1


def test_fixture_pair_records_a_redelivery():
    """The checked-in pair IS a recorded redelivery episode: the
    decode stream carries redelivered admissions and a duplicate-ack,
    and still passes the gate (test_ci_gate_disagg_fixture_pair)."""
    records = _read_fixture("decode.jsonl")
    ins = [r for r in records if r.get("record") == "kv_handoff"
           and r.get("direction") == "in"]
    assert any(r.get("redelivered") and not r.get("duplicate")
               for r in ins)
    assert any(r.get("duplicate") for r in ins)
    summ = next(r for r in records
                if r.get("record") == "serve_summary")
    assert summ["handoff_duplicates"] == 1
    assert summ["handoff_redelivered"] >= 1


def test_supervisor_strips_handoff_drills_on_restart():
    """Satellite (ISSUE 15): --drop-flag-on-restart=--inject-fault
    strips handoff_*@N drills from restart attempts exactly like
    exact-tick serve drills — a restarted decode worker replays the
    spool from its claim set, so the drill would re-fire."""
    spec = importlib.util.spec_from_file_location(
        "apex_sup_test", os.path.join(REPO, "apex_example_tpu",
                                      "resilience", "supervisor.py"))
    sup = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sup)
    argv = ["python", "serve.py", "--role", "decode",
            "--inject-fault", "handoff_crash_preack@1", "--slots", "4"]
    out = sup._strip_flag(argv, "--inject-fault")
    assert out == ["python", "serve.py", "--role", "decode",
                   "--slots", "4"]
    out = sup._strip_flag(["x", "--inject-fault=handoff_torn@2", "y"],
                          "--inject-fault")
    assert out == ["x", "y"]


# --------------------------------------------------- subprocess e2e


def test_disagg_subprocess_pair_e2e(tmp_path):
    """THE one new subprocess e2e: a serve.py --role prefill process
    spools handoffs to disk, a --role decode process consumes them —
    each stream schema-v12 valid with exactly one serve_summary for
    its role, the compile-once gate holds PER ROLE (one prefill
    program, one decode program), zero handoffs lost, and the
    ci_gate/serve_report tooling passes over the recorded pair."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    spool = str(tmp_path / "spool")
    p_jsonl = str(tmp_path / "prefill.jsonl")
    d_jsonl = str(tmp_path / "decode.jsonl")
    common = ["--slots", "4", "--max-len", "32", "--seed", "3",
              "--cost-model"]
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "serve.py"),
         "--requests", "6", "--role", "prefill", "--handoff-dir", spool,
         "--metrics-jsonl", p_jsonl] + common,
        env=env, cwd=REPO, timeout=240).returncode
    assert rc == 0
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "serve.py"),
         "--role", "decode", "--handoff-dir", spool,
         "--metrics-jsonl", d_jsonl] + common,
        env=env, cwd=REPO, timeout=240).returncode
    assert rc == 0

    lint = _load_tool("metrics_lint")
    for path in (p_jsonl, d_jsonl):
        code, errors = lint.lint(path)
        assert code == 0, errors
    p_recs = [json.loads(l) for l in open(p_jsonl) if l.strip()]
    d_recs = [json.loads(l) for l in open(d_jsonl) if l.strip()]
    p_summ = [r for r in p_recs if r["record"] == "serve_summary"]
    d_summ = [r for r in d_recs if r["record"] == "serve_summary"]
    assert len(p_summ) == 1 and p_summ[0]["role"] == "prefill"
    assert len(d_summ) == 1 and d_summ[0]["role"] == "decode"
    assert p_summ[0]["handoffs_out"] == 6
    assert d_summ[0]["handoffs_in"] == 6
    assert d_summ[0]["completed"] == 6

    # compile-once PER ROLE: one program each, under its own name
    from apex_example_tpu.obs.costmodel import compile_counts
    assert compile_counts(p_recs) == {"serve_prefill_step": 1}
    assert compile_counts(d_recs) == {"serve_decode_step": 1}

    ci_gate = _load_tool("ci_gate")
    assert ci_gate.main(["--disagg-stream", p_jsonl,
                         "--disagg-stream", d_jsonl]) == 0
    serve_report = _load_tool("serve_report")
    assert serve_report.main([d_jsonl]) == 0
