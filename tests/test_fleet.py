"""Fleet stratum (apex_example_tpu/fleet/, fleet.py; ISSUE 12):

- router core on tiny no-jax fake replicas: policy selection,
  requeue-on-drain exactly-once, circuit-break/half-open, deadline-
  aware retry, backlog admission — all sub-second, no compiles,
- schema v10 (route / replica_state / fleet_summary, restart
  classification) + v1-v9 back-compat,
- the loadgen substream satellite (disjoint-yet-deterministic
  per-replica workloads),
- supervisor restart classification (two tiny no-jax subprocess
  children, the test_trace pattern),
- in-process chaos on ThreadReplicas riding the session's
  SLOTS=4/MAX_LEN=32 compiled decode program (zero new compiles):
  fleet-wide token identity vs one-shot generate(), deterministic
  crash_storm scores, straggler stall-rescue, thread-mode rolling
  restart,
- ci_gate --fleet-stream + fleet_report serve-fleet mode over the
  checked-in rolling_restart scenario stream,
- THE one new subprocess e2e: rolling restart over 2 supervised
  serve.py replicas — zero lost requests, availability 1.0, one
  trace_id, merged trace --check clean.
"""

import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu import obs
from apex_example_tpu.fleet import (FleetRouter, ThreadReplica,
                                    run_scenario, synthetic_specs)
from apex_example_tpu.models.gpt import generate, gpt_tiny
from apex_example_tpu.obs import schema as obs_schema
from apex_example_tpu.resilience.faults import SERVE_KINDS, FaultPlan
from apex_example_tpu.serve import (Request, ServeEngine, substream,
                                    synthetic_requests)

pytestmark = pytest.mark.fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "fleet",
                       "rolling_restart.jsonl")
SLOTS, MAX_LEN = 4, 32          # the session-shared decode geometry


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_supervisor():
    spec = importlib.util.spec_from_file_location(
        "apex_supervisor_fleet_test",
        os.path.join(REPO, "apex_example_tpu", "resilience",
                     "supervisor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ================================================== no-jax router core

class FakeReplica:
    """The replica contract, scripted: dispatched specs are recorded,
    terminal events are queued by the test and handed to the next
    poll().  No engine, no thread, no jax — the router-core tests run
    sub-second."""

    def __init__(self, name, pending=0, blocks_live=0):
        self.name = name
        self.specs = []
        self.events = []
        self._state = {"state": "healthy", "pending": pending,
                       "blocks_live": blocks_live,
                       "progress_age_s": 0.0, "pid": None,
                       "restarts": 0}
        self.accept = True

    def submit(self, spec):
        if not self.accept:
            return False
        self.specs.append(spec)
        return True

    def poll(self):
        out, self.events = self.events, []
        return out

    def state(self):
        return dict(self._state, name=self.name)

    def set_state(self, **kw):
        self._state.update(kw)

    def report(self, uid, status, **kw):
        self.events.append(dict({"uid": uid, "status": status,
                                 "replica": self.name}, **kw))

    def start(self):
        return self

    def stop(self, *a, **k):
        pass


class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


def _spec(uid, **kw):
    return dict({"uid": uid, "prompt": [1, 2, 3], "max_new_tokens": 4},
                **kw)


def test_policy_round_robin_cycles():
    reps = [FakeReplica(f"r{i}") for i in range(3)]
    router = FleetRouter(reps, policy="round_robin", log=None)
    for i in range(6):
        router.submit(_spec(f"u{i}"))
    assert [len(r.specs) for r in reps] == [2, 2, 2]
    assert [s["uid"] for s in reps[0].specs] == ["u0", "u3"]
    assert [s["uid"] for s in reps[1].specs] == ["u1", "u4"]


def test_policy_least_pending_and_least_kv_use_tailed_gauges():
    reps = [FakeReplica("r0", pending=5, blocks_live=9),
            FakeReplica("r1", pending=0, blocks_live=4),
            FakeReplica("r2", pending=2, blocks_live=0)]
    router = FleetRouter(reps, policy="least_pending", log=None)
    router.poll()                       # pull the health gauges in
    router.submit(_spec("u0"))
    assert [len(r.specs) for r in reps] == [0, 1, 0]

    router2 = FleetRouter(reps, policy="least_kv", log=None)
    router2.poll()
    router2.submit(_spec("k0"))
    assert len(reps[2].specs) == 1      # fewest live KV blocks wins


def test_policy_least_kv_prefers_dtype_accurate_bytes():
    """v12 (ISSUE 14): two replicas holding the SAME block count but
    different arena precisions — least_kv keys on the dtype-accurate
    ``kv_bytes_live`` gauge a sharded/quantized replica heartbeats, so
    the int8 replica (fewer real bytes, more headroom) wins; the block
    count alone could not tell them apart."""
    reps = [FakeReplica("bf16", blocks_live=8),
            FakeReplica("int8", blocks_live=8)]
    reps[0].set_state(kv_bytes_live=8 * 8 * 512)
    reps[1].set_state(kv_bytes_live=8 * 8 * 264)
    router = FleetRouter(reps, policy="least_kv", log=None)
    router.poll()
    router.submit(_spec("q0"))
    assert len(reps[1].specs) == 1 and not reps[0].specs


def test_proc_replica_passes_mesh_flags_through(tmp_path):
    """ISSUE 14 satellite: a ProcReplica built with sharding serve_args
    spawns a supervised child whose argv carries them verbatim — the
    supervisor wrapper must not eat --mesh/--role flags."""
    from apex_example_tpu.fleet.replica import ProcReplica
    rep = ProcReplica("r0", str(tmp_path), REPO,
                      serve_args=["--mesh", "1,2", "--slots", "2"])
    argv = rep.argv()
    assert argv[argv.index("--mesh") + 1] == "1,2"
    assert argv.index("--mesh") > argv.index("--")   # on the CHILD side


def test_requeue_on_drain_exactly_once(tmp_path):
    a, b = FakeReplica("a"), FakeReplica("b")
    sink = ListSink()
    router = FleetRouter([a, b], sink=sink, log=None)
    router.submit(_spec("u1"))
    assert len(a.specs) == 1
    a.report("u1", "drained")
    router.poll()
    # handed to the sibling, exactly once
    assert [s["uid"] for s in b.specs] == ["u1"]
    a.report("u1", "drained")           # duplicate drain report
    router.poll()
    assert len(b.specs) == 1            # NOT re-dispatched
    b.report("u1", "ok", tokens=[7])
    router.poll()
    assert router.done()
    summary = router.close()
    assert summary["completed"] == 1
    assert summary["drained_requeued"] == 1
    assert summary["duplicates"] == 1
    assert summary["lost"] == 0
    assert summary["availability"] == 1.0
    reasons = [r["reason"] for r in sink.records
               if r["record"] == "route"]
    assert reasons == ["dispatch", "requeue_drain"]
    requeue = [r for r in sink.records if r["record"] == "route"][1]
    assert requeue["replica"] == "b" and requeue["from_replica"] == "a"


def test_circuit_breaker_opens_half_opens_and_closes():
    a, b = FakeReplica("a"), FakeReplica("b")
    router = FleetRouter([a, b], breaker_backoff_s=0.05, log=None)
    router.submit(_spec("u1"))
    assert len(a.specs) == 1
    # a crashes holding u1: breaker opens, u1 retries onto b
    a.set_state(state="crashed")
    a.report("u1", "lost")
    router.poll()
    assert router._replicas["a"].breaker == "open"
    assert [s["uid"] for s in b.specs] == ["u1"]
    # while open (and still crashed), everything routes around a
    router.submit(_spec("u2"))
    assert len(a.specs) == 1 and len(b.specs) == 2
    b.report("u1", "ok")
    b.report("u2", "ok")
    router.poll()
    # a comes back; after the backoff the NEXT dispatch is the single
    # half-open probe — and a second request routes around the probe
    a.set_state(state="healthy")
    time.sleep(0.06)
    router.poll()
    router.submit(_spec("u3"))
    router.submit(_spec("u4"))
    assert router._replicas["a"].breaker == "half_open"
    assert [s["uid"] for s in a.specs][-1] == "u3"   # the probe
    assert [s["uid"] for s in b.specs][-1] == "u4"   # routed around
    a.report("u3", "ok")
    b.report("u4", "ok")
    router.poll()
    assert router._replicas["a"].breaker == "closed"
    assert router._replicas["a"].fail_streak == 0
    summary = router.close()
    assert summary["completed"] == 4 and summary["lost"] == 0


def test_probe_loss_does_not_charge_the_request_retry_budget():
    """Regression guard (the PR-16 straggler flake): a half-open probe
    that goes down WITH its target replica was the ROUTER's gamble —
    re-opening the breaker is the whole verdict, and the probed uid
    keeps its retry budget.  Without the probe_loss rule a permanently
    wedged replica (hang drill: never crashes, eats every probe for
    stall_after_s) burns the same request's max_retries through
    repeated probes until the router kills it "failed"."""
    a, b = FakeReplica("a"), FakeReplica("b")
    # max_retries=0: ANY charged loss is instantly terminal — the
    # sharpest possible detector for an unwanted charge.
    router = FleetRouter([a, b], max_retries=0,
                         breaker_backoff_s=0.01, log=None)
    # open a's breaker without involving any request
    a.set_state(state="crashed")
    router.poll()
    assert router._replicas["a"].breaker == "open"
    a.set_state(state="healthy")
    time.sleep(0.02)
    router.poll()
    # the next dispatch is the half-open probe — and the probe target
    # wedges again, surfacing the probed uid as lost
    router.submit(_spec("u1"))
    assert [s["uid"] for s in a.specs] == ["u1"]    # u1 IS the probe
    a.set_state(state="crashed")
    a.report("u1", "lost")
    router.poll()
    # probe loss: breaker re-opens, u1 re-routes UNCHARGED (with
    # max_retries=0 any charge would have killed it "failed" here)
    assert router._replicas["a"].breaker == "open"
    assert [s["uid"] for s in b.specs] == ["u1"]
    b.report("u1", "ok")
    router.poll()
    assert router.results["u1"]["status"] == "ok"
    # a plain (non-probe) loss still charges: u2 dies on its first loss
    router.submit(_spec("u2"))
    b.report("u2", "lost")
    router.poll()
    assert router.results["u2"]["status"] == "failed"
    summary = router.close()
    assert summary["completed"] == 1 and summary["failed"] == 1
    assert summary["lost"] == 0
    assert summary["retries"] == 0      # the probe bounce never counted


def test_deadline_aware_retry_and_budget():
    a = FakeReplica("a")
    router = FleetRouter([a], max_retries=1, log=None)
    # expired deadline: lost resolves as timeout, never re-dispatched
    router.submit(_spec("u1", deadline_s=0.01))
    time.sleep(0.02)
    a.report("u1", "lost")
    router.poll()
    assert router.results["u1"]["status"] == "timeout"
    assert len(a.specs) == 1
    # no deadline: retried up to max_retries, then fails first-class
    router.submit(_spec("u2"))
    a.report("u2", "lost")
    router.poll()
    assert [s["uid"] for s in a.specs] == ["u1", "u2", "u2"]
    a.report("u2", "lost")
    router.poll()
    assert router.results["u2"]["status"] == "failed"
    assert len(a.specs) == 3            # budget exhausted, no 4th try
    summary = router.close()
    assert summary["timed_out"] == 1 and summary["failed"] == 1
    assert summary["retries"] == 1 and summary["lost"] == 0
    assert summary["availability"] == 0.0


def test_late_report_from_released_booking_keeps_inflight_accounting():
    """Review regression (ISSUE 12): a late terminal report from a
    replica whose booking was already released (rescue/retry) must not
    decrement that replica's LIVE inflight count — while the one
    replica still holding a live booking for an already-done uid is
    released exactly when its own report arrives."""
    a, b = FakeReplica("a"), FakeReplica("b")
    router = FleetRouter([a, b], breaker_backoff_s=0.01, log=None)
    # u1 -> a; a loses it; retried to b; b completes it; a then gets a
    # NEW request — and only afterwards late-reports u1.
    router.submit(_spec("u1"))
    a.report("u1", "lost")
    router.poll()
    b.report("u1", "ok")
    router.poll()
    router.submit(_spec("u2"))          # rr -> b, then next to a
    router.submit(_spec("u3"))
    holder = "a" if any(s["uid"] == "u3" for s in a.specs) else "b"
    live_before = router._replicas[holder].inflight
    a.report("u1", "ok")                # late report: booking long gone
    router.poll()
    assert router._replicas[holder].inflight == live_before
    assert router._duplicates == 1

    # the inverse: u5 -> a, a loses it, retried to b — then the
    # ABANDONED copy on a completes first.  a's report wins the uid;
    # b's live booking is released by b's own (now duplicate) report.
    router.submit(_spec("u5"))
    src5 = "a" if any(s["uid"] == "u5" for s in a.specs) else "b"
    other = "b" if src5 == "a" else "a"
    [r for r in (a, b) if r.name == src5][0].report("u5", "lost")
    router.poll()                       # retried onto `other`
    [r for r in (a, b) if r.name == src5][0].report("u5", "ok")
    router.poll()
    assert router.results["u5"]["status"] == "ok"
    assert router._replicas[other].inflight >= 1    # still booked
    [r for r in (a, b) if r.name == other][0].report("u5", "ok")
    router.poll()
    assert router._replicas[other].inflight == \
        sum(1 for e in router._inflight.values()
            if e["replica"] == other)   # booking released exactly once


def test_outbox_replay_skips_drained_occurrences_not_uids(tmp_path):
    """Review regression (ISSUE 12): a 'drained' outbox line consumed
    ONE inbox occurrence — the uid itself must stay servable, or a
    drain-requeue routed back to the same replica (single-survivor
    fleet) is silently lost after the restart."""
    import serve as serve_mod

    path = str(tmp_path / "outbox.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({"uid": "u-ok", "status": "ok",
                             "tokens": []}) + "\n")
        fh.write(json.dumps({"uid": "u-drained", "status": "drained"})
                 + "\n")
        fh.write(json.dumps({"uid": "u-double", "status": "drained"})
                 + "\n")
        fh.write(json.dumps({"uid": "u-double", "status": "drained"})
                 + "\n")
    box = serve_mod._Outbox(path)
    assert box.should_skip("u-ok") and box.should_skip("u-ok")
    # one drain = skip exactly one occurrence, then serve
    assert box.should_skip("u-drained")
    assert not box.should_skip("u-drained")
    # two drains = skip exactly two
    assert box.should_skip("u-double")
    assert box.should_skip("u-double")
    assert not box.should_skip("u-double")
    assert not box.should_skip("u-new")
    box.close()


def test_fleet_report_does_not_misread_replica_child_stream(tmp_path):
    """Review regression (ISSUE 12): a serve.py replica child's OWN
    metrics stream carries replica_state heartbeats but is not a
    router stream — it must fall through to the rank path, not error
    as a 'truncated router stream'."""
    report = _load_tool("fleet_report")
    path = str(tmp_path / "child.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps(
            {"record": "replica_state", "time": 1.0, "replica": "r0",
             "state": "serving", "tick": 3, "pending": 0,
             "blocks_live": 2, "pid": 42}) + "\n")
    assert report.load_fleet_records(path) is None

    # ...while a ROUTER stream truncated before its first dispatch
    # still self-identifies (header platform) and gets the truncation
    # diagnostic instead of a nonsensical rank report
    trunc = str(tmp_path / "trunc.jsonl")
    with open(trunc, "w") as fh:
        fh.write(json.dumps(
            {"record": "run_header", "schema": 10, "time": 1.0,
             "run_id": "x", "num_devices": 0, "process_index": 0,
             "platform": "fleet-router", "config": {}}) + "\n")
    assert report.load_fleet_records(trunc) is not None
    assert report.main([trunc]) == 2    # truncated, not a rank stream


def test_backlog_parks_until_capacity_returns():
    a = FakeReplica("a")
    a.set_state(state="stopped")
    sink = ListSink()
    router = FleetRouter([a], sink=sink, log=None)
    router.poll()                       # pull the down state in
    router.submit(_spec("u1"))
    assert a.specs == [] and not router.done()
    router.poll()
    assert a.specs == []                # still parked
    a.set_state(state="healthy")
    router.poll()
    assert [s["uid"] for s in a.specs] == ["u1"]
    route = [r for r in sink.records if r["record"] == "route"][0]
    assert route["reason"] == "backlog"
    a.report("u1", "ok")
    router.poll()
    assert router.done()


def test_router_stream_validates_and_traces(tmp_path, monkeypatch):
    monkeypatch.delenv("APEX_TRACE_ID", raising=False)
    path = str(tmp_path / "fleet.jsonl")
    a, b = FakeReplica("a"), FakeReplica("b")
    router = FleetRouter([a, b], metrics_jsonl=path, trace=True,
                         log=None)
    try:
        router.submit(_spec("u1"))
        a.report("u1", "drained")
        router.poll()
        b.report("u1", "ok")
        router.poll()
        router.scenario, router.verdict = "none", "pass"
        router.close()
    finally:
        monkeypatch.delenv("APEX_TRACE_ID", raising=False)
    records = obs.read_jsonl(path)
    assert obs_schema.validate_stream(records) == []
    kinds = [r["record"] for r in records]
    assert kinds[0] == "run_header"
    assert kinds[-1] == "fleet_summary"
    assert "route" in kinds and "replica_state" in kinds
    # the router's trace side: one clock_sync before the first event,
    # structurally clean under the exporter's lint
    assert sum(1 for k in kinds if k == "clock_sync") == 1
    export = _load_tool("trace_export")
    assert export.check_stream(records, "fleet.jsonl") == []
    ids = {r["trace_id"] for r in records
           if r["record"] in ("trace_event", "clock_sync")}
    assert ids == {router.trace_id}


# ========================================================= schema v10

def test_schema_v10_fleet_records_validate():
    recs = [
        {"record": "route", "time": 1.0, "request_id": "u1",
         "replica": "r0", "policy": "round_robin", "attempt": 0,
         "reason": "dispatch", "run_id": "x"},
        {"record": "route", "time": 1.0, "request_id": "u1",
         "replica": "r1", "reason": "requeue_drain",
         "from_replica": "r0"},
        {"record": "replica_state", "time": 1.0, "replica": "r0",
         "state": "serving", "tick": 3, "pending": 2, "blocks_live": 5,
         "pid": 123, "run_id": "x"},
        {"record": "replica_state", "time": 1.0, "replica": "r0",
         "state": "restarting", "exit_code": 75,
         "classification": "preempted"},
        {"record": "fleet_summary", "time": 1.0, "replicas": 2,
         "requests": 16, "availability": 1.0, "policy": "least_kv",
         "scenario": "rolling_restart", "verdict": "pass",
         "completed": 16, "failed": 0, "timed_out": 0, "shed": 0,
         "cancelled": 0, "rejected": 0, "drained_requeued": 2,
         "retries": 0, "duplicates": 0, "lost": 0,
         "per_replica": {"r0": {"ok": 8}}, "routing": {"skew": 1.0},
         "duration_s": 20.0, "run_id": "x"},
        {"record": "restart", "time": 1.0, "attempt": 0,
         "exit_code": 75, "reason": "preemption",
         "classification": "preempted", "backoff_s": 0.0},
    ]
    for rec in recs:
        assert obs_schema.validate_record(rec) == [], rec
    assert obs_schema.SCHEMA_VERSION >= 10   # v10 tables are a floor
    # malformed: unknown field, missing required, wrong type
    assert obs_schema.validate_record(
        {"record": "route", "time": 1.0, "request_id": "u",
         "replica": "r", "oops": 1}) != []
    assert obs_schema.validate_record(
        {"record": "replica_state", "time": 1.0, "replica": "r"}) != []
    assert obs_schema.validate_record(
        {"record": "fleet_summary", "time": 1.0, "replicas": 2,
         "requests": 1, "availability": "high"}) != []


def test_schema_v1_v9_streams_still_validate():
    old = [
        {"record": "step", "step": 1, "epoch": 0, "loss": 1.0,
         "scale": 1.0, "step_time_ms": 9.0, "items_per_sec": 10.0},
        {"record": "crash_dump", "time": 1.0, "reason": "sigterm"},
        {"record": "request_complete", "time": 1.0, "request_id": "r",
         "prompt_tokens": 3, "output_tokens": 4, "ttft_ms": 1.0,
         "tpot_ms": 1.0, "finish_reason": "length"},
        {"record": "preemption", "time": 1.0, "signal": "SIGTERM",
         "step": 5},
        {"record": "restart", "time": 1.0, "attempt": 0,
         "exit_code": 75, "reason": "preemption"},   # v4: no classification
        {"record": "request_failed", "time": 1.0, "request_id": "r",
         "status": "timeout"},
        {"record": "serve_drain", "time": 1.0, "signal": "SIGTERM"},
        {"record": "compile_event", "time": 1.0, "name": "f",
         "compile_ms": 2.0, "recompile_cause": "dot shape"},
        {"record": "cost_model", "time": 1.0, "name": "f",
         "flops": None},
        {"record": "trace_event", "ph": "X", "name": "tick", "ts": 0.5,
         "dur": 0.1, "tid": "engine", "trace_id": "t"},
        {"record": "clock_sync", "time": 1.0, "ts": 0.4,
         "trace_id": "t"},
    ]
    for rec in old:
        assert obs_schema.validate_record(rec) == [], rec


# ============================================== loadgen substream (sat)

def test_loadgen_substream_disjoint_and_deterministic():
    """Two replicas sharing a base seed used to serve IDENTICAL prompt
    sets; substream(i) derivation makes them disjoint while each stays
    reproducible."""
    assert substream(0, 0) != 0         # index 0 is not the identity
    assert substream(7, 3) == substream(7, 3)
    assert substream(7, 3) != substream(7, 4)
    assert substream(8, 3) != substream(7, 3)
    with pytest.raises(ValueError):
        substream(0, -1)

    def prompts(sub):
        reqs = synthetic_requests(12, vocab_size=256, seed=42,
                                  seed_substream=sub)
        return [tuple(r.prompt) for r in reqs]

    base = prompts(None)
    r0a, r0b, r1 = prompts(0), prompts(0), prompts(1)
    assert r0a == r0b                   # deterministic per index
    assert not set(r0a) & set(r1)       # disjoint across replicas
    assert r0a != base                  # substreamed != raw seed
    # regression: the pre-fix behavior (same seed, no substream) is
    # exactly the identical-prompt-sets bug
    assert prompts(None) == base


# ================================== supervisor classification (satellite)

def test_supervisor_restart_classification(tmp_path):
    """The v10 satellite: restart records say HOW the child died
    (preempted/crashed/stall_killed) so fleet tooling never re-parses
    child streams.  Two tiny no-jax children, the test_trace pattern."""
    sup_mod = _load_supervisor()

    def run_child(first_exit):
        marker = tmp_path / f"ran{first_exit}"
        child = tmp_path / f"c{first_exit}.py"
        child.write_text(
            f"import os, sys\n"
            f"if os.path.exists({str(marker)!r}): sys.exit(0)\n"
            f"open({str(marker)!r}, 'w').close()\n"
            f"sys.exit({first_exit})\n")
        stream = tmp_path / f"sup{first_exit}.jsonl"
        sup = sup_mod.Supervisor(
            [sys.executable, str(child)], metrics_jsonl=str(stream),
            max_restarts=2, backoff_s=0.01, sleep_fn=lambda s: None,
            log=lambda *a: None)
        assert sup.run() == 0
        recs = obs.read_jsonl(str(stream))
        assert obs_schema.validate_stream(recs) == []
        return [r for r in recs if r["record"] == "restart"]

    preempted = run_child(75)
    assert len(preempted) == 1
    assert preempted[0]["classification"] == "preempted"
    assert preempted[0]["reason"] == "preemption"
    crashed = run_child(3)
    assert len(crashed) == 1
    assert crashed[0]["classification"] == "crashed"
    assert sup_mod.SCHEMA == obs_schema.SCHEMA_VERSION >= 10


# ==================================== in-process chaos (shared compile)

@pytest.fixture(scope="module")
def model_and_params():
    model = gpt_tiny()
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _thread_fleet(model, params, n, faults=None):
    """n ThreadReplicas over the session's SLOTS=4/MAX_LEN=32 decode
    geometry — the engines share ONE compiled program (the step cache
    keys on the module-clone config), so these tests add no compiles."""
    def factory():
        return ServeEngine(model, params, num_slots=SLOTS,
                           max_len=MAX_LEN,
                           rng=jax.random.PRNGKey(0))

    def make_request(spec):
        return Request(prompt=spec["prompt"],
                       max_new_tokens=int(spec["max_new_tokens"]),
                       temperature=float(spec.get("temperature", 0.0)),
                       top_k=int(spec.get("top_k", 0)),
                       eos_id=spec.get("eos_id"),
                       deadline_s=spec.get("deadline_s"),
                       uid=spec["uid"])

    return [ThreadReplica(f"r{i}", factory, make_request,
                          fault=(faults or {}).get(f"r{i}"))
            for i in range(n)]


def _stop_all(router, replicas):
    # Short join: a replica abandoned mid-hang (the straggler drill)
    # never exits its sleep — its daemon thread is simply left behind.
    for r in replicas:
        if router.replica_state(r.name) != "stalled":
            r.stop(timeout_s=2.0)


def test_fleet_token_identity_across_replicas(model_and_params):
    """Routing must not change WHAT gets served: every greedy request
    completes on some replica with tokens identical to one-shot
    generate() — the serve smoke's contract, now fleet-wide."""
    model, params = model_and_params
    replicas = _thread_fleet(model, params, 2)
    router = FleetRouter(replicas, policy="round_robin", log=None)
    specs = synthetic_specs(10, vocab_size=model.vocab_size, seed=3,
                            prompt_len=(3, 8), max_new=(3, 10))
    summary = run_scenario("none", router, replicas, specs,
                           timeout_s=90)
    _stop_all(router, replicas)
    assert summary["verdict"] == "pass"
    assert summary["completed"] == 10 and summary["lost"] == 0
    # both replicas actually served (the routing-balance stats agree)
    assert all(v > 0 for v in
               summary["routing"]["dispatches"].values())
    for spec in specs:
        ev = router.results[spec["uid"]]
        assert ev["status"] == "ok"
        P = len(spec["prompt"])
        n = len(ev["tokens"])
        assert n == min(spec["max_new_tokens"], MAX_LEN - P)
        ref = generate(model, params,
                       jnp.asarray([spec["prompt"]], jnp.int32),
                       max_len=MAX_LEN)
        np.testing.assert_array_equal(
            np.asarray(ref)[0, P:P + n],
            np.asarray(ev["tokens"], np.int32), err_msg=spec["uid"])


def _storm_once(model, params, specs):
    # tick 3: early enough that r0 still holds live slots when it dies
    # (a crash after the last harvest loses nothing and proves nothing)
    faults = {"r0": FaultPlan("crash", 3, kinds=SERVE_KINDS)}
    replicas = _thread_fleet(model, params, 3, faults)
    router = FleetRouter(replicas, breaker_backoff_s=0.1, log=None)
    summary = run_scenario("crash_storm", router, replicas, specs,
                           crashed_names=["r0"], timeout_s=90)
    _stop_all(router, replicas)
    score = {k: summary[k] for k in
             ("completed", "failed", "timed_out", "retries", "lost",
              "availability", "verdict")}
    score["r0_lost"] = summary["per_replica"]["r0"].get("lost", 0)
    return score


def test_crash_storm_inprocess_deterministic_score(model_and_params):
    """crash@tick on pre-submitted queues: which requests the crash
    takes down is a pure function of the workload (ThreadReplica ticks
    only when work exists), so the scenario SCORE is bit-reproducible
    — run it twice and compare."""
    model, params = model_and_params
    specs = synthetic_specs(12, vocab_size=model.vocab_size, seed=4,
                            prompt_len=(3, 6), max_new=(3, 8))
    first = _storm_once(model, params, specs)
    assert first["verdict"] == "pass"
    assert first["completed"] == 12 and first["lost"] == 0
    assert first["retries"] >= 1        # the crash actually cost work
    assert first["r0_lost"] >= 1
    second = _storm_once(model, params, specs)
    assert second == first              # deterministic chaos score


def test_crash_storm_fails_when_the_crash_never_fires(model_and_params):
    """Review regression (ISSUE 12): a drill armed past the workload's
    last tick never fires — the scenario must FAIL its
    every_crash_observed check rather than score a storm that never
    happened."""
    model, params = model_and_params
    faults = {"r0": FaultPlan("crash", 10_000, kinds=SERVE_KINDS)}
    replicas = _thread_fleet(model, params, 2, faults)
    router = FleetRouter(replicas, log=None)
    specs = synthetic_specs(6, vocab_size=model.vocab_size, seed=7,
                            prompt_len=(3, 5), max_new=(3, 5))
    summary = run_scenario("crash_storm", router, replicas, specs,
                           crashed_names=["r0"], timeout_s=60)
    _stop_all(router, replicas)
    assert summary["completed"] == 6 and summary["lost"] == 0
    assert summary["verdict"] == "fail"     # the chaos never happened


def test_straggler_inprocess_stall_rescue(model_and_params):
    """A hung replica (hang drill: the silent-wedge shape) never
    crashes; the router's stall detector must open its breaker and
    rescue its requests onto siblings — availability stays 1.0."""
    model, params = model_and_params
    # Warm the shared decode-step program BEFORE arming the stall
    # clock: a cold jit compile (seconds on this rig) freezes the
    # healthy siblings' first tick past any sane stall_after_s, so a
    # fresh-process run (`pytest -k straggler`) would false-trip them
    # and charge rescues before the hang drill even fires.
    warm = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                       rng=jax.random.PRNGKey(0))
    warm.queue.submit_all([Request(prompt=[1, 2, 3],
                                   max_new_tokens=2, uid="warm")])
    warm.queue.close()
    warm.run(max_steps=50)
    faults = {"r0": FaultPlan("hang", 3, kinds=SERVE_KINDS)}
    replicas = _thread_fleet(model, params, 3, faults)
    sink = ListSink()
    # Regression guard (PR-16 acceptance flake).  Two margins at once:
    # (a) stall_after_s must stay well above the worst-case tick gap
    # of a HEALTHY loaded sibling — at 0.4s a single-core rig under
    # full-suite contention can stretch a healthy replica's jitted
    # tick past the threshold, falsely breakering it and charging a
    # retry to every uid it holds.  The genuinely hung replica is
    # detected at ANY threshold (its progress age grows without
    # bound), so widening only removes false positives.  (b) The wide
    # threshold also keeps the half-open PROBE path hot: r0 never
    # crashes, so after each rescue its breaker half-opens and a live
    # uid probes the wedge, parking there for a full stall_after_s per
    # cycle.  Probe losses must not charge the probed uid's retry
    # budget (router probe_loss rule) or this scenario dies "failed"
    # nondeterministically — exactly the flake this pins.  Keep 2.0s.
    router = FleetRouter(replicas, stall_after_s=2.0,
                         breaker_backoff_s=0.1, sink=sink, log=None)
    specs = synthetic_specs(12, vocab_size=model.vocab_size, seed=5,
                            prompt_len=(3, 6), max_new=(3, 8))
    summary = run_scenario("straggler", router, replicas, specs,
                           straggler_name="r0", timeout_s=90)
    assert summary["verdict"] == "pass"     # incl. the stall_detected check
    assert summary["completed"] == 12 and summary["lost"] == 0
    assert summary["retries"] >= 1      # rescued off the straggler
    # the transition was recorded (the state legitimately reverts once
    # the rescue empties the straggler's inflight set — an idle replica
    # that is not progressing is not stalled)
    assert any(r["record"] == "replica_state" and r["replica"] == "r0"
               and r["state"] == "stalled" for r in sink.records)
    # the rescue is the deadline-aware retry path, not a drain
    assert summary["drained_requeued"] == 0
    for r in replicas[1:]:
        r.stop(timeout_s=2.0)           # r0's thread is hung: abandoned


def test_rolling_restart_inprocess(model_and_params):
    """Thread-transport rolling restart: interrupt() drains the engine
    (queued requests requeue to the sibling) and rebuilds it — zero
    lost, availability 1.0, both replicas restarted."""
    model, params = model_and_params
    replicas = _thread_fleet(model, params, 2)
    router = FleetRouter(replicas, log=None)
    specs = synthetic_specs(16, vocab_size=model.vocab_size, seed=6,
                            prompt_len=(3, 6), max_new=(4, 8))
    summary = run_scenario("rolling_restart", router, replicas, specs,
                           timeout_s=90, settle_timeout_s=30)
    _stop_all(router, replicas)
    assert summary["verdict"] == "pass"
    assert summary["completed"] == 16 and summary["lost"] == 0
    assert summary["availability"] == 1.0
    assert all(r.restarts == 1 for r in replicas)


# ===================================== disagg fleet chaos (ISSUE 15)


def test_router_spool_stale_sweep_reroutes_through_prefill():
    """The one crash window the lease cannot redeliver: a decode
    worker acked its claim (spool file gone) then died before any
    terminal reached its outbox — nothing will ever report the uid.
    With spool_timeout_s armed the router presumes it lost and
    re-routes it through a prefill replica from scratch."""
    pre = FakeReplica("p0")
    pre.role = "prefill"
    dec = FakeReplica("d0")
    dec.role = "decode"
    router = FleetRouter([pre, dec], spool_timeout_s=0.05, log=None)
    router.submit(_spec("u1"))
    assert [s["uid"] for s in pre.specs] == ["u1"]   # never to decode
    pre.report("u1", "handoff")
    router.poll()
    assert not router.done()                # parked on the spool
    time.sleep(0.08)
    router.poll()                           # stale sweep fires
    assert [s["uid"] for s in pre.specs] == ["u1", "u1"]  # re-prefilled
    pre.report("u1", "ok", tokens=[1])
    router.poll()
    assert router.done()
    summary = router.summary_record()
    assert summary["lost"] == 0 and summary["retries"] == 1
    assert summary["handoffs"] == 1 and summary["in_spool"] == 0


@pytest.mark.parametrize("order", ["handoff_first", "terminal_first"])
def test_router_counts_a_handoff_its_terminal_overtook(order):
    """Two outboxes, polled in turn: a decode worker that finishes a short
    request within the poll interval reports ``ok`` before the prefill
    replica's ``handoff`` for the same uid has been read.  The handoff
    happened either way: the count is 1 in both orders, the prefill
    replica's booking is released once, and only a REPLAYED handoff line
    is a duplicate."""
    pre = FakeReplica("p0")
    pre.role = "prefill"
    dec = FakeReplica("d0")
    dec.role = "decode"
    router = FleetRouter([pre, dec], log=None)
    router.submit(_spec("u1"))
    if order == "handoff_first":
        pre.report("u1", "handoff")
        router.poll()
        dec.report("u1", "ok", tokens=[1])
        router.poll()
    else:
        dec.report("u1", "ok", tokens=[1])
        router.poll()
        assert router.done()
        pre.report("u1", "handoff")
        router.poll()
    summary = router.summary_record()
    assert summary["completed"] == 1 and summary["lost"] == 0
    assert summary["handoffs"] == 1 and summary["in_spool"] == 0
    assert summary["duplicates"] == 0
    assert summary["per_replica"]["p0"].get("handoff") == 1
    assert router._replicas["p0"].inflight == 0
    pre.report("u1", "handoff")             # a replayed outbox line
    router.poll()
    summary = router.summary_record()
    assert summary["handoffs"] == 1 and summary["duplicates"] == 1
    assert router._replicas["p0"].inflight == 0


def test_thread_replica_rejects_inert_handoff_drills(model_and_params):
    """A drill the transport/drive loop can never express must be a
    construction error, not a silently-clean chaos run."""
    model, params = model_and_params

    def factory():
        return ServeEngine(model, params, num_slots=SLOTS,
                           max_len=MAX_LEN, role="decode")

    for kind in ("handoff_dup", "handoff_torn", "sentinel_lost"):
        with pytest.raises(ValueError, match="cannot express"):
            ThreadReplica("d0", factory, role="decode",
                          transport_factory=lambda: None,
                          fault=FaultPlan(kind, 1, kinds=SERVE_KINDS))
    with pytest.raises(ValueError, match="cannot express"):
        ThreadReplica("p0", factory, lambda s: s, role="prefill",
                      fault=FaultPlan("handoff_crash_preack", 1,
                                      kinds=SERVE_KINDS))


def _disagg_thread_fleet(model, params, spool, lease_s=0.3,
                         crash_decode=None, crash_prefill_tick=None):
    """1 prefill + 2 decode ThreadReplicas over one leased FileTransport
    spool — every engine rides the session's compiled programs (the
    [4, 8] prefill step shared with test_serve, the [4, 1] decode step
    shared with test_disagg): zero new compiles."""
    from apex_example_tpu.serve import FileTransport

    def make_request(spec):
        return Request(prompt=spec["prompt"],
                       max_new_tokens=int(spec["max_new_tokens"]),
                       temperature=float(spec.get("temperature", 0.0)),
                       top_k=int(spec.get("top_k", 0)),
                       eos_id=spec.get("eos_id"),
                       deadline_s=spec.get("deadline_s"),
                       uid=spec["uid"])

    def prefill_factory():
        tx = FileTransport(spool, worker="p0.tx")
        return ServeEngine(model, params, num_slots=SLOTS,
                           max_len=MAX_LEN, rng=jax.random.PRNGKey(0),
                           role="prefill", handoff_sink=tx.send)

    def decode_factory():
        return ServeEngine(model, params, num_slots=SLOTS,
                           max_len=MAX_LEN, rng=jax.random.PRNGKey(0),
                           role="decode")

    pre_fault = FaultPlan("crash", crash_prefill_tick,
                          kinds=SERVE_KINDS) if crash_prefill_tick \
        else None
    replicas = [ThreadReplica("p0", prefill_factory, make_request,
                              fault=pre_fault, role="prefill")]
    for name in ("d0", "d1"):
        fault = FaultPlan("handoff_crash_preack", 1,
                          kinds=SERVE_KINDS) \
            if name == crash_decode else None

        def tx_factory(worker=name):
            return FileTransport(spool, worker=worker, lease_s=lease_s)

        replicas.append(ThreadReplica(name, decode_factory, fault=fault,
                                      role="decode",
                                      transport_factory=tx_factory))
    return replicas


def _midspool_once(model, params, specs, spool):
    replicas = _disagg_thread_fleet(model, params, spool,
                                    crash_decode="d0")
    router = FleetRouter(replicas, log=None)
    summary = run_scenario("decode_crash_midspool", router, replicas,
                           specs, crashed_name="d0", timeout_s=90)
    results = dict(router.results)
    for r in replicas:
        r.stop(timeout_s=5.0)
    # The INVARIANT score: everything here is a pure function of the
    # workload (which uids exist, that they all complete, that nothing
    # leaks) — handoff_redelivered is deliberately excluded: HOW MANY
    # claims the dead worker held when it died depends on claim-race
    # timing, only that the peer finished them does not.
    score = {k: summary[k] for k in
             ("completed", "failed", "timed_out", "lost",
              "availability", "verdict", "requests", "handoffs",
              "in_spool", "prefill_replicas", "decode_replicas")}
    return score, summary, results


def test_disagg_fleet_decode_crash_midspool_deterministic(
        model_and_params, tmp_path):
    """THE ISSUE 15 chaos acceptance: a 1-prefill + 2-decode fleet;
    decode worker d0 dies in the ack-crash window holding claimed-but-
    unacked handoffs; nobody restarts it — the PEER reclaims the
    expired leases and finishes the redelivered handoffs.  Zero lost,
    exactly-once per uid, redelivery really happened, survivors'
    outputs token-identical to generate(), and the invariant score is
    bit-identical across two runs."""
    model, params = model_and_params
    specs = synthetic_specs(10, vocab_size=model.vocab_size, seed=8,
                            prompt_len=(3, 8), max_new=(3, 8))
    first, summary, results = _midspool_once(
        model, params, specs, str(tmp_path / "spool_a"))
    assert first["verdict"] == "pass"
    assert first["completed"] == 10 and first["lost"] == 0
    assert first["availability"] == 1.0
    assert first["handoffs"] == 10 and first["in_spool"] == 0
    assert first["prefill_replicas"] == 1
    assert first["decode_replicas"] == 2
    assert summary["handoff_redelivered"] >= 1   # the peer did work
    # every uid exactly once, token-identical to one-shot generate()
    assert len(results) == 10
    for spec in specs:
        ev = results[spec["uid"]]
        assert ev["status"] == "ok", (spec["uid"], ev)
        P = len(spec["prompt"])
        n = len(ev["tokens"])
        ref = generate(model, params,
                       jnp.asarray([spec["prompt"]], jnp.int32),
                       max_len=MAX_LEN)
        np.testing.assert_array_equal(
            np.asarray(ref)[0, P:P + n],
            np.asarray(ev["tokens"], np.int32), err_msg=spec["uid"])
    second, _, _ = _midspool_once(model, params, specs,
                                  str(tmp_path / "spool_b"))
    assert second == first              # deterministic chaos score


def test_disagg_fleet_prefill_crash(model_and_params, tmp_path):
    """The prefill role dies mid-serve: requests it held come back
    lost and re-route once the scenario restarts it; requests already
    on the spool keep decoding untouched — zero lost, spool drained."""
    model, params = model_and_params
    spool = str(tmp_path / "spool")
    # tick 1: the first admitted wave hands off within its first tick
    # (one-chunk prompts sample their first token in the same tick),
    # so a later crash would find an empty queue and prove nothing —
    # crash while 6 of 10 requests are still queued behind the slots.
    replicas = _disagg_thread_fleet(model, params, spool,
                                    crash_prefill_tick=1)
    router = FleetRouter(replicas, breaker_backoff_s=0.1, log=None)
    specs = synthetic_specs(10, vocab_size=model.vocab_size, seed=9,
                            prompt_len=(3, 8), max_new=(3, 8))
    summary = run_scenario("prefill_crash", router, replicas, specs,
                           crashed_name="p0", timeout_s=90)
    for r in replicas:
        r.stop(timeout_s=5.0)
    assert summary["verdict"] == "pass"
    assert summary["completed"] == 10 and summary["lost"] == 0
    assert summary["availability"] == 1.0
    assert summary["retries"] >= 1          # the crash really cost work
    assert summary["handoffs"] >= 10        # every uid crossed the spool
    assert summary["in_spool"] == 0
    assert replicas[0].restarts == 1


def test_proc_replica_disagg_argv(tmp_path):
    """Role plumbing for supervised children: a decode ProcReplica
    spawns serve.py with NO --inbox (the spool is its intake), the
    role/spool flags, and the drill-stripping drop flag; submit()
    always refuses on it."""
    from apex_example_tpu.fleet.replica import ProcReplica
    spool = str(tmp_path / "spool")
    dec = ProcReplica("d0", str(tmp_path), REPO, role="decode",
                      spool_dir=spool)
    argv = dec.argv()
    sup_side = argv[:argv.index("--")]
    child = argv[argv.index("--") + 1:]
    assert "--inbox" not in child
    assert child[child.index("--role") + 1] == "decode"
    assert child[child.index("--handoff-dir") + 1] == spool
    assert "--outbox" in child
    assert "--drop-flag-on-restart=--inject-fault" in sup_side
    assert dec.submit({"uid": "x"}) is False
    assert dec.role == "decode"
    pre = ProcReplica("p0", str(tmp_path), REPO, role="prefill",
                      spool_dir=spool)
    child = pre.argv()[pre.argv().index("--") + 1:]
    assert "--inbox" in child
    assert child[child.index("--role") + 1] == "prefill"
    with pytest.raises(ValueError, match="spool_dir"):
        ProcReplica("x0", str(tmp_path), REPO, role="decode")


# ================================= tools over the checked-in scenario

def test_ci_gate_fleet_stream_over_checked_in_scenario(tmp_path,
                                                       capsys):
    ci_gate = _load_tool("ci_gate")
    # ONE full-command run (this is the CI surface: graftlint + fleet
    # gate); the failure variants exercise the gate function directly —
    # re-linting the whole tree per variant would buy nothing.
    assert ci_gate.main(["--fleet-stream", FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "fleet gate" in out and "PASS" in out
    assert ci_gate.main(["--fleet-stream",
                         str(tmp_path / "missing.jsonl")]) == 2

    # doctored streams fail loudly: lost requests / low availability /
    # failed verdict / no summary
    records = obs.read_jsonl(FIXTURE)
    summ = next(r for r in records if r["record"] == "fleet_summary")

    def doctored(**kw):
        path = str(tmp_path / f"bad{len(kw)}{list(kw)[0]}.jsonl")
        with open(path, "w") as fh:
            for r in records:
                r2 = dict(r, **kw) if r["record"] == "fleet_summary" \
                    else r
                fh.write(json.dumps(r2) + "\n")
        return path

    assert ci_gate._fleet_gate(FIXTURE, 1.0) == 0
    assert ci_gate._fleet_gate(doctored(lost=2), 1.0) == 1
    assert ci_gate._fleet_gate(doctored(availability=0.5), 1.0) == 1
    assert ci_gate._fleet_gate(doctored(verdict="fail"), 1.0) == 1
    assert ci_gate._fleet_gate(FIXTURE, summ["availability"]) == 0
    no_summary = str(tmp_path / "nosummary.jsonl")
    with open(no_summary, "w") as fh:
        for r in records:
            if r["record"] != "fleet_summary":
                fh.write(json.dumps(r) + "\n")
    assert ci_gate._fleet_gate(no_summary, 1.0) == 1


def test_fleet_report_serve_fleet_mode(tmp_path, capsys):
    """The fleet_report satellite: per-replica availability table,
    routing-balance skew, scenario verdict line — auto-detected from
    the v10 records, still jax-free (the graftlint contract covers
    it)."""
    report = _load_tool("fleet_report")
    assert report.main([FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "serve fleet:" in out
    assert "scenario rolling_restart" in out
    assert "replica" in out and "avail" in out
    assert "r0" in out and "r1" in out
    assert "routing balance" in out
    assert "scenario verdict: PASS" in out

    # lost requests flip the exit code
    records = obs.read_jsonl(FIXTURE)
    bad = str(tmp_path / "lost.jsonl")
    with open(bad, "w") as fh:
        for r in records:
            r2 = dict(r, lost=3, availability=0.8, verdict="fail") \
                if r["record"] == "fleet_summary" else r
            fh.write(json.dumps(r2) + "\n")
    assert report.main([bad]) == 1
    out = capsys.readouterr().out
    assert "LOST REQUESTS" in out

    # the checked-in stream also validates and the TRAIN mode is
    # untouched (a rank stream without fleet records takes the old path)
    assert obs_schema.validate_stream(records) == []
    lint = _load_tool("metrics_lint")
    assert lint.lint(FIXTURE)[0] == 0


def test_telemetry_report_fleet_line(capsys):
    report = _load_tool("telemetry_report")
    assert report.main([FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "FLEET:" in out and "availability" in out


def test_replica_mode_steps_cap_reports_stranded(tmp_path, capsys):
    """Review regression (ISSUE 12): a --steps-capped replica that runs
    out of ticks with inbox requests still queued/mid-decode must exit
    nonzero with the stranded warning — not 0 with silent loss (the
    router would wait out its timeout on those uids)."""
    import serve as serve_mod

    inbox = str(tmp_path / "inbox.jsonl")
    with open(inbox, "w") as fh:
        for i in range(6):
            fh.write(json.dumps({"uid": f"s{i}", "prompt": [1 + i, 2, 3],
                                 "max_new_tokens": 8}) + "\n")
        # no close sentinel: the queue stays open at the cap
    rc = serve_mod.main(["--inbox", inbox,
                         "--outbox", str(tmp_path / "outbox.jsonl"),
                         "--replica-id", "rX", "--slots", str(SLOTS),
                         "--max-len", str(MAX_LEN), "--steps", "3"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "unfinished at the --steps cap" in err


def test_fleet_cli_thread_smoke(tmp_path, capsys):
    """fleet.py --transport thread end to end: the CLI builds N
    in-process replicas over ONE shared compiled program (the session's
    SLOTS=4/MAX_LEN=32 geometry), routes, scores, exits 0 on a passing
    verdict, and leaves a lintable v10 stream."""
    import fleet as fleet_cli

    path = str(tmp_path / "fleet.jsonl")
    rc = fleet_cli.main(["--transport", "thread", "--replicas", "2",
                         "--requests", "6", "--slots", str(SLOTS),
                         "--max-len", str(MAX_LEN),
                         "--metrics-jsonl", path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "verdict=pass" in out
    records = obs.read_jsonl(path)
    assert obs_schema.validate_stream(records) == []
    summary = records[-1]
    assert summary["record"] == "fleet_summary"
    assert summary["completed"] == 6 and summary["lost"] == 0
    lint = _load_tool("metrics_lint")
    assert lint.lint(path)[0] == 0


# ============================================ THE subprocess scenario

def test_rolling_restart_supervised_e2e(tmp_path):
    """The ISSUE 12 acceptance bar: 2 supervised serve.py subprocess
    replicas under burst load, SIGTERM'd in turn by the scenario —
    every submitted uid reaches exactly one non-drained terminal
    status (zero lost), fleet availability 1.0, ONE trace_id across
    router + children + supervisors, and the merged 7-stream export is
    trace_export --check clean.  The suite's one new subprocess e2e."""
    import fleet as fleet_cli

    fleet_jsonl = str(tmp_path / "fleet.jsonl")
    workdir = str(tmp_path / "work")
    argv = ["--replicas", "2", "--transport", "proc",
            "--scenario", "rolling_restart", "--requests", "16",
            "--slots", "2", "--max-len", "16",
            "--metrics-jsonl", fleet_jsonl, "--workdir", workdir,
            "--trace", "--timeout", "150"]
    try:
        rc = fleet_cli.main(argv)
    finally:
        os.environ.pop("APEX_TRACE_ID", None)   # the router exports it
    assert rc == 0

    records = obs.read_jsonl(fleet_jsonl)
    assert obs_schema.validate_stream(records) == []
    summary = records[-1]
    assert summary["record"] == "fleet_summary"
    assert summary["scenario"] == "rolling_restart"
    assert summary["verdict"] == "pass"
    assert summary["availability"] == 1.0
    assert summary["lost"] == 0
    assert summary["requests"] == 16

    # zero lost at the uid level: every uid exactly ONE non-drained
    # terminal across the whole fleet (outboxes are append-only and
    # survive the restarts, so this audits all attempts at once)
    terminal = {}
    for name in ("r0", "r1"):
        with open(os.path.join(workdir, name, "outbox.jsonl")) as fh:
            for line in fh:
                ev = json.loads(line)
                if ev.get("status") != "drained":
                    terminal[ev["uid"]] = terminal.get(ev["uid"], 0) + 1
    assert len(terminal) == 16
    assert set(terminal.values()) == {1}

    # both replicas were actually restarted (supervisor streams carry
    # the v10 classification: a drain is a preemption, not a crash)
    for name in ("r0", "r1"):
        sup = obs.read_jsonl(os.path.join(workdir, name, "sup.jsonl"))
        restarts = [r for r in sup if r["record"] == "restart"]
        assert len(restarts) == 1
        assert restarts[0]["exit_code"] == 75
        assert restarts[0]["classification"] == "preempted"
        att0 = obs.read_jsonl(
            os.path.join(workdir, name, "serve.jsonl"))
        assert any(r["record"] == "serve_drain" for r in att0)
        beats = [r for r in att0 if r["record"] == "replica_state"]
        assert beats and all(r["replica"] == name for r in beats)

    # ONE trace across router + 2 children x 2 attempts + 2 supervisors,
    # and the merged export passes the structural lint
    streams = [fleet_jsonl]
    for name in ("r0", "r1"):
        streams += [os.path.join(workdir, name, "serve.jsonl"),
                    os.path.join(workdir, name, "serve.jsonl.attempt1"),
                    os.path.join(workdir, name, "sup.jsonl")]
    assert all(os.path.exists(s) for s in streams)
    ids = set()
    for s in streams:
        for r in obs.read_jsonl(s):
            if r["record"] in ("trace_event", "clock_sync") \
                    and "trace_id" in r:
                ids.add(r["trace_id"])
    assert len(ids) == 1, ids
    export = _load_tool("trace_export")
    assert export.main(["--check"] + streams) == 0
    merged = str(tmp_path / "merged.json")
    assert export.main(streams + ["-o", merged]) == 0
    names = {e["name"] for e in
             json.load(open(merged))["traceEvents"]}
    assert {"route", "interrupt", "drain", "attempt",
            "scenario:rolling_restart"} <= names

    # and the recorded stream passes the CI fleet gate + fleet_report
    ci_gate = _load_tool("ci_gate")
    assert ci_gate.main(["--fleet-stream", fleet_jsonl]) == 0
    report = _load_tool("fleet_report")
    assert report.main([fleet_jsonl]) == 0


def test_disagg_proc_decode_crash_e2e(tmp_path, capsys):
    """THE ISSUE 15 subprocess chaos e2e: a 1-prefill + 2-decode
    supervised serve.py fleet over one leased spool; decode child r1
    crashes in the ack-crash window at its first admit
    (handoff_crash_preack@1), its supervisor restarts it with the
    drill STRIPPED (the drop-flag satellite, live), its adopted claims
    redeliver, and the scenario scores verdict pass — zero lost,
    exactly one non-drained terminal per uid across the decode
    outboxes, fleet gate + report green with the DISAGG line."""
    import fleet as fleet_cli

    fleet_jsonl = str(tmp_path / "fleet.jsonl")
    workdir = str(tmp_path / "work")
    # Regression guard (the PR-16 acceptance flake): this e2e proves
    # LEASE redelivery, not the stale sweep — at the derived
    # spool_timeout (max(4*lease, 5) = 5s here) a loaded single-core
    # rig can park honest spool dwell past the threshold (the
    # restarted decode child pays python+jax startup plus a recompile
    # before its first claim), the sweep re-routes the uids through
    # prefill a second time, and handoffs lands at 20 != 10.  The
    # sweep path has its own dedicated unit test
    # (test_router_spool_stale_sweep_reroutes_through_prefill); here
    # it is pushed far out of the hot path.
    argv = ["--replicas", "3", "--decode-replicas", "2",
            "--transport", "proc",
            "--scenario", "decode_crash_midspool",
            "--requests", "10", "--slots", "2", "--max-len", "16",
            "--handoff-lease", "1.0", "--spool-timeout", "120",
            "--metrics-jsonl", fleet_jsonl, "--workdir", workdir,
            "--timeout", "150"]
    rc = fleet_cli.main(argv)
    assert rc == 0

    records = obs.read_jsonl(fleet_jsonl)
    assert obs_schema.validate_stream(records) == []
    summary = records[-1]
    assert summary["record"] == "fleet_summary"
    assert summary["scenario"] == "decode_crash_midspool"
    assert summary["verdict"] == "pass"
    assert summary["lost"] == 0 and summary["availability"] == 1.0
    assert summary["prefill_replicas"] == 1
    assert summary["decode_replicas"] == 2
    assert summary["handoffs"] == 10 and summary["in_spool"] == 0
    assert summary["handoff_redelivered"] >= 1

    # the crashed decode child was classified + restarted, and the
    # restart attempt's argv lost the drill (otherwise it would
    # re-fire on the replayed claim set and flap until the budget ran
    # out)
    sup = obs.read_jsonl(os.path.join(workdir, "r1", "sup.jsonl"))
    restarts = [r for r in sup if r["record"] == "restart"]
    assert restarts and restarts[0]["classification"] == "crashed"
    assert len(restarts) == 1               # the stripped drill stayed dead

    # exactly-once at the uid level across the decode outboxes
    terminal = {}
    for name in ("r1", "r2"):
        path = os.path.join(workdir, name, "outbox.jsonl")
        if os.path.exists(path):
            with open(path) as fh:
                for line in fh:
                    ev = json.loads(line)
                    if ev.get("status") != "drained":
                        terminal[ev["uid"]] = \
                            terminal.get(ev["uid"], 0) + 1
    assert len(terminal) == 10
    assert set(terminal.values()) == {1}

    ci_gate = _load_tool("ci_gate")
    assert ci_gate.main(["--fleet-stream", fleet_jsonl]) == 0
    report = _load_tool("fleet_report")
    capsys.readouterr()
    assert report.main([fleet_jsonl]) == 0
    out = capsys.readouterr().out
    assert "DISAGG: 1 prefill + 2 decode" in out
