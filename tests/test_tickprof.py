"""Hot-path overhead attribution (obs/tickprof.py, schema v15;
ISSUE 17):

- TickProfiler fold semantics: phase sketches, sampling cadence,
  host-gap arithmetic, schema-valid tick_profile / overhead_summary
  emission, unknown-phase and bad-kind rejection,
- the jax-free contract: tickprof.py loads BY FILE PATH on a bare
  host (no package __init__, no jax in sys.modules) — the loader
  perf_ledger itself uses,
- the armed serve smoke on the session's SLOTS=4/MAX_LEN=32 compiled
  geometry: greedy outputs token-identical to one-shot generate(),
  phase components sum to tick wall within 1%, ONE compile_event with
  the profiler + tracer + cost model all armed (zero new compiled
  programs), trace_export --check clean with the host_gap_ms counter
  track present in the export, serve_summary carrying the v15 idle +
  host_overhead_frac fields, serve_report's OVERHEAD lines rendered,
- idle-spin accounting: a staggered workload accrues idle_ticks and
  (with idle_wait_s) idle_wait_ms in the summary,
- the perf-regression ledger over the checked-in recorded fixtures
  (tests/fixtures/perf/): schema-valid, ci_gate --perf-stream PASS,
  a tampered host fraction FAILS, missing stream exits 2,
  PERF_BASELINE.json round-trips and compares clean at HEAD while a
  shifted baseline value is flagged as a regression,
- report degradation: pre-v15 streams render no OVERHEAD line; the
  train fixture renders one via telemetry_report,
- v15 back-compat: every older checked-in fixture stream (v10-v14)
  still validates, and the two hard-coded jax-free SCHEMA constants
  (resilience/supervisor.py, fleet/router.py) moved in lockstep,
- graftlint's schema-emission rule covers the two new record types
  (an undeclared field on either fires statically).
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu import obs
from apex_example_tpu.models.gpt import generate, gpt_tiny
from apex_example_tpu.obs import schema as obs_schema
from apex_example_tpu.obs.tickprof import (DEVICE_PHASE, SERVE_PHASES,
                                           TRAIN_PHASES, TickProfiler)
from apex_example_tpu.serve import ServeEngine, synthetic_requests

pytestmark = pytest.mark.tickprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE_FIXTURE = os.path.join(REPO, "tests", "fixtures", "perf",
                             "serve_perf.jsonl")
TRAIN_FIXTURE = os.path.join(REPO, "tests", "fixtures", "perf",
                             "train_perf.jsonl")
BASELINE = os.path.join(REPO, "PERF_BASELINE.json")
SLOTS, MAX_LEN = 4, 32          # the session-shared decode geometry


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fixture_records(path):
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


# ====================================== profiler fold semantics (unit)

def test_profiler_folds_phases_and_samples_every_nth():
    sink = ListSink()
    prof = TickProfiler(kind="serve", sample_every=3,
                        emit=sink.write, run_id="r0")
    for i in range(7):
        rec = prof.observe_tick(i * 0.01, 10.0, admit=1.0,
                                dispatch_enqueue=0.5, device_wait=7.0,
                                harvest=1.0, spool_io=0.2,
                                telemetry=0.3)
        # sampled on ticks 0, 3, 6; None in between
        assert (rec is not None) == (i % 3 == 0)
    assert prof.ticks == 7 and prof.sampled == 3
    assert len(sink.records) == 3
    for rec in sink.records:
        assert rec["record"] == "tick_profile"
        assert rec["kind"] == "serve" and rec["run_id"] == "r0"
        assert set(rec["phases"]) == set(SERVE_PHASES)
        assert sum(rec["phases"].values()) == pytest.approx(10.0)
        # host gap = wall - device_wait, per tick
        assert rec["host_gap_ms"] == pytest.approx(3.0)
    # cumulative accessors: every tick folded, not just the sampled ones
    assert prof.wall_ms == pytest.approx(70.0)
    assert prof.device_ms() == pytest.approx(49.0)
    assert prof.host_gap_ms() == pytest.approx(21.0)
    assert prof.host_overhead_frac() == pytest.approx(0.3)

    summ = prof.summary_record()
    sink.write(summ)
    assert summ["record"] == "overhead_summary"
    assert summ["ticks"] == 7 and summ["sampled"] == 3
    assert summ["host_overhead_frac"] == pytest.approx(0.3)
    assert set(summ["phases"]) == set(SERVE_PHASES)
    for name in SERVE_PHASES:
        ph = summ["phases"][name]
        assert ph["count"] == 7
        assert ph["p50"] > 0 or ph["total_ms"] >= 0
    assert summ["phases"]["device_wait"]["total_ms"] == \
        pytest.approx(summ["device_ms"])
    # constant per-tick inputs: the sketch percentiles sit on the value
    assert summ["wall"]["count"] == 7
    assert summ["wall"]["p50"] == pytest.approx(10.0, rel=0.02)
    assert summ["host_gap"]["p50"] == pytest.approx(3.0, rel=0.02)
    # everything emitted is schema-valid v15
    assert obs_schema.validate_stream(sink.records) == []


def test_profiler_rejects_unknown_phase_and_bad_kind():
    with pytest.raises(ValueError):
        TickProfiler(kind="mystery")
    with pytest.raises(ValueError):
        TickProfiler(kind="serve", sample_every=0)
    prof = TickProfiler(kind="train")
    with pytest.raises(ValueError):
        prof.observe_tick(0.0, 1.0, admit=1.0)   # a SERVE phase
    ok = dict.fromkeys(TRAIN_PHASES, 0.2)
    prof.observe_tick(0.0, 1.0, **ok)
    assert prof.device_ms() == pytest.approx(0.2)
    assert DEVICE_PHASE["train"] == "device"
    assert DEVICE_PHASE["serve"] == "device_wait"
    # no emit wired: observe_tick still folds, returns None
    assert prof.observe_tick(0.1, 1.0, **ok) is None
    assert prof.host_overhead_frac() == pytest.approx(0.8)


def test_tickprof_loads_jax_free_by_file_path():
    """The contract perf_ledger depends on: tickprof.py (and its slo.py
    fallback import) must load by file path on a host with no package
    import — and pull in NO jax."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('tp', "
        f"{os.path.join(REPO, 'apex_example_tpu', 'obs', 'tickprof.py')!r})\n"
        "tp = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(tp)\n"
        "prof = tp.TickProfiler(kind='serve')\n"
        "prof.observe_tick(0.0, 2.0, admit=0.5, dispatch_enqueue=0.5,\n"
        "                  device_wait=0.5, harvest=0.25, spool_io=0.0,\n"
        "                  telemetry=0.25)\n"
        "assert prof.summary_record()['record'] == 'overhead_summary'\n"
        "assert 'jax' not in sys.modules, 'tickprof pulled in jax'\n"
        "assert 'apex_example_tpu' not in sys.modules\n"
        "print('JAXFREE-OK')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "JAXFREE-OK" in out.stdout


# =================== armed serve smoke (shared compiled geometry)

@pytest.fixture(scope="module")
def model_and_params():
    model = gpt_tiny()
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def test_armed_serve_smoke_decomposes_without_perturbing(
        model_and_params, tmp_path, compile_events, capsys):
    """The acceptance bar: the profiler armed on the session's
    SLOTS=4/MAX_LEN=32 smoke — greedy outputs stay token-identical to
    one-shot generate(), every tick's phase components sum to its wall
    within 1%, the explicit block-until-ready boundary adds ZERO
    compiled programs (one compile_event, cost_report gate passes),
    trace_export --check stays clean and the export carries the
    host_gap_ms counter track, and the summary/report surface the v15
    fields."""
    from apex_example_tpu.obs import costmodel
    from apex_example_tpu.obs import trace as trace_lib
    model, params = model_and_params
    path = str(tmp_path / "armed.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={"slots": SLOTS, "max_len": MAX_LEN},
                       arch="gpt_tiny")
    prof = TickProfiler(kind="serve", sample_every=1, emit=sink.write,
                        run_id=emitter.run_id)
    costmodel.set_default(obs.CostModel(
        sink=sink, registry=emitter.registry, run_id=emitter.run_id))
    trace_lib.set_default(obs.Tracer(sink, run_id=emitter.run_id))
    try:
        reqs = synthetic_requests(8, vocab_size=model.vocab_size,
                                  seed=3, prompt_len=(3, 8),
                                  max_new=(3, 12), stagger=4)
        eng = ServeEngine(model, params, num_slots=SLOTS,
                          max_len=MAX_LEN, rng=jax.random.PRNGKey(0),
                          sink=sink, run_id=emitter.run_id,
                          registry=emitter.registry,
                          tick_profiler=prof)
        eng.queue.submit_all(reqs)
        eng.queue.close()
        comps = eng.run(max_steps=2000)
    finally:
        costmodel.set_default(None)
        trace_lib.set_default(None)
    summary = eng.summary_record()
    sink.write(summary)
    sink.write(prof.summary_record())
    sink.close()
    assert len(comps) == 8

    # (a) the profiler is a pure observer: token-identical to one-shot
    # generate() on every request's output-budget prefix.
    by_uid = {c.request.uid: c for c in comps}
    for r in reqs:
        c = by_uid[r.uid]
        P, n = len(r.prompt), len(c.tokens)
        assert n == min(r.max_new_tokens, MAX_LEN - P)
        ref = generate(model, params,
                       jnp.asarray([r.prompt], jnp.int32),
                       max_len=MAX_LEN)
        np.testing.assert_array_equal(np.asarray(ref)[0, P:P + n],
                                      np.asarray(c.tokens, np.int32),
                                      err_msg=r.uid)

    records = obs.read_jsonl(path)
    assert obs_schema.validate_stream(records) == []

    # (b) the 1% decomposition invariant, per sampled tick AND on the
    # cumulative summary — enforced by the contiguous-boundary design,
    # asserted here against the recorded stream.
    ticks = [r for r in records if r["record"] == "tick_profile"]
    assert len(ticks) == prof.ticks == prof.sampled > 0
    for t in ticks:
        assert set(t["phases"]) == set(SERVE_PHASES)
        total = sum(t["phases"].values())
        assert abs(total - t["wall_ms"]) <= 0.01 * t["wall_ms"] + 1e-6
        gap = t["wall_ms"] - t["phases"]["device_wait"]
        assert t["host_gap_ms"] == pytest.approx(gap, abs=1e-6)
    ov = next(r for r in records if r["record"] == "overhead_summary")
    assert ov["ticks"] == len(ticks)
    phase_total = sum(p["total_ms"] for p in ov["phases"].values())
    assert abs(phase_total - ov["wall_ms"]) <= 0.01 * ov["wall_ms"]
    assert ov["host_gap_ms"] == \
        pytest.approx(ov["wall_ms"] - ov["device_ms"], abs=1e-6)
    assert ov["host_overhead_frac"] == \
        pytest.approx(ov["host_gap_ms"] / ov["wall_ms"], abs=1e-9)
    # ... which is exactly what perf_ledger's always-on gate recomputes
    perf_ledger = _load_tool("perf_ledger")
    assert perf_ledger.consistency_errors(records) == []

    # (c) compile-once with the profiler armed: the block-until-ready
    # boundary syncs values the tick was about to sync anyway — ONE
    # compile_event, and the actual CI gate command agrees.
    assert compile_events(records) == {"serve_decode_step": 1}
    assert compile_events.gate(path) == 0
    capsys.readouterr()

    # (d) the trace stratum: --check clean, and the export carries the
    # host-gap counter track (Perfetto ph "C") from the tick_profile
    # samples.
    trace_export = _load_tool("trace_export")
    assert trace_export.main(["--check", path]) == 0
    trace_out = str(tmp_path / "trace.json")
    assert trace_export.main([path, "-o", trace_out]) == 0
    doc = json.load(open(trace_out))
    counters = [e for e in doc["traceEvents"] if e.get("ph") == "C"]
    assert len(counters) == len(ticks)
    assert {e["name"] for e in counters} == {"host_gap_ms"}
    assert all("host_gap_ms" in e["args"] for e in counters)
    capsys.readouterr()

    # (e) the v15 summary fields + serve_report's OVERHEAD rendering.
    assert summary["idle_ticks"] >= 0
    assert summary["host_overhead_frac"] == \
        pytest.approx(ov["host_overhead_frac"], abs=1e-5)
    serve_report = _load_tool("serve_report")
    assert serve_report.report(path) == 0
    out = capsys.readouterr().out
    assert "OVERHEAD: host_overhead_frac" in out
    assert "phases (p50/p99 ms):" in out
    for name in SERVE_PHASES:
        assert name in out
    assert "idle:" in out


def test_idle_spin_accounting_lands_in_summary(model_and_params):
    """Satellite 1: a staggered workload (second arrival 40 virtual
    ticks after the first wave finishes) accrues idle_ticks, and
    idle_wait_s-throttled spins accrue idle_wait_ms — both in the
    serve_summary, profiler armed or not."""
    model, params = model_and_params
    reqs = synthetic_requests(2, vocab_size=model.vocab_size, seed=7,
                              prompt_len=(3, 4), max_new=(3, 4),
                              stagger=40)
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0))
    eng.queue.submit_all(reqs)
    eng.queue.close()
    comps = eng.run(max_steps=2000, idle_wait_s=0.0005)
    assert len(comps) == 2
    summary = eng.summary_record()
    assert summary["idle_ticks"] > 0
    assert summary["idle_wait_ms"] > 0.0
    assert eng.idle_ticks + eng.compute_steps == eng.step_count
    # no profiler on this engine: the fraction accessor stays None and
    # the summary omits the field rather than claiming 0.0
    assert eng.host_overhead_frac() is None
    assert "host_overhead_frac" not in summary


# ============== ledger + gates over the recorded perf fixtures

def test_perf_fixtures_validate_and_carry_the_decomposition():
    for path, kind, phases in ((SERVE_FIXTURE, "serve", SERVE_PHASES),
                               (TRAIN_FIXTURE, "train", TRAIN_PHASES)):
        records = _fixture_records(path)
        assert obs_schema.validate_stream(records) == [], path
        ticks = [r for r in records if r["record"] == "tick_profile"]
        assert ticks, path
        ov = next(r for r in records
                  if r["record"] == "overhead_summary")
        assert ov["kind"] == kind
        assert set(ov["phases"]) == set(phases), path
        assert 0.0 <= ov["host_overhead_frac"] <= 1.0


def test_ci_gate_perf_stream_passes_on_fixtures(capsys):
    ci_gate = _load_tool("ci_gate")
    assert ci_gate.main(["--perf-stream", SERVE_FIXTURE,
                         "--perf-stream", TRAIN_FIXTURE,
                         "--perf-baseline", BASELINE]) == 0
    out = capsys.readouterr().out
    assert f"ci_gate: perf gate {SERVE_FIXTURE}: PASS" in out
    assert f"ci_gate: perf gate {TRAIN_FIXTURE}: PASS" in out
    assert ci_gate.main(
        ["--perf-stream", SERVE_FIXTURE + ".missing"]) == 2
    assert ci_gate.main(["--perf-stream", SERVE_FIXTURE,
                         "--perf-baseline",
                         BASELINE + ".missing"]) == 2


def test_ci_gate_perf_stream_fails_on_tamper(tmp_path, capsys):
    """The tamper gate: an edited host fraction (or phase totals that
    stop summing to wall) must FAIL no matter how wide the baseline's
    noise bands are — consistency is checked against the stream's own
    arithmetic."""
    ci_gate = _load_tool("ci_gate")
    records = _fixture_records(SERVE_FIXTURE)

    def rewrite(mutate):
        out = []
        for rec in records:
            rec = json.loads(json.dumps(rec))     # deep copy
            mutate(rec)
            out.append(rec)
        p = tmp_path / "tampered.jsonl"
        p.write_text("".join(json.dumps(r) + "\n" for r in out))
        return str(p)

    def forge_fraction(rec):
        if rec["record"] == "overhead_summary":
            rec["host_overhead_frac"] = 0.01      # "we're efficient"

    assert ci_gate.main(["--perf-stream", rewrite(forge_fraction)]) == 1
    assert "tampered" in capsys.readouterr().err

    def shrink_a_phase(rec):
        if rec["record"] == "tick_profile":
            rec["phases"]["dispatch_enqueue"] *= 0.5

    assert ci_gate.main(["--perf-stream", rewrite(shrink_a_phase)]) == 1
    assert "sum to wall" in capsys.readouterr().err

    def drop_summary(rec):
        if rec["record"] == "overhead_summary":
            rec["record"] = "tick_profile"        # will also fail lint

    assert ci_gate.main(["--perf-stream", rewrite(drop_summary)]) == 1
    assert "overhead_summary" in capsys.readouterr().err


def test_perf_baseline_round_trips_and_flags_regressions(tmp_path,
                                                         capsys):
    """PERF_BASELINE.json is generated FROM the checked-in fixtures, so
    comparing the fixtures against it is exact — exit 0 at HEAD.  A
    re-derived baseline matches the checked-in one, and shifting a
    value past its noise band is flagged."""
    perf_ledger = _load_tool("perf_ledger")
    assert perf_ledger.main([SERVE_FIXTURE, TRAIN_FIXTURE,
                             "--compare", BASELINE]) == 0
    assert "compare vs" in capsys.readouterr().out

    # round-trip: snapshot -> make_baseline == the checked-in file
    snaps = [perf_ledger.snapshot(_fixture_records(p), p)
             for p in (SERVE_FIXTURE, TRAIN_FIXTURE)]
    assert json.load(open(BASELINE)) == perf_ledger.make_baseline(snaps)
    assert perf_ledger.compare(snaps, json.load(open(BASELINE))) == []

    # regression: a host fraction drifting past its band is named
    shifted = perf_ledger.make_baseline(snaps)
    m = shifted["streams"]["serve"]["metrics"]["host_overhead_frac"]
    m["value"] = m["value"] * 0.5                  # 50% drop, 10% band
    failures = perf_ledger.compare(snaps, shifted)
    assert any("host_overhead_frac" in f and "regression" in f
               for f in failures)
    # exact-band counters catch any drift at all
    shifted2 = perf_ledger.make_baseline(snaps)
    shifted2["streams"]["serve"]["metrics"]["requests"]["value"] += 1
    assert perf_ledger.compare(snaps, shifted2) != []
    # unusable inputs exit 2
    assert perf_ledger.main([str(tmp_path / "nope.jsonl")]) == 2
    bad = tmp_path / "bad.jsonl"
    bad.write_text("not json\n")
    assert perf_ledger.main([str(bad)]) == 2


# ===================== report degradation + schema back-compat

def test_reports_degrade_gracefully_on_pre_v15_streams(capsys):
    """Pre-v15 streams carry no overhead_summary / idle fields: both
    report tools must render WITHOUT an OVERHEAD line, not crash — and
    the v15 train fixture must render one."""
    old_serve = os.path.join(REPO, "tests", "fixtures", "slo",
                             "serve_slo.jsonl")
    serve_report = _load_tool("serve_report")
    assert serve_report.report(old_serve) == 0
    assert "OVERHEAD" not in capsys.readouterr().out
    telemetry_report = _load_tool("telemetry_report")
    assert telemetry_report.report(old_serve) == 0
    assert "OVERHEAD" not in capsys.readouterr().out
    assert telemetry_report.report(TRAIN_FIXTURE) == 0
    out = capsys.readouterr().out
    assert "OVERHEAD: kind train" in out
    assert "data_wait" in out and "dispatch" in out


def test_v17_validates_every_older_fixture_stream():
    """v17 and every version since is a strict superset: every
    checked-in v10-v17 fixture stream still validates unchanged, and the
    two hard-coded jax-free SCHEMA constants moved in lockstep with
    SCHEMA_VERSION."""
    assert obs_schema.SCHEMA_VERSION >= 17
    fixture_root = os.path.join(REPO, "tests", "fixtures")
    seen = 0
    for sub in ("slo", "fleet", "quant", "disagg", "perf", "spec",
                "sched"):
        d = os.path.join(fixture_root, sub)
        for name in sorted(os.listdir(d)):
            if not name.endswith(".jsonl"):
                continue
            records = _fixture_records(os.path.join(d, name))
            assert obs_schema.validate_stream(records) == [], name
            seen += 1
    assert seen >= 7            # the older strata are actually covered
    sup = _load_tool_pkg("apex_example_tpu/resilience/supervisor.py",
                         "_sup")
    router = _load_tool_pkg("apex_example_tpu/fleet/router.py",
                            "_router")
    assert sup.SCHEMA == obs_schema.SCHEMA_VERSION
    assert router.SCHEMA == obs_schema.SCHEMA_VERSION


def _load_tool_pkg(rel, name):
    """Grep-light SCHEMA extraction: both files are jax-free by
    contract but import their package siblings, so read the constant
    textually instead of executing them here."""
    class _C:
        pass

    with open(os.path.join(REPO, rel)) as fh:
        for line in fh:
            if line.startswith("SCHEMA = "):
                c = _C()
                c.SCHEMA = int(line.split("=")[1].split("#")[0])
                return c
    raise AssertionError(f"no SCHEMA constant in {rel}")


def test_schema_emission_rule_covers_v15_record_types():
    """graftlint's static schema-emission rule knows tick_profile and
    overhead_summary: valid emitters are quiet, an undeclared field on
    either fires with the bump-the-schema message."""
    from tools.graftlint import schema_rules
    from tools.graftlint.base import tree_from_sources
    with open(os.path.join(REPO, "apex_example_tpu", "obs",
                           "schema.py")) as fh:
        real_schema = fh.read()
    tree = tree_from_sources({
        "apex_example_tpu/obs/schema.py": real_schema,
        "pkg/emit.py": """
def emit(sink, ts, phases):
    sink.write({"record": "tick_profile", "time": 1.0, "ts": ts,
                "kind": "serve", "tick": 3, "wall_ms": 2.0,
                "host_gap_ms": 1.0, "phases": phases})
    sink.write({"record": "overhead_summary", "time": 1.0,
                "kind": "serve", "ticks": 4, "wall_ms": 8.0,
                "device_ms": 4.0, "host_gap_ms": 4.0,
                "host_overhead_frac": 0.5, "phases": phases})
"""})
    assert schema_rules.check(tree) == []
    tree = tree_from_sources({
        "apex_example_tpu/obs/schema.py": real_schema,
        "pkg/emit.py": """
def emit(sink, ts, phases):
    rec = {"record": "tick_profile", "time": 1.0, "ts": ts,
           "kind": "serve", "tick": 3, "wall_ms": 2.0,
           "host_gap_ms": 1.0, "phases": phases}
    rec["gpu_ms"] = 0.5            # undeclared: needs a schema bump
    sink.write(rec)
    sink.write({"record": "overhead_summary", "time": 1.0,
                "kind": "serve", "ticks": 4})   # missing required
"""})
    msgs = [f.message for f in schema_rules.check(tree)]
    assert any("'tick_profile' emits field 'gpu_ms'" in m
               and "bump the schema" in m for m in msgs)
    assert any("never sets required field 'host_overhead_frac'" in m
               for m in msgs)


def test_fleet_tick_profile_advertises_worst_replica(tmp_path, capsys):
    """fleet.py --tick-profile (thread transport, the session's
    SLOTS=4/MAX_LEN=32 geometry): every replica engine gets an
    ACCUMULATE-only profiler (no per-engine sink), heartbeats advertise
    the cumulative host_overhead_frac, the router's close emits one
    final replica_state per armed replica carrying it, the stream stays
    schema-valid with NO v15 tick records leaking into the router
    stream, fleet_report names the worst-host-overhead replica, and
    perf_ledger's fleet snapshot ranks on the same number."""
    import fleet as fleet_cli

    path = str(tmp_path / "fleet.jsonl")
    rc = fleet_cli.main(["--transport", "thread", "--replicas", "2",
                         "--requests", "6", "--slots", str(SLOTS),
                         "--max-len", str(MAX_LEN),
                         "--tick-profile", "--tick-profile-every", "4",
                         "--metrics-jsonl", path])
    assert rc == 0
    records = obs.read_jsonl(path)
    assert obs_schema.validate_stream(records) == []
    kinds = {r["record"] for r in records}
    assert "tick_profile" not in kinds       # router stream stays
    assert "overhead_summary" not in kinds   # fleet-only (emit=None)
    fracs = [r for r in records if r["record"] == "replica_state"
             and "host_overhead_frac" in r]
    assert {r["replica"] for r in fracs} == {"r0", "r1"}
    assert all(0.0 < r["host_overhead_frac"] <= 1.0 for r in fracs)

    fleet_report = _load_tool("fleet_report")
    capsys.readouterr()
    assert fleet_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "host overhead: worst replica" in out
    assert "2 replica(s) reporting" in out

    perf_ledger = _load_tool("perf_ledger")
    snap = perf_ledger.snapshot(records, path)
    assert snap["kind"] == "fleet"
    worst = max(r["host_overhead_frac"] for r in fracs)
    assert snap["metrics"]["worst_host_overhead_frac"] == \
        pytest.approx(worst)
