"""The program's own span vocabulary (ISSUE 24): the serve tick's phases as
``jax.profiler.TraceAnnotation`` events and the named scopes of the compiled
programs.

- a tiny engine stepped under ``jax.profiler.start_trace`` on the CPU, its
  ``.xplane.pb`` read back with ``ProfileData``: every tick that ran a step
  has one ``engine.tick`` with the six phases once, in order, contiguous,
  summing to the tick, and carries its tick number and live count; an idle
  spin writes nothing,
- tracing is a pure observer: tokens with a session open equal tokens
  without, and the jitted step compiles once either way,
- one level down (ISSUE 38): every hand-off between the tick's host thread
  and the runtime is a child span, whole inside the phase
  ``ENGINE_HANDOFFS`` names for it, overlapping no other; their number a
  tick is the tick's ``handoffs=`` and the engine's own count (ISSUE 39:
  5, the key, the one packed put, the call and two fetches); a child
  moves no boundary,
- the Tracer's X events and the TickProfiler's fold come from the same
  boundary readings (they agree to the float, and the streams validate),
- ``obs.spans.Phases`` telescopes, closes once, and closes on an exception,
- one table of names: the engine, the benchmark's reader and README agree,
- the scopes are in the compiled programs' ``op_name`` metadata and move no
  parameter path.
"""

import glob
import importlib.util
import os
import re

import pytest

import jax
import jax.numpy as jnp

from apex_example_tpu import obs
from apex_example_tpu.models.gpt import gpt_tiny
from apex_example_tpu.obs import schema as obs_schema
from apex_example_tpu.obs import trace as trace_lib
from apex_example_tpu.obs.spans import PHASES, Phases
from apex_example_tpu.obs.tickprof import (ENGINE_HANDOFF_SPANS,
                                           ENGINE_HANDOFFS,
                                           ENGINE_KEY_AHEAD, ENGINE_PHASES,
                                           ENGINE_TICK, SERVE_PHASES,
                                           TickProfiler)
from apex_example_tpu.serve import ServeEngine, synthetic_requests
from apex_example_tpu.serve.engine import _slot_step

pytestmark = pytest.mark.tickprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, MAX_LEN = 4, 32          # the session-shared decode geometry
NAMES = list(ENGINE_PHASES)


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt_tiny()
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _engine(model_and_params, **kw):
    model, params = model_and_params
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0), **kw)
    eng.queue.submit_all(synthetic_requests(
        6, vocab_size=model.vocab_size, seed=3, prompt_len=(3, 8),
        max_new=(3, 10), stagger=5))
    eng.queue.close()
    return eng


def _tokens(eng):
    """Served tokens in submission order (uids count up process-wide)."""
    done = sorted(eng.completions, key=lambda c: c.request.uid)
    return [list(c.tokens) for c in done]


def _engine_events(trace_dir):
    """(name, start_ns, end_ns, stats) of the ``engine.*`` host events."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


@pytest.fixture(scope="module")
def traced(model_and_params, tmp_path_factory):
    """One run with a profiler session open and one without."""
    plain = _engine(model_and_params)
    plain.run(max_steps=500)
    compiled = plain._step_fn._cache_size()
    trace_dir = str(tmp_path_factory.mktemp("engine_spans"))
    eng = _engine(model_and_params)
    jax.profiler.start_trace(trace_dir)
    try:
        eng.run(max_steps=500)
        spun = eng.step_count
        for _ in range(5):          # idle spin: nothing live, nothing queued
            assert eng.step() is False
    finally:
        jax.profiler.stop_trace()
    return {"plain": plain, "eng": eng, "spun": spun, "compiled": compiled,
            "events": _engine_events(trace_dir)}


def test_every_tick_that_ran_a_step_has_the_six_phases(traced):
    eng, events = traced["eng"], traced["events"]
    ticks = [ev for ev in events if ev[0] == ENGINE_TICK]
    ran = []
    for _, start, end, stats in ticks:
        inside = [ev for ev in events if ev[0] in ENGINE_PHASES
                  and start <= ev[1] and ev[2] <= end]
        if [ev[0] for ev in inside] == NAMES[:1]:
            # it had a request to look at and turned it away or could
            # not place it: no step ran, and it says so
            assert "live" not in stats
            continue
        assert [ev[0] for ev in inside] == NAMES, stats
        # contiguous: a phase starts where the one before ended, with
        # nothing between but the two annotations' own exit and enter
        # (tens of microseconds under the profiler's Python tracer, on
        # ticks of a few milliseconds here; 0.03% of a tick on the chip)
        slack = 200_000                                 # ns
        for a, b in zip(inside, inside[1:]):
            assert 0 <= b[1] - a[2] <= slack
        assert 0 <= inside[0][1] - start <= slack
        assert 0 <= end - inside[-1][2] <= slack
        total = sum(ev[2] - ev[1] for ev in inside)
        assert 0 <= (end - start) - total \
            <= 0.01 * (end - start) + 7 * slack
        assert 1 <= stats["live"] <= SLOTS
        ran.append(stats["tick"])
    assert len(ran) == eng.compute_steps > 0
    assert ran == sorted(set(ran)) and ran[-1] < traced["spun"]
    # every phase event belongs to some tick
    assert sum(ev[0] in ENGINE_PHASES for ev in events) \
        == 6 * len(ran) + (len(ticks) - len(ran))


def _by_tick(events):
    """(tick, its phases by name, its children) of every traced tick."""
    out = []
    for tick in (ev for ev in events if ev[0] == ENGINE_TICK):
        inside = [ev for ev in events
                  if tick[1] <= ev[1] and ev[2] <= tick[2]]
        out.append((tick,
                    {ev[0]: ev for ev in inside if ev[0] in ENGINE_PHASES},
                    [ev for ev in inside if ev[0] in ENGINE_HANDOFFS]))
    return out


@pytest.mark.parametrize("case", ["inside_their_phase", "disjoint",
                                  "counted", "none_without_a_step",
                                  "no_session_no_difference"])
def test_the_hand_offs_are_children_of_the_phases(traced, case):
    eng, events = traced["eng"], traced["events"]
    ticks = _by_tick(events)
    ran = [t for t in ticks if len(t[1]) == 6]
    assert len(ran) == eng.compute_steps > 0
    if case == "inside_their_phase":
        # every child event of the trace is some tick's, and lies whole
        # inside the phase the table names for it
        assert sum(len(kids) for _, _, kids in ticks) \
            == sum(ev[0] in ENGINE_HANDOFFS for ev in events)
        for _, phases, kids in ran:
            assert {k[0] for k in kids} == set(ENGINE_HANDOFFS)
            for name, start, end, _ in kids:
                _, lo, hi, _ = phases[ENGINE_HANDOFFS[name]]
                assert lo <= start <= end <= hi, name
    elif case == "disjoint":
        for _, _, kids in ran:
            for a, b in zip(kids, kids[1:]):
                assert a[2] <= b[1], (a[0], b[0])
            # the host's own work first, then the key, then the one put
            assert [k[0] for k in kids][:3] == [
                "engine.build", "engine.rng", "engine.put"]
            assert [k[0] for k in kids][3:] == ["engine.fetch"] * 2
            assert kids[0][3]["lanes"] >= 1
    elif case == "counted":
        # the events, the tick's own word and the engine's count agree:
        # the key + the one put + the step's call + 2 fetches on this engine
        total = 0
        for tick, phases, kids in ran:
            made = [k for k in kids if k[0] in ENGINE_HANDOFF_SPANS] \
                + [phases["engine.enqueue"]]
            assert len(made) == tick[3]["handoffs"] == 5
            total += len(made)
            assert [k[3]["arg"] for k in kids if k[0] == "engine.put"] \
                == ["packed"]
            assert [k[3]["out"] for k in kids if k[0] == "engine.fetch"] \
                == ["nxt", "finite"]
            assert [k[3]["bytes"] for k in kids if k[0] == "engine.put"] \
                == [4 * SLOTS * eng.tick_args.width]
            assert all(k[3]["bytes"] > 0 for k in kids
                       if k[0] == "engine.fetch")
        assert total == eng.runtime_handoffs
        assert eng.summary_record()["runtime_handoffs_per_tick"] == 5
    elif case == "none_without_a_step":
        idle = [t for t in ticks if len(t[1]) != 6]
        for tick, phases, kids in idle:
            assert list(phases) == NAMES[:1] and kids == []
            assert "handoffs" not in tick[3]
    else:
        # the count is made with a session or without one
        assert traced["plain"].runtime_handoffs == eng.runtime_handoffs
        assert traced["plain"].compute_steps == eng.compute_steps
        assert _tokens(eng) == _tokens(traced["plain"])
        assert eng._step_fn._cache_size() == traced["compiled"]


def test_speculation_fetches_its_two_lane_arrays_as_well(model_and_params):
    eng = _engine(model_and_params, speculate=2)
    eng.run(max_steps=500)
    assert eng.runtime_handoffs == 7 * eng.compute_steps > 0


def test_an_idle_spin_writes_nothing(traced):
    eng, events = traced["eng"], traced["events"]
    assert eng.idle_ticks >= 5
    assert eng.step_count == traced["spun"] + 5
    assert all(ev[3]["tick"] < traced["spun"] for ev in events
               if ev[0] == ENGINE_TICK)
    # the staggered arrivals leave idle ticks between waves too: far more
    # ticks than annotations
    assert sum(ev[0] == ENGINE_TICK for ev in events) \
        < eng.step_count - 5


def test_tokens_and_compile_count_do_not_depend_on_a_session(
        traced, model_and_params):
    assert len(traced["eng"].completions) == 6
    assert _tokens(traced["eng"]) == _tokens(traced["plain"])
    # one jitted step for both engines (lru-cached on the module config),
    # compiled by the first: the session added no program
    assert traced["eng"]._step_fn is traced["plain"]._step_fn
    assert traced["eng"]._step_fn._cache_size() == traced["compiled"] >= 1


def test_tracer_and_tickprof_are_fed_from_the_same_boundaries(
        model_and_params, tmp_path):
    path = str(tmp_path / "armed.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    emitter = obs.TelemetryEmitter(sink)
    emitter.run_header(config={"slots": SLOTS}, arch="gpt_tiny")
    prof = TickProfiler(kind="serve", sample_every=1, emit=sink.write,
                        run_id=emitter.run_id)
    trace_lib.set_default(obs.Tracer(sink, run_id=emitter.run_id))
    try:
        eng = _engine(model_and_params, sink=sink, run_id=emitter.run_id,
                      tick_profiler=prof)
        eng.run(max_steps=500)
    finally:
        trace_lib.set_default(None)
    sink.write(eng.summary_record())
    sink.write(prof.summary_record())
    sink.close()
    records = obs.read_jsonl(path)
    assert obs_schema.validate_stream(records) == []
    spec = importlib.util.spec_from_file_location(
        "trace_export", os.path.join(REPO, "tools", "trace_export.py"))
    trace_export = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_export)
    assert trace_export.main(["--check", path]) == 0

    profiles = [r for r in records if r["record"] == "tick_profile"]
    engine_row = [r for r in records if r["record"] == "trace_event"
                  and r["tid"] == "engine"]
    begins = [r for r in engine_row if r["ph"] == "B"]
    ends = [r for r in engine_row if r["ph"] == "E"]
    assert len(profiles) == len(begins) == len(ends) == eng.compute_steps
    for prof_rec, b, e in zip(profiles, begins, ends):
        kids = {r["name"]: r for r in engine_row
                if r.get("parent_id") == b["span_id"]}
        assert list(kids) == ["admit", "dispatch", "harvest"]
        ph = prof_rec["phases"]
        assert set(ph) == set(SERVE_PHASES)
        assert prof_rec["ts"] == b["ts"]
        ms = lambda name: kids[name]["dur"] * 1e3
        assert ms("admit") == pytest.approx(ph["admit"], abs=1e-6)
        assert ms("dispatch") == pytest.approx(
            ph["dispatch_enqueue"] + ph["device_wait"], abs=1e-6)
        assert ms("harvest") == pytest.approx(
            ph["harvest"] + ph["spool_io"] + ph["telemetry"], abs=1e-6)
        # the parts telescope to the wall: no reading is taken twice
        assert sum(ph.values()) == pytest.approx(prof_rec["wall_ms"],
                                                 abs=1e-6)
        assert (e["ts"] - b["ts"]) * 1e3 == pytest.approx(
            prof_rec["wall_ms"], abs=1e-6)
        assert b["args"]["tick"] >= 0 and b["args"]["live"] >= 1


def test_phases_telescope_and_close_once():
    with Phases("t", "a", tick=3) as ph:
        ph.enter("b")
        ph.set_meta(live=2)
        now = ph.enter("c")
        end = ph.close()
        with ph.child("kid", n=1) as kid:   # an annotation, no boundary
            kid.set_metadata(m=2)
        assert ph.close() == end            # idempotent
    assert ph.names == ["a", "b", "c"] and len(ph.at) == 4
    assert ph.at[2] == now and ph.at[-1] == end
    assert ph.ms("a", "b", "c") == pytest.approx(
        (ph.at[-1] - ph.at[0]) * 1e3)
    assert ph.ms("b") == pytest.approx((ph.at[2] - ph.at[1]) * 1e3)
    silent = Phases("t", "a", annotate=False)
    silent.enter("b")
    silent.set_meta(live=1)                 # nothing to carry it: ignored
    with silent.child("kid", n=1) as kid:   # nothing at all
        kid.set_metadata(m=2)
    assert silent.names == ["a", "b"] and len(silent.at) == 2
    assert silent.close() >= silent.at[0]


def test_phases_close_on_an_exception(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(RuntimeError):
            with Phases(ENGINE_TICK, NAMES[0], tick=0) as ph:
                ph.enter(NAMES[1])
                raise RuntimeError("mid-tick")
        with Phases(ENGINE_TICK, NAMES[0], tick=1):
            pass
    finally:
        jax.profiler.stop_trace()
    assert len(ph.at) == len(ph.names) + 1
    events = _engine_events(str(tmp_path))
    ticks = [ev for ev in events if ev[0] == ENGINE_TICK]
    assert [t[3]["tick"] for t in ticks] == [0, 1]
    assert ticks[0][2] <= ticks[1][1]       # the first closed: no nesting
    assert sum(ev[0] != ENGINE_TICK for ev in events) == 3


def test_one_table_of_names():
    assert NAMES == ["engine.admit", "engine.marshal", "engine.enqueue",
                     "engine.sync", "engine.harvest", "engine.gauges"]
    assert set(ENGINE_PHASES.values()) | {"spool_io"} == set(SERVE_PHASES)
    spec = importlib.util.spec_from_file_location(
        "program_trace", os.path.join(REPO, "benchmarks",
                                      "program_trace.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert list(reader.ENGINE_PHASES) == NAMES
    assert reader.ENGINE_TICK == ENGINE_TICK
    assert set(reader.SCOPES) < set(PHASES)
    assert set(ENGINE_HANDOFFS.values()) <= set(NAMES)
    assert not set(ENGINE_HANDOFFS) & set(NAMES)
    # what is counted: the children but the host's own work, and the call
    assert set(ENGINE_HANDOFF_SPANS) \
        == set(ENGINE_HANDOFFS) - {"engine.build"} | {"engine.enqueue"}
    spec = importlib.util.spec_from_file_location(
        "handoff_trace", os.path.join(REPO, "benchmarks",
                                      "handoff_trace.py"))
    handoffs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(handoffs)
    assert handoffs.CHILDREN == ENGINE_HANDOFFS
    assert handoffs.HANDOFFS == ENGINE_HANDOFF_SPANS
    readme = open(os.path.join(REPO, "README.md")).read()
    for name in NAMES + [ENGINE_TICK] + list(ENGINE_HANDOFFS) \
            + list(reader.SCOPES):
        assert f"`{name}`" in readme, name
    # every engine.* name the engine emits is in one of the two tables;
    # the span of the key split ahead is in neither (nothing waits for it,
    # the chip is busy under it) and is named once, beside them
    assert ENGINE_KEY_AHEAD not in set(NAMES) | set(ENGINE_HANDOFFS) \
        | set(ENGINE_HANDOFF_SPANS)
    assert f"`{ENGINE_KEY_AHEAD}`" in readme
    engine_src = open(os.path.join(
        REPO, "apex_example_tpu", "serve", "engine.py")).read()
    assert set(re.findall(r'"(engine\.\w+)"', engine_src)) \
        == set(NAMES) | set(ENGINE_HANDOFFS)
    assert "ph.child(ENGINE_KEY_AHEAD)" in engine_src


def _op_names(compiled):
    return set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))


def test_the_serving_program_carries_its_scopes(model_and_params):
    """Since ISSUE 41 the float arena is walked by
    ``ops.attention.paged_gqa_attention`` under ``paged_attention`` (the
    interpreter's kernel here): no ``kv_gather``; the op's XLA form, which
    ``FORCE_XLA`` and a plain CPU drive take, gathers under that name."""
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.serve import engine as engine_lib
    model, params = model_and_params
    eng = ServeEngine(model, params, num_slots=SLOTS, max_len=MAX_LEN,
                      rng=jax.random.PRNGKey(0))
    pool = eng.pool

    def names():
        engine_lib._slot_step.cache_clear()     # the form is read at trace
        return _op_names(_slot_step(pool.dec, eng.tick_args).lower(
            params, pool.cache, jnp.asarray(eng.tick_args.blank(SLOTS)[0]),
            jax.random.PRNGKey(0)).compile())

    def scopes(found):
        return {scope for scope in ("kv_cow", "kv_write", "kv_gather",
                                    "paged_attention")
                if any("/attention/" in n and f"/{scope}/" in n
                       for n in found)}

    kernel = names()
    assert scopes(kernel) == {"kv_cow", "kv_write", "paged_attention"}
    assert any("/paged_attention/paged_gqa_attention/" in n for n in kernel)
    assert any("/sample/" in n for n in kernel)
    # the output projection is the attention scope's, under its own name
    assert any("/paged_attention/output/" in n for n in kernel)
    with ops_config.force_xla():
        xla = names()
    engine_lib._slot_step.cache_clear()
    assert scopes(xla) == {"kv_cow", "kv_write", "kv_gather",
                           "paged_attention"}
    assert any("/paged_attention/kv_gather/" in n for n in xla)


def test_the_training_program_carries_its_scopes_and_moves_no_parameter():
    from apex_example_tpu import amp
    from apex_example_tpu.engine import TrainState, make_train_step
    from apex_example_tpu.models.bert import bert_tiny
    from apex_example_tpu.optim import FusedLAMB
    from apex_example_tpu.workloads import mlm_loss
    policy, scaler = amp.initialize("O2")
    md = amp.module_dtypes(policy)
    model = bert_tiny(dtype=md.compute, param_dtype=md.param,
                      ln_dtype=md.ln_io, softmax_dtype=md.softmax)
    ids = jnp.zeros((4, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert {"mlm_dense", "mlm_ln", "mlm_bias", "word_embeddings"} \
        <= set(params)
    assert "mlm_head" not in params
    opt = FusedLAMB(lr=1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats={}, opt_state=opt.init(params),
                       scaler=scaler)
    step = make_train_step(model, opt, policy, loss_fn=mlm_loss,
                           compute_accuracy=False)
    batch = (ids, (ids, jnp.ones((4, 16), jnp.float32)))
    names = _op_names(jax.jit(step).lower(state, batch).compile())
    wrapped = lambda scope: re.compile(
        rf"/(?:\w+\()*{scope}\)*/")
    # (unscale_check is folded away under O2's static scale of 1)
    for scope in ("fwd_bwd", "optimizer", "mlm_head", "loss"):
        assert any(wrapped(scope).search(n + "/") for n in names), scope
    # forward and backward of the head and of the loss are both named: since
    # PR 46 both passes are loops over the labelled rows' blocks under
    # ``loss`` (workloads.mlm_loss.over_rows), the head inside them
    assert any("/fwd_bwd/jvp(loss)/while/body/" in n for n in names)
    assert any("/transpose(fwd_bwd)/jvp(loss)/while/body/" in n
               for n in names)
    assert any("/while/body/jvp(BertForMaskedLM.head)/mlm_head/" in n
               for n in names)
    assert any("/while/body/transpose(jvp(BertForMaskedLM.head))/mlm_head/"
               in n for n in names)
