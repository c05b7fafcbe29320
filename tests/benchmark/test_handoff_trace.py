"""The chip's idle time between two ticks by the innermost program span
(``benchmarks/handoff_trace.py``) and the five per-layer metrics over it.

``HAND`` is a trace written by hand in ``program_trace``'s plain form, in
microseconds here and nanoseconds where it is read: three runs of the
tick's program (1000-2000, 2900-3900, 4800-5800), the two small programs
of the key split inside each gap (15 us busy), and three ticks on the host
whose children are laid out so that every rule has a stretch to itself: a
fetch that outlasts the program (the tail), a fetch that starts as the gap
ends (nothing of it), children with nothing between them and children with
5-10 us between them (the phase's own), a tick that starts 5 us before its
first phase (``engine.tick``'s own), and 40-45 us between two ticks
(``harness``).  The expected parts below were added up by hand from those
numbers, gap by gap; each gap is 900 - 15 = 885 us idle.

``recorded_handoff_trace.json`` is a cut of a traced ``gpt1.chat_poisson``
run of PR 38 on the chip (TPU v5e, jax 0.9.0, seed 2500380011), times from
0.2 ms before its first tick: ``planes`` in the plain form, three
consecutive runs of the tick's program with their three ticks on the host
(ticks 2137-2139; two gaps, each with the key split's two small programs
inside ``engine.rng``); between two runs every operation is kept, inside a
run those of 20 us or longer (382 of 3856); ``meta`` holds the metadata of
its ``engine.*`` events, ``[name, start_ns, {...}]``.  The constants at the
end were added up by hand from the events round each gap: run 1 ends at
23,232,605 ns and run 2 starts at 29,813,091, the five operations between
them take 3,091 ns, ``engine.fetch`` of ``nxt`` returns 1,158,055 ns after
run 1 ends and the one of ``finite`` takes 422,330 more, the eight puts of
tick 2138 add up to 2,816,620, its ``engine.rng`` is 993,260 less the
3,091 busy, and its program starts 467,571 ns after ``engine.enqueue``
opens; the second gap likewise.
"""

import json
import os
import statistics

import pytest

from benchmarks import handoff_trace as ht
from benchmarks import harness
from benchmarks import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = harness.benchmark_spec()
NEW = ["tick_handoffs_p50", "tick_gap_build_ms_p50", "tick_gap_rng_ms_p50",
       "tick_gap_put_ms_p50", "tick_gap_fetch_ms_p50"]
SERVING = ["gpt1.chat_poisson", "xing4.longctx_poisson",
           "granite4h.shortchat_poisson", "pangu718b.reason_poisson"]
STEP, SPLIT, UNSTACK = "jit_step(1)", "jit__threefry_split(2)", \
    "jit__unstack(3)"


def _us(events):
    return [[name, start * 1000, (end - start) * 1000]
            for name, start, end in events]


def _hand():
    ops = [("%fusion.1", 1000, 2000), ("%fusion.1", 2900, 3900),
           ("%fusion.1", 4800, 5800),
           ("%threefry.1", 2620, 2630), ("%slice.1", 2640, 2645),
           ("%threefry.1", 4520, 4530), ("%slice.1", 4540, 4545)]
    modules = [(STEP, 1000, 2000), (STEP, 2900, 3900), (STEP, 4800, 5800),
               (SPLIT, 2620, 2630), (UNSTACK, 2640, 2645),
               (SPLIT, 4520, 4530), (UNSTACK, 4540, 4545)]
    host = [
        # tick 1: 6 hand-offs (rng, 2 puts, enqueue, 2 fetches)
        ("engine.tick", 100, 2400), ("engine.admit", 100, 150),
        ("engine.marshal", 150, 900), ("engine.build", 160, 400),
        ("engine.rng", 410, 500), ("engine.put", 510, 600),
        ("engine.put", 610, 700), ("engine.enqueue", 900, 990),
        ("engine.sync", 990, 2250), ("engine.fetch", 1000, 2200),
        ("engine.fetch", 2205, 2240), ("engine.harvest", 2250, 2350),
        ("engine.gauges", 2350, 2400),
        # tick 2: 6 hand-offs; starts 5 us before its admit
        ("engine.tick", 2445, 4300), ("engine.admit", 2450, 2480),
        ("engine.marshal", 2480, 2850), ("engine.build", 2490, 2600),
        ("engine.rng", 2605, 2660), ("engine.put", 2670, 2740),
        ("engine.put", 2750, 2840), ("engine.enqueue", 2850, 2890),
        ("engine.sync", 2890, 4150), ("engine.fetch", 2895, 4100),
        ("engine.fetch", 4105, 4140), ("engine.harvest", 4150, 4250),
        ("engine.gauges", 4250, 4300),
        # a tick that turned away what it had: admit alone, no child
        ("engine.tick", 4310, 4330), ("engine.admit", 4310, 4330),
        # tick 3: 7 hand-offs (3 puts, back to back)
        ("engine.tick", 4340, 6200), ("engine.admit", 4345, 4370),
        ("engine.marshal", 4370, 4760), ("engine.build", 4375, 4500),
        ("engine.rng", 4500, 4560), ("engine.put", 4560, 4620),
        ("engine.put", 4620, 4690), ("engine.put", 4690, 4755),
        ("engine.enqueue", 4760, 4795), ("engine.sync", 4795, 6050),
        ("engine.fetch", 4800, 6000), ("engine.fetch", 6000, 6040),
        ("engine.harvest", 6050, 6150), ("engine.gauges", 6150, 6200),
        ("bench.engine_step", 95, 2405), ("bench.engine_step", 2440, 4305),
        ("bench.engine_step", 4335, 6205)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": _us(modules)},
            {"name": "XLA Ops", "events": [ev + [""] for ev in _us(ops)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": _us(host)}]}]


# gap 1 (2000-2900) and gap 2 (3900-4800), us, added up by hand
HAND_PARTS_US = [
    {"engine.fetch": 200 + 35 + 5, "engine.sync": 5 + 10 + 5,
     "engine.harvest": 100, "engine.gauges": 50, "harness": 45,
     "engine.tick": 5, "engine.admit": 30,
     "engine.marshal": 10 + 5 + 10 + 10 + 10, "engine.build": 110,
     "engine.rng": 55 - 15, "engine.put": 70 + 90, "engine.enqueue": 40},
    # the tick between (4310-4330) ran no step: the harness's, all 40 us
    {"engine.fetch": 200 + 35, "engine.sync": 5 + 10 + 5,
     "engine.harvest": 100, "engine.gauges": 50, "harness": 40,
     "engine.tick": 5, "engine.admit": 25, "engine.marshal": 5 + 5,
     "engine.build": 125, "engine.rng": 60 - 15,
     "engine.put": 60 + 70 + 65, "engine.enqueue": 35}]


@pytest.fixture(scope="module")
def hand():
    return _hand()


@pytest.fixture(scope="module")
def old_recording():
    """PR 24's cut: the six phases and no child."""
    return harness.load_json(os.path.join(
        HERE, "recorded_program_trace.json"))["gpt1.chat_poisson"]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_handoff_trace.json")) as f:
        return json.load(f)


def _without_children(planes):
    return [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [ev for ev in ln["events"]
                                        if ev[0] not in ht.CHILDREN]}
        for ln in p["lines"]]} for p in planes]


def test_the_hand_built_gaps_split_as_added_up_by_hand(hand):
    gaps = ht.gap_parts(hand)
    assert len(gaps) == 2
    for got, want in zip(gaps, HAND_PARTS_US):
        assert sum(want.values()) == 885
        assert {k: v for k, v in got.items()
                if k not in ("idle", "period")} \
            == {k: v * 1000 for k, v in want.items()}
        assert got["idle"] == 885_000 and got["period"] == 1_900_000


def test_the_five_readers_give_the_hand_checked_values(hand, monkeypatch):
    monkeypatch.setattr(ht, "of_run", lambda: hand)
    got = {name: harness.layer_metric_reader(name)(None) for name in NEW}
    assert got == {
        "tick_handoffs_p50": 6.0,                      # 6, 6 and 7
        "tick_gap_build_ms_p50": pytest.approx((0.110 + 0.125) / 2),
        "tick_gap_rng_ms_p50": pytest.approx((0.040 + 0.045) / 2),
        "tick_gap_put_ms_p50": pytest.approx((0.160 + 0.195) / 2),
        "tick_gap_fetch_ms_p50": pytest.approx((0.240 + 0.235) / 2)}
    # what a phase's children leave is the phase's own, by its own name
    assert ht.gap_ms_p50(hand, "engine.marshal") \
        == pytest.approx((0.045 + 0.010) / 2)
    assert ht.tick_handoffs(hand) == [6, 6, 7]


@pytest.mark.parametrize("which", ["hand", "recorded"])
def test_parts_and_remainder_make_tick_gaps_idle_to_the_nanosecond(
        hand, recorded, which):
    planes = hand if which == "hand" else recorded["planes"]
    new, old = ht.gap_parts(planes), pt.tick_gaps(planes)
    assert len(new) == len(old) >= 2
    for g, o in zip(new, old):
        assert g["idle"] == o["idle"] > 0 and g["period"] == o["period"]
        assert sum(v for k, v in g.items()
                   if k not in ("idle", "period")) == g["idle"]
        # innermost wins: a phase keeps what its children do not cover, and
        # with them it is what tick_gaps lays to the phase whole
        for phase in pt.ENGINE_PHASES:
            kids = sum(g.get(k, 0) for k, into in ht.CHILDREN.items()
                       if into == phase)
            assert g.get(phase, 0) + kids == o[phase], phase
        assert g.get(pt.ENGINE_TICK, 0) == o[pt.ENGINE_TICK]
        assert g[pt.HARNESS] == o[pt.HARNESS]
        assert sum(g.get(k, 0) for k in ht.CHILDREN) > 0
        assert g.get("engine.marshal", 0) < o["engine.marshal"]


def test_innermost_takes_any_names_and_counts_a_nanosecond_once():
    idle = [(0, 100), (150, 200)]
    levels = [[("leaf", 10, 30), ("leaf", 160, 170)],
              [("branch", 5, 60), ("twig", 140, 180)],
              [("root", 0, 190)]]
    assert ht.innermost(idle, levels, "outside") == {
        "leaf": 20 + 10, "branch": 55 - 20, "twig": 30 - 10,
        "root": 5 + 40 + 10, "outside": 10}
    assert ht.innermost(idle, [], "outside") == {"outside": 150}
    assert ht.innermost([], levels, "outside") == {"outside": 0}


@pytest.mark.parametrize("which", ["parent", "old_recording", "host_only",
                                   "nothing", "no_trace", "training"])
def test_every_reader_gives_none_where_there_is_nothing_to_read(
        hand, old_recording, which, monkeypatch):
    planes = {
        "parent": _without_children(hand),
        "old_recording": old_recording,
        "host_only": [p for p in hand if not p["name"].startswith("/device")],
        "nothing": [], "no_trace": None,
        "training": harness.load_json(os.path.join(
            HERE, "recorded_program_trace.json"))["bert_base.lamb_s128"],
    }[which]
    monkeypatch.setattr(ht, "of_run", lambda: planes)
    for name in NEW:
        assert harness.layer_metric_reader(name)(None) is None, name
    if planes:
        # the older readers read a trace with children as one without
        assert pt.gap_ms_p50(planes) == pt.gap_ms_p50(
            _without_children(planes))
        ht.report(planes)           # nothing to say is no error either


def test_the_older_gap_metrics_do_not_see_the_children(hand):
    bare = _without_children(hand)
    assert pt.tick_gaps(hand) == pt.tick_gaps(bare)
    assert len(pt.engine_ticks(hand)) == len(pt.engine_ticks(bare)) == 3
    for phases in (None, pt.DISPATCH_PHASES, pt.HARVEST_PHASES):
        assert pt.gap_ms_p50(hand, phases) == pt.gap_ms_p50(bare, phases)


def test_the_notes_add_up_and_name_a_stall(hand, capsys):
    meta = {("engine.tick", 4_340_000): {"tick": 7, "handoffs": 7},
            ("engine.tick", 2_445_000): {"tick": 6, "handoffs": 6},
            ("engine.put", 4_690_000): {"arg": "aux", "bytes": 512},
            ("engine.fetch", 4_800_000): {"out": "nxt", "bytes": 256}}
    # a fourth tick that stalls 9 ms in one put
    stalled = _hand()
    stalled[1]["lines"][0]["events"] += _us([
        ("engine.tick", 6300, 17300), ("engine.admit", 6300, 6350),
        ("engine.marshal", 6350, 15900), ("engine.build", 6360, 6500),
        ("engine.rng", 6500, 6560), ("engine.put", 6560, 15800),
        ("engine.enqueue", 15900, 15950), ("engine.sync", 15950, 17150),
        ("engine.fetch", 15960, 17100), ("engine.harvest", 17150, 17250),
        ("engine.gauges", 17250, 17300)])
    meta[("engine.put", 6_560_000)] = {"arg": "table", "bytes": 8192}
    ht.report(stalled, meta)
    err = capsys.readouterr().err
    line = next(ln for ln in err.splitlines() if "by innermost span" in ln)
    total, parts = line.split("ms mean: ")[1].split(" = ")
    assert float(total) == pytest.approx(0.885)
    assert sum(float(p.rsplit(" ", 1)[1]) for p in parts.split(" + ")) \
        == pytest.approx(0.885, abs=0.006)          # twelve roundings
    assert "of the mean idle under engine.marshal (0.365 ms by tick_gaps)" \
        in err
    assert "p50 6, min 4, max 7; engine.tick's handoffs= says 6.5" in err
    assert "put[aux]" in err and "fetch[nxt]" in err
    stall = next(ln for ln in err.splitlines() if "traced tick 3" in ln)
    assert "under put[table]" in stall and "9." in stall
    # no program ran inside the stalled tick of this trace
    assert "its program started" not in stall
    assert ht.launch_leads(stalled) == [100_000, 50_000, 40_000, None]


def test_the_benchmark_names_the_five_for_the_serving_cells():
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        m = by_name[name]                   # found by name, wherever it is
        assert m["workloads"] == SERVING, name
        assert (m["layer"], m["moves"], m["source"], m["better"]) \
            == ("serve engine", "tpot_ms_p50", "program_span", "lower")
        assert m["unit"] == ("count" if name == "tick_handoffs_p50"
                             else "ms")
        assert harness.layer_metric_reader(name) is not None
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(SERVING) <= cells
    assert ht.CHILDREN.keys() >= {"engine.build", "engine.rng",
                                  "engine.put", "engine.fetch"}


def test_the_recording_from_the_chip_reads_as_added_up_by_hand(recorded):
    planes = recorded["planes"]
    gaps = ht.gap_parts(planes)
    assert [g["idle"] for g in gaps] == [6_577_395, 6_841_605]
    for g, want in zip(gaps, RECORDED_PARTS_NS):
        assert {k: g[k] for k in want} == want
    assert ht.handoffs_p50(planes) == 12.0
    for name, want in RECORDED_P50_MS.items():
        assert ht.gap_ms_p50(planes, name) == pytest.approx(want, abs=1e-9)
    assert statistics.median(g["idle"] for g in gaps) / 1e6 \
        == pytest.approx(pt.gap_ms_p50(planes))
    # the program starts after the call that launches it
    assert ht.launch_leads(planes) == [354_845, 467_571, 314_111]


def test_the_recorded_ticks_say_what_was_counted(recorded):
    """One traced tick read by hand (2138): the split, eight puts in the
    order the step takes them, the call, two fetches: twelve, as its
    ``handoffs=`` says."""
    planes = recorded["planes"]
    meta = {(n, s): m for n, s, m in recorded["meta"]}
    ticks = pt.engine_ticks(planes)
    kids = ht.child_spans(planes)
    assert [meta[(pt.ENGINE_TICK, t["start"])]["tick"] for t in ticks] \
        == [2137, 2138, 2139]
    for t, counted in zip(ticks, ht.tick_handoffs(planes)):
        said = meta[(pt.ENGINE_TICK, t["start"])]
        assert counted == said["handoffs"] == 12
        mine = [k for k in kids if t["start"] <= k[1] and k[2] <= t["end"]]
        assert [k[0].split(".")[1] for k in mine] \
            == ["build", "rng"] + ["put"] * 8 + ["fetch"] * 2
        assert [meta[(k[0], k[1])]["arg"] for k in mine
                if k[0] == "engine.put"] == [
            "tok", "table", "fill", "n_new", "cow_src", "cow_dst", "temps",
            "ks"]
        assert [meta[(k[0], k[1])]["out"] for k in mine
                if k[0] == "engine.fetch"] == ["nxt", "finite"]
        assert meta[(mine[0][0], mine[0][1])]["lanes"] >= said["live"]


RECORDED_PARTS_NS = [
    {"engine.fetch": 1_158_055 + 422_330, "engine.build": 151_780,
     "engine.rng": 993_260 - 3_091, "engine.put": 2_816_620,
     "engine.enqueue": 467_571},
    {"engine.fetch": 1_639_841, "engine.build": 149_360,
     "engine.rng": 1_081_133, "engine.put": 3_085_544,
     "engine.enqueue": 314_111}]
RECORDED_P50_MS = {"engine.build": 0.150570, "engine.rng": 1.035651,
                   "engine.put": 2.951082, "engine.fetch": 1.610113}
