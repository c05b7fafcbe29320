"""The reader of the program's own spans and scopes
(``benchmarks/program_trace.py``) and the six per-layer metrics over it.

``recorded_program_trace.json`` holds two cuts of traces recorded on the chip
by PR 24 (TPU v5e, jax 0.9.0), in the plain form with scope paths, names
shortened as in ``recorded_trace.json``:

- ``gpt1.chat_poisson``: three consecutive runs of the tick's program and
  their three ticks on the host (two gaps; two ``jit__threefry_split`` /
  ``jit__unstack`` pairs; the ``engine.*`` and ``bench.*`` events).  Between
  two runs every operation is kept; inside a run those of a microsecond or
  longer (1503 of 6298, 99.98% of the time),
- ``bert_base.lamb_s128``: the operations of one training step that last a
  microsecond or longer (1425 of 10595, 99.69% of the time).

The constants at the end were worked out from the events round each gap:
run 1 ends at 85,309,838 ns of its cut's clock and run 2 starts at
92,994,135; the five operations of the two RNG programs between them take
3,225 ns; ``engine.sync`` of the first tick ends 3.0 ms after its program,
and so on (a second summation, phase by phase, agrees to the nanosecond).
"""

import os
import statistics

import pytest

from benchmarks import harness
from benchmarks import program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = harness.benchmark_spec()
NEW = ["tick_device_gap_ms_p50", "tick_gap_dispatch_ms_p50",
       "tick_gap_harvest_ms_p50", "kv_relayout_time_pct",
       "optimizer_time_pct", "head_loss_time_pct"]


@pytest.fixture(scope="module")
def recorded():
    return harness.load_json(os.path.join(HERE,
                                          "recorded_program_trace.json"))


@pytest.fixture(scope="module")
def serve(recorded):
    return recorded["gpt1.chat_poisson"]


@pytest.fixture(scope="module")
def train(recorded):
    return recorded["bert_base.lamb_s128"]


def _host_only(planes):
    return [p for p in planes if not p["name"].startswith("/device:")]


def _bare(planes):
    """The trace a program without the spans and scopes leaves: the device
    planes, no scope path; the harness's own spans."""
    out = []
    for plane in planes:
        lines = []
        for line in plane["lines"]:
            events = [ev[:3] for ev in line["events"]
                      if not ev[0].startswith("engine.")]
            lines.append({"name": line["name"], "events": events})
        out.append({"name": plane["name"], "lines": lines})
    return out


# ------------------------------------------------------------ the ticks

def test_recorded_ticks_hold_the_six_phases_in_order(serve):
    ticks = pt.engine_ticks(serve)
    assert len(ticks) == 3
    for t in ticks:
        assert [n for n, _, _ in t["phases"]] == list(pt.ENGINE_PHASES)
        covered = sum(e - s for _, s, e in t["phases"])
        # on the chip the six phases fill their tick to a part in 2000
        assert 0.9995 < covered / (t["end"] - t["start"]) <= 1.0
        for (_, _, a_end), (_, b_start, _) in zip(t["phases"],
                                                  t["phases"][1:]):
            assert 0 <= b_start - a_end < 50_000        # ns


def test_gap_parts_and_the_harness_remainder_make_the_gap(serve):
    gaps = pt.tick_gaps(serve)
    assert len(gaps) == 2
    for g in gaps:
        parts = sum(g[k] for k in pt.ENGINE_PHASES
                    + (pt.ENGINE_TICK, pt.HARNESS))
        assert parts == g["idle"] > 0
        assert g["idle"] < g["period"]
        # the split named in BENCHMARK.json leaves out only the harness's
        # loop and the microseconds between two annotations
        named = sum(g[k] for k in pt.ENGINE_PHASES)
        assert g["idle"] - named == g[pt.HARNESS] + g[pt.ENGINE_TICK]
        assert g[pt.ENGINE_TICK] < 50_000


def test_serving_readers_give_the_hand_checked_values(serve):
    gaps = pt.tick_gaps(serve)
    assert [g["idle"] for g in gaps] == GAP_IDLE_NS
    assert [sum(g[p] for p in pt.DISPATCH_PHASES) for g in gaps] \
        == GAP_DISPATCH_NS
    assert [sum(g[p] for p in pt.HARVEST_PHASES) for g in gaps] \
        == GAP_HARVEST_NS
    assert [g[pt.HARNESS] for g in gaps] == GAP_HARNESS_NS
    assert pt.gap_ms_p50(serve) \
        == pytest.approx(statistics.median(GAP_IDLE_NS) / 1e6)
    assert pt.gap_ms_p50(serve, pt.DISPATCH_PHASES) \
        == pytest.approx(statistics.median(GAP_DISPATCH_NS) / 1e6)
    assert pt.gap_ms_p50(serve, pt.HARVEST_PHASES) \
        == pytest.approx(statistics.median(GAP_HARVEST_NS) / 1e6)
    assert pt.scope_pct(serve, ("kv_cow", "kv_write", "kv_gather")) \
        == pytest.approx(KV_RELAYOUT_PCT, abs=1e-3)
    # no training scope in a serving program: nothing to read, not zero
    assert pt.scope_pct(serve, ("optimizer",)) is None
    assert pt.scope_pct(serve, ("mlm_head", "loss")) is None


def test_the_rng_programs_between_two_ticks_count_as_busy(serve):
    ops = pt.device_ops(serve)
    modules = [ev for p in serve if p["name"].startswith("/device:")
               for line in p["lines"] if line["name"] == "XLA Modules"
               for ev in line["events"]]
    runs = [ev for ev in modules if ev[0].startswith("jit_step(")]
    small = [ev for ev in modules if not ev[0].startswith("jit_step(")]
    assert len(runs) == 3 and len(small) == 4
    gaps = pt.tick_gaps(serve)
    for (_, s0, d0), (_, s1, _), g in zip(runs, runs[1:], gaps):
        between = sum(d for _, s, d, _ in ops if s0 + d0 <= s < s1)
        assert between > 0
        assert g["idle"] == (s1 - (s0 + d0)) - between


def test_training_readers_give_the_hand_checked_values(train):
    assert pt.tick_gaps(train) == [] or pt.engine_ticks(train) == []
    assert pt.scope_pct(train, ("optimizer",)) \
        == pytest.approx(OPTIMIZER_PCT, abs=1e-3)
    assert pt.scope_pct(train, ("mlm_head", "loss")) \
        == pytest.approx(HEAD_LOSS_PCT, abs=1e-3)
    assert pt.scope_pct(train, ("kv_cow", "kv_write", "kv_gather")) is None
    st = pt.scope_time(train)
    # each operation once under its innermost scope: the shares add up
    assert sum(st["innermost"].values()) == st["busy_ns"]
    assert pt.scope_pct(train, ("fwd_bwd",)) \
        > pt.scope_pct(train, ("loss",)) > 0
    # backward and rematerialized operations keep the scope of the forward
    paths = {ev[3] for ev in pt.device_ops(train)}
    assert any("transpose(jvp(loss))" in p for p in paths)
    assert any("/mlm_head/" in p and "transpose(" in p for p in paths)


@pytest.mark.parametrize("path,scopes", [
    ("jit(step)/GPTForCausalLM/layer_3/attention/kv_gather/gather",
     ["kv_gather"]),
    ("jit(train_step)/fwd_bwd/transpose(jvp(loss))/mul",
     ["fwd_bwd", "loss"]),
    ("jit(train_step)/fwd_bwd/transpose(fwd_bwd)/jvp(BertForMaskedLM)/"
     "mlm_head/mlm_ln/reduce_sum", ["fwd_bwd", "fwd_bwd", "mlm_head"]),
    ("jit(train_step)/fwd_bwd/checkpoint/rematted_computation/jvp(loss)/exp",
     ["fwd_bwd", "loss"]),
    ("jit(optimizer)/jit(loss)/add", []),
    ("cache['layer_3']['attention']['cached_value']", []),
    ("jit(step)/sample/jit(take_along_axis)/gather", ["sample"]),
    ("", []),
])
def test_scopes_of_a_path(path, scopes):
    assert list(pt.scopes_of(path)) == scopes


# ------------------------------------- what has nothing to read says so

@pytest.mark.parametrize("which", ["host_only", "nothing", "no_trace"])
def test_every_reader_gives_none_without_a_tpu_plane(serve, which,
                                                     monkeypatch):
    planes = {"host_only": _host_only(serve), "nothing": [],
              "no_trace": None}[which]
    assert pt.gap_ms_p50(planes) is None
    assert pt.gap_ms_p50(planes, pt.DISPATCH_PHASES) is None
    assert pt.scope_pct(planes, ("optimizer",)) is None
    monkeypatch.setattr(pt, "of_run", lambda: planes)
    for name in NEW:
        assert harness.layer_metric_reader(name)(None) is None, name


def test_a_program_without_spans_and_scopes_gives_only_the_device_gap(
        serve, monkeypatch):
    bare = _bare(serve)
    monkeypatch.setattr(pt, "of_run", lambda: bare)
    values = {name: harness.layer_metric_reader(name)(None) for name in NEW}
    assert values["tick_device_gap_ms_p50"] \
        == pytest.approx(statistics.median(GAP_IDLE_NS) / 1e6)
    assert all(values[name] is None for name in NEW[1:]), values
    pt.report(bare)              # the notes do not need the spans either


def test_the_six_readers_read_the_run_s_own_trace(serve, train,
                                                  monkeypatch):
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"], name
    monkeypatch.setattr(pt, "of_run", lambda: serve)
    got = {name: harness.layer_metric_reader(name)(None) for name in NEW[:4]}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the parts never pass the whole
    assert got["tick_gap_dispatch_ms_p50"] + got["tick_gap_harvest_ms_p50"] \
        <= got["tick_device_gap_ms_p50"] * 1.02
    monkeypatch.setattr(pt, "of_run", lambda: train)
    for name in NEW[4:]:
        assert 0 < harness.layer_metric_reader(name)(None) < 100, name


# --------------------------------------------------- reading the file

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def test_op_names_come_from_the_event_metadata_s_tf_op_stat(tmp_path):
    """An XSpace written by hand in the wire format, as tsl's xplane.proto
    lays it out: the reader must find ``tf_op`` whether the value is a
    string or a reference to an interned one, skip planes that are no
    device, and step over fields it does not read (a double, a line)."""
    tf_op, interned = 26, 31
    stats = _field(5, _entry(tf_op, _field(1, tf_op) + _field(2, "tf_op"))) \
        + _field(5, _entry(24, _field(1, 24) + _field(2, "hlo_category"))) \
        + _field(5, _entry(interned, _field(1, interned) + _field(
            2, "jit(step)/sample/argmax:")))
    fusion = _field(1, 7) + _field(2, "%fusion.1 = f32[8]{0} fusion()") \
        + _field(5, _field(1, 24) + _field(5, "loop fusion")) \
        + _field(5, _field(1, tf_op) + _field(
            5, "jit(step)/layer_0/attention/kv_gather/gather:")) \
        + _field(5, _field(1, 27) + b"\x11" + b"\0" * 8)     # a double
    argmax = _field(1, 8) + _field(2, "%argmax.2 = s32[8]{0} fusion()") \
        + _field(5, _field(1, tf_op) + _field(7, interned))
    bare = _field(1, 9) + _field(2, "%copy.3 = f32[8]{0} copy()")
    line = _field(2, "XLA Ops") + _field(4, _field(1, 7) + _field(2, 5))
    device = _field(1, 1) + _field(2, "/device:TPU:0") + _field(3, line) \
        + _field(4, _entry(7, fusion)) + _field(4, _entry(8, argmax)) \
        + _field(4, _entry(9, bare)) + stats
    host = _field(2, "/host:CPU") + _field(4, _entry(7, fusion)) + stats
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert pt.op_names(str(path)) == {
        "%fusion.1 = f32[8]{0} fusion()":
            "jit(step)/layer_0/attention/kv_gather/gather",
        "%argmax.2 = s32[8]{0} fusion()": "jit(step)/sample/argmax"}


def test_a_trace_recorded_off_the_chip_loads_to_host_events_only(tmp_path):
    import jax
    import jax.numpy as jnp
    from benchmarks import trace as trace_lib
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("engine.tick", tick=0, live=1):
            jnp.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("something.else"):
            pass
    finally:
        jax.profiler.stop_trace()
    planes = pt.load(trace_lib.find_xplane(str(tmp_path)))
    assert [ev[0] for p in planes for line in p["lines"]
            for ev in line["events"]] == ["engine.tick"]
    assert pt.device_ops(planes) == [] and pt.scope_time(planes) is None
    assert pt.gap_ms_p50(planes) is None
    pt.report(planes)


# hand-checked on the recorded cut (ns; see the module docstring)
GAP_IDLE_NS = [7681072, 8505051]
GAP_DISPATCH_NS = [4067336, 5018689]
GAP_HARVEST_NS = [3435096, 3284722]
GAP_HARNESS_NS = [171730, 172130]
KV_RELAYOUT_PCT = 64.19877
OPTIMIZER_PCT = 5.49665
HEAD_LOSS_PCT = 19.48921
