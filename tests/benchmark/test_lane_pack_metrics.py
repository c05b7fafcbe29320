"""The two per-layer metrics lane packing brought to
``granite4h.shortchat_poisson``: their entries, what the readers read from a
rehearsal at tiny widths on the CPU, and that they give nothing on a run
whose program has neither the counter nor the scope (the parent's).
"""

import io
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

SPEC = harness.benchmark_spec()
CELL = "granite4h.shortchat_poisson"
NEW = {"tick_rows_live_pct": ("program_counter", "serve engine", "higher"),
       "lane_pack_time_pct": ("device_trace", "model", "lower")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_names_the_cell_and_the_metric_it_moves(name):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    source, layer, better = NEW[name]
    assert entry == {"name": name, "unit": "%", "better": better,
                     "source": source, "layer": layer,
                     "moves": "tpot_ms_p50", "workloads": [CELL]}
    assert harness.layer_metric_reader(name) is not None


@pytest.fixture(scope="module")
def traced():
    import test_granite_cell as cell
    args = SimpleNamespace(workload=CELL, seed=2**31 + 35, seconds=1.5,
                           trace=1)
    notes = io.StringIO()
    stderr, sys.stderr = sys.stderr, notes
    try:
        line = bench_run.run_cell(args, rehearsal=True,
                                  overrides=cell.OVERRIDES,
                                  out=io.StringIO())
    finally:
        sys.stderr = stderr
    return line, notes.getvalue()


def test_rehearsal_reads_the_rows_and_notes_the_chunks_left_waiting(traced):
    """4 slots of 8 lanes: 12 packed rows (every slot's lane 0 and one
    chunk), so the live share of the rows is 32 / 12 of the lanes'."""
    line, notes = traced
    assert line["correct"] is True and line["failed"] == 0
    rows = line["metrics"]["tick_rows_live_pct"]
    lanes = line["metrics"]["tick_lanes_live_pct"]
    assert rows["unit"] == "%" and 0 < rows["value"] <= 100
    assert rows["value"] == pytest.approx(lanes["value"] * 32 / 12)
    assert "rows of the tick's token-wise products: 12, " in notes
    assert "prefill chunks left waiting for rows: " in notes
    # the device-trace reader needs a device's trace; where it read
    # something it read a share
    if "lane_pack_time_pct" in line["metrics"]:
        assert 0 < line["metrics"]["lane_pack_time_pct"]["value"] < 100


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_without_its_counter_or_scope(name,
                                                            monkeypatch):
    """The parent's program sows `lanes_live` and no `rows_dense`, and has
    no `lane_pack` scope; the other cells' have neither."""
    from benchmarks import program_trace
    reader = harness.layer_metric_reader(name)
    recorded = harness.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "recorded_program_trace.json"))
    cfg, trf = harness.cell_files(harness.find_cell(SPEC, CELL))
    for got in (*recorded.values(), None):
        monkeypatch.setattr(program_trace, "of_run", lambda got=got: got)
        for facts in ({}, {"counted": {}}, {"counted": {"lanes_live": {
                "ticks": 3, "max_over_mean": 2.0, "touched": 40.0,
                "routed": 111.0}}}):
            run = SimpleNamespace(
                cell={"name": CELL}, config=cfg, traffic=trf, end_to_end={},
                facts=facts, trace=None, spans={},
                peaks=harness.device_peaks("TPU v5 lite"))
            assert reader(run) is None


def test_rows_reader_divides_lanes_by_rows_and_counts_the_waiting(capsys):
    reader = harness.layer_metric_reader("tick_rows_live_pct")
    hit = lambda ticks, routed: {"ticks": ticks, "max_over_mean": 1.0,
                                 "touched": 1.0, "routed": routed}
    run = SimpleNamespace(facts={"counted": {
        "lanes_live": hit(600, 96.0), "rows_dense": hit(600, 256.0),
        "prefill_chunks_deferred": hit(40, 1.5)}})
    assert reader(run) == pytest.approx(37.5)
    assert "waiting for rows: 60 in 40 of 600 ticks" in capsys.readouterr().err
    del run.facts["counted"]["prefill_chunks_deferred"]
    assert reader(run) == pytest.approx(37.5)
    assert "waiting for rows: none in 600 ticks" in capsys.readouterr().err
