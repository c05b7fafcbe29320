"""``expert_weight_visits_over_touched`` (ISSUE 33; CPU): the reader divides
the model's ``expert_weight_visits`` counter by the touched experts of
``expert_load``, both as the runner's ``counted`` reduces them, gives
nothing for a program without the counter (the parent of the PR that added
it, or the XLA form of the grouped products), and in a traced rehearsal of
the cell at tiny widths reads 1.0 with the kernel (64 rows: one row tile)
and is left off the line for the XLA form."""

import importlib
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.runners import serve_blocked  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_xing4_cell import CELL, rehearse  # noqa: E402

NAME = "expert_weight_visits_over_touched"
reader = importlib.import_module("benchmarks.layer_metrics." + NAME)


def _run(facts):
    return SimpleNamespace(facts=facts)


def test_reader_is_the_visits_over_the_touched_experts():
    # two ticks, two expert layers, four experts; a tick with nothing live
    # (all zeros) is no part of either mean
    load = [np.array([[3, 0, 2, 1], [0, 0, 6, 0]]),
            np.array([[1, 1, 1, 1], [4, 0, 0, 4]]),
            np.zeros((2, 4), int)]
    visits = [np.array([[1, 0, 2, 1], [0, 0, 1, 0]]),   # one group straddles
              np.array([[1, 1, 1, 1], [1, 0, 0, 2]]),   # and one more
              np.zeros((2, 4), int)]
    log = [(float(t + 1), {"expert_load": a, "expert_weight_visits": v})
           for t, (a, v) in enumerate(zip(load, visits))]
    ticks = [(1.0, 0.5, 2, 40), (2.0, 0.5, 3, 60), (3.0, 0.5, 0, 0)]
    counted = serve_blocked.counted(log, ticks)
    assert counted["expert_load"]["touched"] == pytest.approx(10 / 4)
    assert counted["expert_weight_visits"]["routed"] == pytest.approx(12 / 4)
    assert reader.compute(_run({"counted": counted})) == pytest.approx(1.2)


@pytest.mark.parametrize("facts", [
    {}, {"counted": {}}, {"counted": None},
    {"counted": {"expert_load": {"routed": 3.0, "touched": 2.0}}},
    {"counted": {"expert_weight_visits": {"routed": 3.0}}}],
    ids=["no_facts", "nothing_counted", "none", "the_load_alone",
         "the_visits_alone"])
def test_reader_gives_nothing_without_both_counters(facts):
    assert reader.compute(_run(facts)) is None


def test_entry_names_the_cell_the_reader_and_the_metric_it_moves():
    spec = harness.benchmark_spec()
    entry = spec["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "ratio", "better": "lower",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "tpot_ms_p50", "workloads": [CELL]}
    assert harness.layer_metric_reader(NAME) is not None
    assert "tpot_ms_p50" in {m["name"] for m in spec["end_to_end"]
                             if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_traced_rehearsal_reads_one_visit_an_expert_or_nothing(
        form, step_traced_with):
    with step_traced_with(xla=form == "xla"):
        line, _ = rehearse(trace=1, seed=2**31 + 33)
    assert line["correct"] is True and line["failed"] == 0
    assert "expert_load_max_over_mean" in line["metrics"]
    if form == "xla":
        assert NAME not in line["metrics"]
    else:
        # 4 slots x 8 lanes x 2 experts = 64 rows, one row tile: every
        # touched expert is visited exactly once
        assert line["metrics"][NAME] == {"value": pytest.approx(1.0),
                                         "unit": "ratio"}
