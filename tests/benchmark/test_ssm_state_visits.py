"""``ssm_state_visits_over_advanced`` (ISSUE 37; CPU): the reader divides
the model's ``ssm_state_visits`` counter by ``ssm_slots_advanced``, both as
the runner's ``counted`` reduces them, and gives nothing for a program
without the counter (the parent of the PR that added it, or the scan's XLA
form); the ``BENCHMARK.json`` entry is found by its name."""

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

NAME = "ssm_state_visits_over_advanced"
CELL = "granite4h.shortchat_poisson"
reader = importlib.import_module("benchmarks.layer_metrics." + NAME)


def _run(facts):
    return SimpleNamespace(facts=facts)


def _counted(moved, visits):
    log = [(float(t + 1), {"ssm_slots_advanced": m, **(
        {} if v is None else {"ssm_state_visits": v})})
           for t, (m, v) in enumerate(zip(moved, visits))]
    ticks = [(float(t + 1), 0.5, 1, 1) for t in range(len(log))]
    return serve_blocked.counted(log, ticks)


# two ticks of two Mamba layers and four slots, and one with nothing live
# (all zeros: no part of either mean)
MOVED = [np.array([[1, 0, 1, 1], [1, 0, 1, 1]]),
         np.array([[0, 0, 1, 0], [0, 0, 1, 0]]),
         np.zeros((2, 4), int)]


@pytest.mark.parametrize("visits,want", [
    (MOVED, 1.0),                                    # the kernel's walk
    ([np.ones((2, 4), int)] * 2 + [MOVED[2]], 2.0),  # every slot's state
    ([MOVED[0], np.array([[0, 1, 1, 0], [0, 0, 1, 0]]), MOVED[2]], 1.125),
], ids=["the_advanced_alone", "every_slot", "one_more"])
def test_reader_is_the_visits_over_the_states_that_advanced(visits, want):
    counted = _counted(MOVED, visits)
    assert counted["ssm_slots_advanced"]["routed"] == pytest.approx(2.0)
    assert reader.compute(_run({"counted": counted})) == pytest.approx(want)


@pytest.mark.parametrize("facts", [
    {}, {"counted": {}}, {"counted": None},
    {"counted": _counted(MOVED, [None] * 3)},
    {"counted": {"ssm_state_visits": {"routed": 3.0}}},
    {"counted": {"ssm_state_visits": {"routed": 3.0},
                 "ssm_slots_advanced": {"routed": 0.0}}}],
    ids=["no_facts", "nothing_counted", "none", "the_xla_form_or_the_parent",
         "the_visits_alone", "nothing_advanced"])
def test_reader_gives_nothing_without_both_counters(facts):
    assert reader.compute(_run(facts)) is None


def test_entry_is_found_by_name_and_names_the_cell_and_what_it_moves():
    spec = harness.benchmark_spec()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "ratio", "better": "lower",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "tpot_ms_p50", "workloads": [CELL]}
    assert harness.layer_metric_reader(NAME) is not None
    assert CELL in {w["name"] for w in spec["workloads"]}
    assert "tpot_ms_p50" in {m["name"] for m in spec["end_to_end"]
                             if CELL in m.get("workloads", [CELL])}
    # beside the metrics of the same scan, which read the same counter
    assert all(CELL in m["workloads"] for m in spec["per_layer"]
               if m["name"] in ("ssm_scan_time_pct", "ssm_scan_roofline_pct"))
