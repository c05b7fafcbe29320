"""``attn_walked_pct`` (ISSUE 28; CPU): the reader takes the model's
``attn_positions_walked`` counter through the runner's ``counted`` as it
takes any counter, gives nothing for a program that has none (the parent of
the PR that added it), and in a traced rehearsal of the cell at tiny widths
reads a little over ``kv_pool_live_pct`` with the kernel and 100 for the
XLA form, which scores every position of every slot."""

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

reader = importlib.import_module("benchmarks.layer_metrics.attn_walked_pct")


def _run(facts):
    return SimpleNamespace(facts=facts)


def test_reader_is_the_walked_positions_over_the_pools_room():
    # two ticks, two layers, three slots; the runner's window covers both
    log = [(1.0, {"attn_positions_walked": np.array([[16, 0, 32],
                                                     [16, 0, 32]])}),
           (2.0, {"attn_positions_walked": np.array([[32, 8, 32],
                                                     [32, 8, 32]])})]
    ticks = [(1.0, 0.5, 2, 40), (2.0, 0.5, 3, 60)]
    counted = serve_blocked.counted(log, ticks)
    assert counted["attn_positions_walked"]["routed"] == 60.0   # a layer, tick
    assert reader.compute(_run({"counted": counted, "pool_tokens": 240})) \
        == pytest.approx(25.0)


@pytest.mark.parametrize("facts", [
    {}, {"counted": {}}, {"counted": None, "pool_tokens": 64},
    {"counted": {"expert_load": {"routed": 3.0}}, "pool_tokens": 64}],
    ids=["no_facts", "nothing_counted", "none", "another_counter_only"])
def test_reader_gives_nothing_without_the_counter(facts):
    assert reader.compute(_run(facts)) is None


def test_entry_names_the_cell_and_the_metric_it_moves():
    entry = {m["name"]: m for m in harness.benchmark_spec()["per_layer"]}[
        "attn_walked_pct"]
    assert entry == {"name": "attn_walked_pct", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "tpot_ms_p50",
                     "workloads": [CELL]}


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_traced_rehearsal_reads_the_live_share_or_the_whole_pool(
        form, step_traced_with):
    with step_traced_with(xla=form == "xla"):
        line, _ = rehearse(trace=1, seed=2**31 + 28)
    assert line["correct"] is True and line["failed"] == 0
    walked = line["metrics"]["attn_walked_pct"]["value"]
    live = line["metrics"]["kv_pool_live_pct"]["value"]
    if form == "xla":
        # every slot's whole row, whatever is live: the pool is at dense
        # capacity, so that is all of it in every tick that served a slot
        assert walked == pytest.approx(100.0)
    else:
        # live blocks alone: the live tokens, block rounding and the
        # tick's own lanes over them (walked is read before the tick's
        # finished requests leave, live after)
        assert 0.5 * live < walked < live + 25.0 and walked < 60.0
