"""The judged reading of the serving cells, `tpot_ms_p50`, on synthetic
stamps (CPU; listed in BENCHMARK.json ``paths``): since PR 30 the median
over every gap between two consecutive tokens of one request, where it was
the median of the requests' means, which one stalled tick shifts for every
request then alive.  Ticks and stamps are made here as ``runners/serve.drive``
and the engine make them: a tick is (end, duration, ...) on the harness's
clock, the engine's stamp of a token lies inside the tick that delivers it.
"""

import os
import re
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.runners import serve  # noqa: E402

TICK = 0.046          # the long-context cell's tick, s
LOOP = 0.0002         # the harness's loop between two ticks
STAMP = 0.00005       # the engine stamps this long before its tick ends


def make_ticks(n, stalls=None, seed=0):
    """``n`` ticks of about TICK, ``stalls`` {index: duration} among them."""
    rng = np.random.default_rng(seed)
    durations = TICK * (1 + 0.002 * rng.standard_normal(n))
    for i, d in (stalls or {}).items():
        durations[i] = d
    ends = np.cumsum(durations + LOOP)
    return [(float(e), float(d), 30, 0.0, 0) for e, d in zip(ends, durations)]


def completion(ticks, first, n, ticks_spanned=None):
    """A finished request of ``n`` tokens whose first came in tick ``first``
    and whose last ``ticks_spanned`` (n - 1 unless told) ticks later."""
    last = first + (n - 1 if ticks_spanned is None else ticks_spanned)
    return SimpleNamespace(status="ok", tokens=[0] * n,
                           t_first_token=ticks[first][0] - STAMP,
                           t_finish=ticks[last][0] - STAMP)


def p50(values):
    return harness.quantile(list(values), 50)


def window(stalls, requests=64, tokens=256, every=16):
    """64 requests of 256 tokens, one starting every 16 ticks."""
    ticks = make_ticks(5 + every * requests + tokens + 5, stalls)
    firsts = [5 + every * r for r in range(requests)]
    reqs = [(0.0, tokens, completion(ticks, f, tokens)) for f in firsts]
    held = sum(any(f < k <= f + tokens - 1 for k in (stalls or {}))
               for f in firsts)
    return ticks, reqs, held


# (a) one 1.5 s tick and two of 175 ms in a 40 s window of 46 ms ticks
STALLS = {300: 0.175, 400: 0.175, 800: 1.5}


def test_a_stalled_tick_moves_the_requests_median_and_not_the_gaps():
    calm_ticks, calm_reqs, none_held = window(None)
    ticks, reqs, held = window(STALLS)
    assert none_held == 0 and 32 < held < 45     # a little over half of 64
    calm = serve.time_per_token(calm_ticks, calm_reqs, calm_ticks[-1][0])
    got = serve.time_per_token(ticks, reqs, ticks[-1][0])
    assert got["matched"] == calm["matched"] == 64
    assert got["stalled"] == held and calm["stalled"] == 0
    assert got["gaps"].size == calm["gaps"].size == 64 * 255
    by_request = p50(got["per_request"]) / p50(calm["per_request"]) - 1
    by_gap = p50(got["gaps"]) / p50(calm["gaps"]) - 1
    assert by_request > 0.01                      # what the driver read
    assert abs(by_gap) < 0.001
    assert p50(got["gaps"]) == pytest.approx((TICK + LOOP) * 1e3, rel=2e-3)
    # the tail still sees the stall: tpot_ms_p95 reads the requests' means
    assert harness.quantile(got["per_request"], 95) \
        > 1.1 * p50(got["per_request"])


# (b) a request's gaps are whole tick-to-tick intervals and sum to its span
@pytest.mark.parametrize("first,n", [(0, 2), (7, 64), (300, 256), (795, 10)])
def test_a_requests_gaps_sum_to_finish_less_first_token(first, n):
    ticks = make_ticks(1100, STALLS)
    ends = np.array([t[0] for t in ticks])
    starts = ends - np.array([t[1] for t in ticks])
    c = completion(ticks, first, n)
    i0, i1 = serve.request_ticks(starts, ends, c.t_first_token, c.t_finish,
                                 n, TICK / 2)
    assert (i0, i1) == (first, first + n - 1)
    got = serve.time_per_token(ticks, [(0.0, n, c)], ends[-1])
    assert got["gaps"].size == n - 1 and got["matched"] == 1
    assert got["gaps"].sum() / 1e3 == pytest.approx(
        c.t_finish - c.t_first_token, abs=1e-9)
    assert np.allclose(got["gaps"], np.diff(ends[first:first + n]) * 1e3)
    assert got["stamp_to_end_ms"] == pytest.approx(STAMP * 1e3, rel=1e-6)
    assert got["per_request"] == [pytest.approx(got["gaps"].mean())]


def test_a_stamp_belongs_to_the_tick_that_holds_it_or_to_none():
    ticks = make_ticks(50)
    ends = np.array([t[0] for t in ticks])
    starts = ends - np.array([t[1] for t in ticks])
    find = lambda a, b, n: serve.request_ticks(starts, ends, a, b, n, 1.0)
    # stamped at the tick's very start, in its middle and at its very end
    for at in (starts[10], ends[10] - TICK / 2, ends[10]):
        assert find(at, ends[20] - STAMP, 11) == (10, 20)
    # between two ticks (the harness's loop) or after the last: no tick
    assert find(ends[10] + LOOP / 2, ends[20] - STAMP, 11) is None
    assert find(ends[10] - STAMP, ends[-1] + 1.0, 40) is None
    # the gaps must sum to the span: stamps the ticks' ends do not bear
    # out (here the first lies a whole tick before its tick's end, the
    # last at it) give no gaps of their own
    assert serve.request_ticks(starts, ends, starts[10], ends[20], 11,
                               TICK / 2) is None


# (c) ticks that do not match the tokens one for one: n - 1 times the mean
@pytest.mark.parametrize("n,spanned,why", [
    (100, 140, "preempted or migrated: more ticks than tokens"),
    (100, 50, "several tokens a tick"),
    (100, 98, "one token short of a tick each")])
def test_a_request_whose_ticks_do_not_match_counts_with_its_mean(
        n, spanned, why):
    ticks = make_ticks(400, {120: 0.175})
    odd = completion(ticks, 90, n, ticks_spanned=spanned)
    even = completion(ticks, 20, 30)
    got = serve.time_per_token(ticks, [(0.0, n, odd), (0.0, 30, even)],
                               ticks[-1][0])
    mean = (odd.t_finish - odd.t_first_token) / (n - 1) * 1e3
    assert got["matched"] == 1 and got["gaps"].size == n - 1 + 29
    assert np.allclose(got["gaps"][:n - 1], mean)
    assert got["per_request"][0] == pytest.approx(mean)
    one = completion(ticks, 5, 1)                 # one token: no gap at all
    assert serve.time_per_token(ticks, [(0.0, 1, one)],
                                ticks[-1][0])["gaps"].size == 0


# (d) failed or unfinished: asked - 1 gaps of the drain's end less the due
@pytest.mark.parametrize("failed,lifted", [(0, False), (1, False),
                                           (3, False), (4, True),
                                           (12, True)])
def test_failed_requests_count_by_what_they_asked_for(failed, lifted):
    ticks = make_ticks(700)
    t_end = ticks[-1][0]
    reqs = [(0.0, 64, completion(ticks, 10 + 20 * r, 64))
            for r in range(12 - failed)]
    reqs += [(1.0 + r, 128, None) for r in range(failed)]
    got = serve.time_per_token(ticks, reqs, t_end)
    assert got["gaps"].size == (12 - failed) * 63 + failed * 127
    assert len(got["per_request"]) == 12          # the old reading: once
    late = [(t_end - 1.0 - r) * 1e3 for r in range(failed)]
    assert sorted(got["per_request"])[12 - failed:] \
        == pytest.approx(sorted(late))
    assert sorted(set(got["gaps"][(12 - failed) * 63:])) \
        == pytest.approx(sorted(late))
    # 4 x 127 failed gaps against 8 x 63 sound ones are over half
    assert (p50(got["gaps"]) > 1e3) is lifted
    if failed < 12:
        assert got["matched"] == 12 - failed and got["stalled"] == 0


def test_no_request_gives_no_reading():
    got = serve.time_per_token(make_ticks(10), [], 1.0)
    assert got["gaps"].size == 0 and got["per_request"] == []
    assert serve.time_per_token([], [], 1.0)["gaps"].size == 0


# (e) the cells' lines and the tail's reader
@pytest.mark.parametrize("cell", ["gpt1.chat_poisson",
                                  "xing4.longctx_poisson"])
def test_the_rehearsal_line_carries_the_gaps_median(cell, monkeypatch):
    import test_benchmark
    import test_xing4_cell
    notes = []
    monkeypatch.setattr(harness, "note", notes.append)
    if cell == test_xing4_cell.CELL:
        line, _ = test_xing4_cell.rehearse(trace=0, seed=2**31 + 30)
    else:
        line, _ = test_benchmark.rehearse(cell, trace=0, seed=2**31 + 30)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}
    said = [re.search(r"median of (\d+) gaps (\S+) \(judged\), median of "
                      r"(\d+) requests' means (\S+); (\d+) requests' ticks "
                      r"match", n) for n in notes]
    said = [m for m in said if m]
    assert len(said) == 1
    gaps, by_gap, requests, by_request, matched = said[0].groups()
    assert line["metrics"]["tpot_ms_p50"]["value"] \
        == pytest.approx(float(by_gap), abs=1e-4)
    assert float(by_request) > 0 and int(gaps) >= int(requests) > 0
    # nothing preempts, migrates or speculates here: every request that got
    # two tokens or more gives gaps of its own
    assert int(matched) == int(requests) == line["attempted"]


def test_the_tail_reads_the_requests_means():
    reader = harness.layer_metric_reader("tpot_ms_p95")
    means = [float(v) for v in range(1, 101)]
    run = SimpleNamespace(facts={"tpot_ms": means, "token_gaps": 12345})
    assert reader(run) == 95.0
    assert reader(SimpleNamespace(facts={"token_gaps": 3})) is None
    doc = reader.__globals__["__doc__"]
    assert "per request" in doc and "per gap" in doc
