"""The trinity_mini cell of the benchmark (CPU; listed in BENCHMARK.json
``paths``): its configuration equals the catalog's ``config`` key for key
outside ``reduced``, its traffic file holds ISSUE 40's parameters, a
rehearsal at tiny widths prints the contract's line with the cell's
per-layer metrics, a timed path broken underneath and the fp8 control come
out as not correct against the cell's own limits file, ``roofline_gqa``
matches hand counts at two sizes, and each new reader reads a synthetic run
and gives nothing (and does not raise) without its counter.  Entries of
BENCHMARK.json are looked up by name and membership, never by position."""

import io
import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, roofline_gqa  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

SPEC = harness.benchmark_spec()
CELL = "trinity_mini.mixedctx_poisson"
NEW = ("paged_gqa_attention_time_pct", "paged_gqa_attention_roofline_pct",
       "window_kv_held_pct")
JOINED = ("tpot_ms_p50", "kv_pool_live_pct", "decode_step_roofline_pct",
          "tick_device_gap_ms_p50", "tick_gap_dispatch_ms_p50",
          "tick_gap_harvest_ms_p50", "tick_gap_build_ms_p50",
          "tick_gap_rng_ms_p50", "tick_gap_put_ms_p50",
          "tick_gap_fetch_ms_p50", "tick_handoffs_p50",
          "kv_relayout_time_pct", "expert_ffn_time_pct",
          "expert_ffn_roofline_pct", "expert_load_max_over_mean",
          "expert_weight_visits_over_touched", "attn_walked_pct",
          "tick_lanes_live_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
W, F = "sliding_attention", "full_attention"

KINDS = [W, W, W, W, F]
SIZES = dict(vocab_size=512, hidden_size=64, num_dense_layers=1, num_heads=4,
             num_kv_heads=2, head_dim=16, intermediate_size=128,
             moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
             route_scale=2.826, sliding_window=8, layer_types=KINDS,
             rms_norm_eps=1e-5, rope_theta=10000)
OVERRIDES = {
    "config": {"model": {"kwargs": dict(SIZES, num_layers=5,
                                        max_position=4096, dtype="float32",
                                        param_dtype="float32")},
               "reference_cfg": dict(SIZES, block=16), "reference_block": 16,
               "expert_layer": {"layers": 4, "num_experts": 8,
                                "num_experts_per_tok": 2, "hidden_size": 64,
                                "moe_intermediate_size": 32},
               "attention_layer": {"layers": 5, "window_layers": 4,
                                   "sliding_window": 8, "num_heads": 4,
                                   "num_kv_heads": 2, "head_dim": 16}},
    "traffic": {"engine": {"slots": 4, "max_len": 64, "block_size": 4},
                "ramp_s": 0.5, "drain_grace_s": 60, "check_requests": 12,
                "trace_seconds": 1,
                "mix": {"rate_per_s": 6,
                        "prompt_tokens": {"median": 14, "sigma": 0.6,
                                          "min": 4, "max": 36},
                        "output_tokens": {"median": 14, "sigma": 0.3,
                                          "min": 8, "max": 20}}}}


def _by_name(entries):
    return {e["name"]: e for e in entries}


def _files():
    cell = harness.find_cell(SPEC, CELL)
    cfg, trf = harness.cell_files(cell)
    for target, patch in OVERRIDES.items():
        bench_run._merge({"config": cfg, "traffic": trf}[target], patch)
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            CELL + ".json"))
    return cell, cfg, trf, limits


def rehearse(trace=0, break_step=None, seed=2**31 + 91, seconds=1.5):
    args = SimpleNamespace(workload=CELL, seed=seed, seconds=seconds,
                           trace=trace)
    out = io.StringIO()
    line = bench_run.run_cell(args, rehearsal=True, overrides=OVERRIDES,
                              break_step=break_step, out=out)
    return line, out.getvalue()


def test_the_configuration_is_the_catalogs_outside_reduced():
    cell = harness.find_cell(SPEC, CELL)
    cfg, _ = harness.cell_files(cell)
    entry = _by_name(SPEC["configs"])[cell["config"]]
    assert cell["chips"] == 1 and cell["config"] == "trinity_mini"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert cfg["source"] == entry["source"]
    assert entry["file"] == "benchmarks/configs/trinity_mini.json"
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "Trinity-Mini")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["layer_types"]) == (5, 1, KINDS)
    assert cfg["published"]["num_hidden_layers"] == 32 \
        and cfg["published"]["num_dense_layers"] == 2 \
        and cfg["published"]["layer_types"] == [W, W, W, F] * 8
    # published layers 4-7 are one whole period at the published 3 : 1
    assert cfg["published"]["layer_types"][4:8] == KINDS[1:]
    for key in ("deployment", "assumed", "departures", "precision"):
        assert cfg[key], key
    for key in ("output_gate", "head_norms", "positions", "window_edge",
                "sandwich_norm", "mup", "router"):
        assert key in cfg["assumed"], key
    # the program's model and the reference hold the published widths
    kw, rcfg = cfg["model"]["kwargs"], cfg["reference_cfg"]
    model = harness.resolve(cfg["model"]["builder"])(**kw)
    assert list(model.layer_kinds()) == rcfg["layer_types"] \
        == cfg["layer_types"]
    for pub, mine in dict(
            hidden_size="hidden_size", num_attention_heads="num_heads",
            num_key_value_heads="num_kv_heads", head_dim="head_dim",
            vocab_size="vocab_size", intermediate_size="intermediate_size",
            moe_intermediate_size="moe_intermediate_size",
            num_experts="num_experts",
            num_experts_per_tok="num_experts_per_tok",
            route_scale="route_scale", sliding_window="sliding_window",
            num_dense_layers="num_dense_layers",
            rms_norm_eps="rms_norm_eps", rope_theta="rope_theta").items():
        assert getattr(model, mine) == rcfg[mine] == cfg[pub], pub
    assert model.num_layers == cfg["num_hidden_layers"]
    assert model.max_position == cfg["max_position_embeddings"]
    assert model.mup_enabled is cfg["mup_enabled"] is True
    # the issue's count of parameters, and the bytes the roofline reads
    p = cfg["parameters"]
    assert p["attention"] == 27263232 and p["one_expert"] == 6291456
    assert p["dense_layer"] == 65020160
    assert p["expert_layer_whole"] == 839131520
    assert p["held"] == p["dense_layer"] + 4 * p["expert_layer_whole"] \
        + p["embedding_and_head_and_final_norm"] == 4241534720
    assert cfg["serving_bytes"]["weight_bytes"] == 2 * p["held"]
    assert cfg["serving_bytes"]["kv_bytes_per_token"] == 2 * 4 * 128 * 2
    assert cfg["runner"] == "serve_blocked" and cfg["reference_block"] == 512


def test_the_traffic_is_the_issues():
    cell = harness.find_cell(SPEC, CELL)
    _, trf = harness.cell_files(cell)
    assert cell["traffic"] == "mixedctx_poisson"
    assert trf["engine"] == {"slots": 64, "max_len": 16384, "block_size": 16}
    mix = trf["mix"]
    assert mix["arrivals"] == "poisson"
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 1.1,
                                    "min": 128, "max": 12288}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.5,
                                    "min": 64, "max": 768}
    # 0.8 of the swept knee, both numbers in the file and in the cell's why
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert f"{mix['rate_per_s']:g} req/s" in cell["why"]
    assert (trf["check_requests"], trf["trace_seconds"]) == (8, 3)
    # the longest request fits a slot; half the queue never leaves the window
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= trf["engine"]["max_len"]
    from benchmarks import loadgen
    prompts = loadgen._lognormal_quantiles(600, 2048, 1.1, 128, 12288)
    assert 0.45 < (prompts <= 2048).mean() < 0.55
    assert 0.12 < (prompts > 6144).mean() < 0.2


def test_every_entry_names_the_cell():
    metrics = _by_name(SPEC["end_to_end"] + SPEC["per_layer"])
    for name in NEW:
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p50"
        assert harness.layer_metric_reader(name) is not None
    assert metrics["paged_gqa_attention_roofline_pct"]["unit"] == "%"
    for name in JOINED:
        assert CELL in metrics[name]["workloads"], name
    assert len(harness.find_cell(SPEC, CELL)["why"]) <= 200


def test_roofline_counts_match_hand_counts_at_two_sizes():
    small = dict(num_heads=4, num_kv_heads=2, head_dim=3)
    assert roofline_gqa.attention_flops(walked=10, num_heads=4,
                                        head_dim=3) == 4 * 4 * 3 * 10
    assert roofline_gqa.attention_bytes(
        walked=10, lanes=7, kv_itemsize=2, activation_itemsize=4,
        **small) == 10 * 2 * 2 * 3 * 2 + 7 * 2 * 4 * 3 * 4
    shape = harness.cell_files(harness.find_cell(SPEC, CELL))[0][
        "attention_layer"]
    peaks = harness.device_peaks("TPU v5 lite")
    # the cell's widths: 100,000 positions of 2,048 B and 200 lanes of
    # 2 x 8,192 B: 208 MB, 0.25 ms; 1.6 G operations are 8 us of the MXU
    assert roofline_gqa.attention_bytes(
        walked=1e5, lanes=200, num_heads=32, num_kv_heads=4, head_dim=128,
        kv_itemsize=2, activation_itemsize=2) == 1e5 * 2048 + 200 * 16384
    got = roofline_gqa.attention_seconds(shape, 1e5, 200, peaks)
    assert got["bound"] == "bytes" and 2.5e-4 < got["seconds"] < 2.6e-4
    assert roofline_gqa.attention_seconds(shape, 0, 0, peaks)["seconds"] == 0


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The traced rehearsal, its profile kept in a directory of its own:
    ``harness.trace_dir`` is one fixed directory of the checkout that
    every traced run empties first, and the other cells' tests run their
    traced rehearsals beside this one (a CPU trace holds no device plane,
    so no reader of this line needs it)."""
    private = str(tmp_path_factory.mktemp("bench_trace"))
    real, harness.trace_dir = harness.trace_dir, lambda: private
    try:
        return rehearse(trace=1)
    finally:
        harness.trace_dir = real


def test_rehearsal_prints_the_per_layer_line(traced):
    line, text = traced
    assert json.loads(json.dumps(line)) == line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    got = line["metrics"]
    assert {"tick_ms_p50", "kv_pool_live_pct", "slot_occupancy_pct",
            "expert_load_max_over_mean", "attn_walked_pct",
            "window_kv_held_pct", "tick_lanes_live_pct"} <= set(got)
    for name in NEW:
        if name in got:
            assert 0 < got[name]["value"] <= 100, name
    # requests of up to 56 tokens against a window of 8: blocks were freed
    assert got["window_kv_held_pct"]["value"] < 90
    # four of five layers walk a window only: under the pool's live share
    assert got["attn_walked_pct"]["value"] \
        < got["kv_pool_live_pct"]["value"]
    for name in ("served_off_first_share", "served_logit_gap_mean",
                 "served_logit_gap_p90", "token_count_mismatch"):
        assert f"check {name}: " in text


def test_rehearsal_with_every_token_altered_is_not_correct():
    line, text = rehearse(break_step="alter_token")
    assert line["correct"] is False and "FAIL" in text
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}


def test_the_control_fails_the_cells_limits_through_the_runners_own_check():
    """The control goes through ``run`` itself: the same drive, the same
    sample, the same ``harness.Check`` against the cell's own limits file,
    with the reference at ``control.precision`` in the program's place; a
    sound run of the same seed passes them."""
    import jax
    from benchmarks.runners import serve_blocked
    cell, cfg, trf, limits = _files()
    assert cfg["control"] == dict(cfg["control"], kind="reference",
                                  precision="fp8")
    args = SimpleNamespace(seed=5, seconds=1.5, trace=0)
    checks = {}
    for served_by in (None, cfg["control"]["precision"]):
        res = serve_blocked.run(
            cell, cfg, trf, limits, args, jax.devices()[:1],
            time.perf_counter(), harness.Spans(), harness.CompileCounter(),
            served_by=served_by)
        assert res["failed"] == 0
        checks[served_by] = res["check"]
    assert checks[None].ok
    control = checks["fp8"]
    assert not control.ok
    assert {r["name"] for r in control.rows if not r["ok"]} \
        <= set(limits) and [r for r in control.rows if not r["ok"]]


def _synthetic(counted, trace=True):
    cfg, trf = harness.cell_files(harness.find_cell(SPEC, CELL))
    return SimpleNamespace(
        cell={"name": CELL}, config=cfg, traffic=trf, end_to_end={},
        facts={"counted": counted, "ticks": [(1.0, 0.1, 2, 40.0, 7)],
               "pool_tokens": 1 << 20},
        trace={"main_module_runs": 4, "busy_s": 1.0, "window_s": 2.0}
        if trace else None, spans={},
        peaks=harness.device_peaks("TPU v5 lite"))


def _stat(routed):
    return {"ticks": 3, "max_over_mean": 1.0, "touched": 1.0,
            "routed": routed}


def test_window_kv_held_pct_on_a_synthetic_run(capsys):
    reader = harness.layer_metric_reader("window_kv_held_pct")
    run = _synthetic({"window_tokens_held": _stat(50000.0),
                      "full_tokens_held": _stat(200000.0),
                      "window_blocks_released": dict(_stat(4.0), ticks=2)})
    assert reader(run) == pytest.approx(25.0)
    assert "handed back while their requests ran: 8 in 2 of 3 ticks" \
        in capsys.readouterr().err
    # nothing released: 100
    run = _synthetic({"window_tokens_held": _stat(7.0),
                      "full_tokens_held": _stat(7.0)})
    assert reader(run) == pytest.approx(100.0)
    assert "ran: none in 3 ticks" in capsys.readouterr().err
    for counted in ({}, {"window_tokens_held": _stat(5.0)},
                    {"full_tokens_held": _stat(5.0)}):
        assert reader(_synthetic(counted)) is None


def test_the_kernels_readers_on_a_synthetic_trace(monkeypatch):
    """A trace of two operations, one under the kernel's scope: 0.4 ms of 1
    ms busy over 4 ticks, 0.1 ms a tick; 5,000 positions and 10 lanes a
    layer need 12.7 us a layer, 5 layers 63.5 us a tick: 63.5%."""
    from benchmarks import program_trace, scope_time
    ops = [["fusion.1", 0, 400000,
            "jit(step)/layer_1/attn/paged_gqa_attention/jit(_paged_gqa_pallas)"
            "/paged_gqa_attention"],
           ["fusion.2", 500000, 600000, "jit(step)/layer_1/moe/moe_experts"]]
    monkeypatch.setattr(program_trace, "of_run", lambda: "planes")
    monkeypatch.setattr(program_trace, "device_ops", lambda planes: ops)
    scope_time._components.cache_clear()
    time_pct = harness.layer_metric_reader("paged_gqa_attention_time_pct")
    roof = harness.layer_metric_reader("paged_gqa_attention_roofline_pct")
    counted = {"attn_positions_walked": _stat(5e3),
               "lanes_live": _stat(10.0)}
    run = _synthetic(counted)
    assert time_pct(run) == pytest.approx(40.0)
    least = roofline_gqa.attention_seconds(
        run.config["attention_layer"], 5e3, 10.0, run.peaks)["seconds"]
    assert roof(run) == pytest.approx(100 * 5 * least / (4e-4 / 4))
    assert 63 < roof(run) < 64
    # without the counters, the configuration's shape or the trace: nothing
    assert roof(_synthetic({})) is None
    assert roof(_synthetic({"lanes_live": _stat(10.0)})) is None
    assert roof(_synthetic(counted, trace=False)) is None
    bare = _synthetic(counted)
    bare.config = {k: v for k, v in bare.config.items()
                   if k != "attention_layer"}
    assert roof(bare) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_nothing_on_a_run_without_its_scope_or_counter(
        name, monkeypatch):
    """The other cells' runs (and the parent's of this one): no
    `paged_gqa_attention` scope in the trace, no counter in the facts, no
    `attention_layer` in the configuration; with no trace at all likewise."""
    from benchmarks import program_trace
    reader = harness.layer_metric_reader(name)
    peaks = harness.device_peaks("TPU v5 lite")
    recorded = harness.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "recorded_program_trace.json"))
    other = harness.cell_files(harness.find_cell(SPEC,
                                                 "xing4.longctx_poisson"))
    for got in (*recorded.values(), None):
        monkeypatch.setattr(program_trace, "of_run", lambda got=got: got)
        for facts in ({}, {"counted": {"expert_load": {
                "ticks": 3, "max_over_mean": 2.0, "touched": 5.0,
                "routed": 64.0}}, "ticks": [(1.0, 0.1, 2, 40.0, 7)]}):
            run = SimpleNamespace(
                cell={"name": "xing4.longctx_poisson"}, config=other[0],
                traffic=other[1], end_to_end={}, facts=facts,
                trace={"main_module_runs": 3, "busy_s": 1.0,
                       "window_s": 2.0} if got else None,
                spans={}, peaks=peaks)
            assert reader(run) is None
