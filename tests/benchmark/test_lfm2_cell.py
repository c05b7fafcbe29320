"""The lfm2 cell of the benchmark (CPU; listed in BENCHMARK.json ``paths``):
its configuration equals the catalog's ``config`` key for key outside
``reduced``, ISSUE 43's table of parameters by hand count, its traffic file
holds the issue's parameters, a rehearsal at tiny widths prints the
contract's line with both new per-layer metrics, a timed path broken
underneath and the fp8 control come out as not correct against the cell's
own limits file, and each new reader reads a synthetic run and gives
nothing (and does not raise) without its scope or counter.  Entries of
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

from benchmarks import harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

SPEC = harness.benchmark_spec()
CELL = "lfm2.ragextract_poisson"
NEW = ("short_conv_time_pct", "experts_touched_pct")
JOINED = ("tpot_ms_p50", "kv_pool_live_pct", "decode_step_roofline_pct",
          "tick_device_gap_ms_p50", "tick_gap_dispatch_ms_p50",
          "tick_gap_harvest_ms_p50", "tick_gap_build_ms_p50",
          "tick_gap_rng_ms_p50", "tick_gap_put_ms_p50",
          "tick_gap_fetch_ms_p50", "tick_handoffs_p50",
          "kv_relayout_time_pct", "expert_ffn_time_pct",
          "expert_ffn_roofline_pct", "expert_load_max_over_mean",
          "expert_weight_visits_over_touched", "attn_walked_pct",
          "tick_lanes_live_pct")
# lists that a standing test pins to one cell, or that read what this
# model has not (ISSUE 43 section 5)
NOT_JOINED = ("paged_gqa_attention_time_pct",
              "paged_gqa_attention_roofline_pct", "window_kv_held_pct",
              "ssm_mixer_time_pct", "latent_attention_time_pct",
              "held_expert_ffn_roofline_pct", "tick_rows_live_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
C, F = "conv", "full_attention"

KINDS = [C] + [F, C, C, C] * 3
TINY_KINDS = [C, F, C, C, C]
SIZES = dict(vocab_size=512, hidden_size=128, num_dense_layers=1,
             layer_types=TINY_KINDS, num_heads=4, num_kv_heads=2,
             head_dim=64, intermediate_size=256, moe_intermediate_size=128,
             num_experts=8, num_experts_per_tok=4, routed_scaling_factor=1,
             conv_L_cache=3, norm_eps=1e-5, rope_theta=1000000)
OVERRIDES = {
    "config": {"model": {"kwargs": dict(SIZES, num_layers=5,
                                        max_position=4096, dtype="float32",
                                        param_dtype="float32")},
               "reference_cfg": dict(SIZES, block=16), "reference_block": 16,
               "expert_layer": {"layers": 4, "num_experts": 8,
                                "num_experts_per_tok": 4, "hidden_size": 128,
                                "moe_intermediate_size": 128},
               "attention_layer": {"layers": 1, "num_heads": 4,
                                   "num_kv_heads": 2, "head_dim": 64}},
    "traffic": {"engine": {"slots": 4, "max_len": 64, "block_size": 4},
                "ramp_s": 0.5, "drain_grace_s": 60, "check_requests": 12,
                "trace_seconds": 1,
                "mix": {"rate_per_s": 6,
                        "prompt_tokens": {"median": 20, "sigma": 0.6,
                                          "min": 4, "max": 40},
                        "output_tokens": {"median": 8, "sigma": 0.3,
                                          "min": 4, "max": 16}}}}


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


def rehearse(trace=0, break_step=None, seed=2**31 + 43, seconds=1.5):
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
    assert cell["chips"] == 1 and cell["config"] == "lfm2_8b_a1b"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert cfg["source"] == entry["source"] == "https://huggingface.co/" \
        "LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert entry["file"] == "benchmarks/configs/lfm2_8b_a1b.json"
    assert 0 < len(entry["why"]) <= 200 and 0 < len(entry["source"]) <= 200
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["layer_types"]) == (13, 1, KINDS)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (24, 2)
    assert pub["layer_types"] == [C, C, F] + [C, C, C, F] * 4 \
        + [C, C, F, C, C]
    # published layer 0, then layers 2-13: three whole periods
    assert [pub["layer_types"][0]] + pub["layer_types"][2:14] == KINDS
    for key in ("deployment", "assumed", "departures", "precision"):
        assert cfg[key], key
    for key in ("tied_head", "in_proj_order", "conv", "positions",
                "head_norms", "router", "gate_sum_eps"):
        assert key in cfg["assumed"], key
    ref, _ = harness.load_reference(cfg["reference"])
    assert ref.ASSUMED["gate_sum_eps"] == cfg["assumed"]["gate_sum_eps"]
    # the program's model and the reference hold the published widths
    kw, rcfg = cfg["model"]["kwargs"], cfg["reference_cfg"]
    model = harness.resolve(cfg["model"]["builder"])(**kw)
    assert list(model.layer_kinds()) == rcfg["layer_types"] \
        == cfg["layer_types"]
    for pub_key, mine in dict(
            hidden_size="hidden_size", num_attention_heads="num_heads",
            num_key_value_heads="num_kv_heads", vocab_size="vocab_size",
            intermediate_size="intermediate_size",
            moe_intermediate_size="moe_intermediate_size",
            num_experts="num_experts",
            num_experts_per_tok="num_experts_per_tok",
            routed_scaling_factor="routed_scaling_factor",
            conv_L_cache="conv_L_cache",
            num_dense_layers="num_dense_layers", norm_eps="norm_eps",
            rope_theta="rope_theta").items():
        assert getattr(model, mine) == rcfg[mine] == cfg[pub_key], pub_key
    assert model.head_dim == rcfg["head_dim"] \
        == cfg["hidden_size"] // cfg["num_attention_heads"] == 64
    assert model.num_layers == cfg["num_hidden_layers"]
    assert model.max_position == cfg["max_position_embeddings"]
    assert cfg["conv_bias"] is False and cfg["use_expert_bias"] is True
    assert cfg["runner"] == "serve_blocked" and cfg["reference_block"] == 512
    assert cfg["control"] == dict(cfg["control"], kind="reference",
                                  precision="fp8")


def test_the_issues_table_of_parameters_by_hand_count():
    cfg, _ = harness.cell_files(harness.find_cell(SPEC, CELL))
    p, d = cfg["parameters"], 2048
    assert p["conv_mixer"] == d * 6144 + d * d + 3 * d == 16783360
    assert p["attention_mixer"] == 2 * d * 2048 + 2 * d * 512 + 2 * 64 \
        == 10485888
    assert p["one_expert"] == 3 * d * 1792 == 11010048
    assert p["expert_ffn"] == 32 * p["one_expert"] + d * 32 + 32 == 352387104
    assert p["expert_layer_conv"] == 369174560
    assert p["expert_layer_attention"] == 362877088
    assert p["dense_layer_conv"] == p["conv_mixer"] + 3 * d * 7168 + 2 * d \
        == 60827648
    assert p["embedding_and_final_norm"] == 65536 * d + d == 134219776
    assert p["held"] == p["dense_layer_conv"] \
        + 3 * p["expert_layer_attention"] + 9 * p["expert_layer_conv"] \
        + p["embedding_and_final_norm"] == 4606249728
    sb = cfg["serving_bytes"]
    assert sb["weight_bytes"] == 2 * p["held"] == 9212499456
    assert sb["kv_bytes_per_token"] == 3 * 2 * 8 * 64 * 2 == 6144
    # the kept rows: named as not counted
    conv = cfg["conv_layer"]
    assert conv["layers"] * (conv["conv_L_cache"] - 1) \
        * conv["hidden_size"] * conv["rows_itemsize"] == 81920
    assert "81,920 B a slot" in sb["why"]
    assert cfg["expert_layer"]["layers"] == 12 \
        and cfg["attention_layer"]["layers"] == 3


def test_the_traffic_is_the_issues():
    cell = harness.find_cell(SPEC, CELL)
    _, trf = harness.cell_files(cell)
    assert cell["traffic"] == "ragextract_poisson"
    assert trf["engine"] == {"slots": 64, "max_len": 8192, "block_size": 16}
    mix = trf["mix"]
    assert mix["arrivals"] == "poisson"
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 0.6,
                                    "min": 256, "max": 6144}
    assert mix["output_tokens"] == {"median": 96, "sigma": 0.6,
                                    "min": 16, "max": 384}
    # 0.8 (or, by the issue's rule, 0.7) of the swept knee, both numbers in
    # the file and the rate in the cell's why
    share = mix["rate_per_s"] / mix["knee_per_s"]
    assert share == pytest.approx(0.8) or share == pytest.approx(0.7)
    assert f"{mix['rate_per_s']:g} req/s" in cell["why"]
    assert f"{mix['rate_per_s']:g} req/s" in trf["why"] \
        and f"{mix['knee_per_s']:g}" in trf["why"]
    assert (trf["ramp_s"], trf["drain_grace_s"], trf["check_requests"],
            trf["trace_seconds"]) == (20, 60, 8, 3)
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= trf["engine"]["max_len"]
    # prefill-heavy: a request spends more ticks absorbing its prompt, 16
    # lanes a chunk, than decoding
    from benchmarks import loadgen
    prompts = loadgen._lognormal_quantiles(600, 2048, 0.6, 256, 6144)
    outputs = loadgen._lognormal_quantiles(600, 96, 0.6, 16, 384)
    assert (prompts / 16).mean() > outputs.mean() > 100


def test_every_entry_names_the_cell():
    metrics = _by_name(SPEC["end_to_end"] + SPEC["per_layer"])
    for name in NEW:
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p50"
        assert (m["unit"], m["layer"]) == ("%", "model")
        assert harness.layer_metric_reader(name) is not None
    assert (metrics["short_conv_time_pct"]["source"],
            metrics["short_conv_time_pct"]["better"]) \
        == ("device_trace", "lower")
    assert (metrics["experts_touched_pct"]["source"],
            metrics["experts_touched_pct"]["better"]) \
        == ("program_counter", "higher")
    for name in JOINED:
        assert CELL in metrics[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in metrics[name]["workloads"], name
    cell = harness.find_cell(SPEC, CELL)
    assert len(cell["why"]) <= 200 and cell["chips"] == 1


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The traced rehearsal, its profile kept in a directory of its own (as
    ``test_trinity_cell.py``'s: ``harness.trace_dir`` is one fixed directory
    of the checkout that every traced run empties first)."""
    private = str(tmp_path_factory.mktemp("bench_trace"))
    real, harness.trace_dir = harness.trace_dir, lambda: private
    try:
        return rehearse(trace=1)
    finally:
        harness.trace_dir = real


def test_rehearsal_prints_the_per_layer_line_with_both_new_metrics(traced):
    line, text = traced
    assert json.loads(json.dumps(line)) == line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    got = line["metrics"]
    assert {"tick_ms_p50", "kv_pool_live_pct", "slot_occupancy_pct",
            "expert_load_max_over_mean", "attn_walked_pct",
            "tick_lanes_live_pct", "experts_touched_pct"} <= set(got)
    # 8 experts, 4 a token, a handful of live lanes a tick: most touched
    assert 50 < got["experts_touched_pct"]["value"] <= 100
    assert got["experts_touched_pct"]["unit"] == "%"
    # a CPU trace holds no device plane: the scope's reader gives nothing
    # here and the line leaves it out (on the chip it reads the scope)
    if "short_conv_time_pct" in got:
        assert 0 < got["short_conv_time_pct"]["value"] <= 100
    # one attention layer that walks live blocks only
    assert got["attn_walked_pct"]["value"] \
        < got["kv_pool_live_pct"]["value"] + 25
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
    # the limits are the published widths' (13 layers of bfloat16 against
    # fp8): at tiny widths the control reads what they allow at 5 layers
    # (0.30-0.39 / 0.08-0.15 / 0.23-0.60) and fails all three at the cell's
    # own depth and pattern (0.63-0.67 / 0.44-0.52 / 1.05-1.32), so the
    # rehearsal runs that depth
    for sizes in (cfg["model"]["kwargs"], cfg["reference_cfg"]):
        sizes["layer_types"] = KINDS
    cfg["model"]["kwargs"]["num_layers"] = len(KINDS)
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


def _synthetic(counted, config=None):
    cfg, trf = harness.cell_files(harness.find_cell(SPEC, CELL))
    return SimpleNamespace(
        cell={"name": CELL}, config=cfg if config is None else config,
        traffic=trf, end_to_end={},
        facts={"counted": counted, "ticks": [(1.0, 0.1, 2, 40.0, 7)],
               "pool_tokens": 1 << 19},
        trace={"main_module_runs": 4, "busy_s": 1.0, "window_s": 2.0},
        spans={}, peaks=harness.device_peaks("TPU v5 lite"))


def test_experts_touched_pct_on_a_synthetic_run():
    reader = harness.layer_metric_reader("experts_touched_pct")
    load = {"ticks": 3, "max_over_mean": 1.5, "touched": 31.5,
            "routed": 2000.0}
    assert reader(_synthetic({"expert_load": load})) \
        == pytest.approx(100 * 31.5 / 32)
    assert reader(_synthetic({"expert_load": dict(load, touched=32.0)})) \
        == pytest.approx(100.0)
    # no counter, or a configuration that names no expert layer: nothing
    assert reader(_synthetic({})) is None
    assert reader(_synthetic({"lanes_live": load})) is None
    assert reader(_synthetic({"expert_load": load},
                             config={"serving_bytes": {}})) is None


def test_short_conv_time_pct_on_a_synthetic_trace(monkeypatch):
    """A trace of three operations, two under the mixer's scope (its
    product and the taps): 0.3 ms of 1 ms busy."""
    from benchmarks import program_trace, scope_time
    ops = [["fusion.1", 0, 250000,
            "jit(step)/layer_2/short_conv/conv/dot_general"],
           ["fusion.2", 300000, 50000,
            "jit(step)/layer_2/short_conv/conv/mul"],
           ["fusion.3", 400000, 700000, "jit(step)/layer_2/moe/moe_experts"]]
    monkeypatch.setattr(program_trace, "of_run", lambda: "planes")
    monkeypatch.setattr(program_trace, "device_ops", lambda planes: ops)
    scope_time._components.cache_clear()
    reader = harness.layer_metric_reader("short_conv_time_pct")
    assert reader(_synthetic({})) == pytest.approx(30.0)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_nothing_on_a_run_without_its_scope_or_counter(
        name, monkeypatch):
    """The other cells' runs (and the parent's): no ``short_conv`` scope in
    the trace, no ``expert_load`` in the facts or no ``expert_layer`` in the
    configuration; with no trace at all likewise."""
    from benchmarks import program_trace
    reader = harness.layer_metric_reader(name)
    peaks = harness.device_peaks("TPU v5 lite")
    recorded = harness.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "recorded_program_trace.json"))
    other = harness.cell_files(harness.find_cell(
        SPEC, "granite4h.shortchat_poisson"))
    for got in (*recorded.values(), None):
        monkeypatch.setattr(program_trace, "of_run", lambda got=got: got)
        for facts in ({}, {"counted": {"lanes_live": {
                "ticks": 3, "max_over_mean": 2.0, "touched": 5.0,
                "routed": 64.0}}, "ticks": [(1.0, 0.1, 2, 40.0, 7)]}):
            run = SimpleNamespace(
                cell={"name": "granite4h.shortchat_poisson"},
                config=other[0], traffic=other[1], end_to_end={},
                facts=facts,
                trace={"main_module_runs": 3, "busy_s": 1.0,
                       "window_s": 2.0} if got else None,
                spans={}, peaks=peaks)
            assert reader(run) is None
