"""The pangu718b cell of the benchmark (CPU; listed in BENCHMARK.json
``paths``): its files resolve and hold the published configuration (the
catalog's, where it can be read) under ISSUE 36's traffic, a rehearsal at
tiny widths prints the contract's line with the cell's per-layer metrics, a
timed path broken underneath, a module scrambled underneath and the fp8
control come out as not correct against the cell's own limits file, the new
readers' arithmetic by hand, and each new reader gives nothing (and does not
raise) on the other cells' runs.  Entries of BENCHMARK.json are looked up by
name and membership, never by position."""

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

from benchmarks import harness, roofline_moe  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

SPEC = harness.benchmark_spec()
CELL = "pangu718b.reason_poisson"
NEW = ("held_expert_ffn_roofline_pct", "mtp_module_time_pct",
       "draft_accept_pct")
JOINED = ("tpot_ms_p50", "kv_pool_live_pct", "decode_step_roofline_pct",
          "tick_device_gap_ms_p50", "tick_gap_dispatch_ms_p50",
          "tick_gap_harvest_ms_p50", "kv_relayout_time_pct",
          "expert_ffn_time_pct", "latent_attention_time_pct",
          "expert_load_max_over_mean")
NOT_JOINED = ("expert_ffn_roofline_pct", "expert_weight_visits_over_touched",
              "attn_walked_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

SIZES = dict(vocab_size=512, hidden_size=64, num_layers=3, first_k_dense=1,
             num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, q_lora_rank=24, kv_lora_rank=32,
             intermediate_size=128, moe_intermediate_size=32,
             n_routed_experts=8, num_experts_per_tok=2, experts_held=[0, 4])
OVERRIDES = {
    "config": {"model": {"kwargs": dict(SIZES, max_position=4096,
                                        dtype="float32",
                                        param_dtype="float32")},
               "reference_cfg": SIZES, "reference_block": 16,
               "expert_layer": {"layers": 3, "n_routed_experts": 8,
                                "experts_held": 4, "num_experts_per_tok": 2,
                                "hidden_size": 64,
                                "moe_intermediate_size": 32}},
    "traffic": {"engine": {"slots": 4, "max_len": 64, "block_size": 8},
                "ramp_s": 0.5, "drain_grace_s": 60, "check_requests": 12,
                "trace_seconds": 1,
                "mix": {"rate_per_s": 6,
                        "prompt_tokens": {"median": 12, "sigma": 0.5,
                                          "min": 4, "max": 30},
                        "output_tokens": {"median": 14, "sigma": 0.3,
                                          "min": 8, "max": 20}}}}


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


def _metric(name):
    return next(m for m in SPEC["end_to_end"] + SPEC["per_layer"]
                if m["name"] == name)


def test_the_cell_is_the_published_configuration_under_the_issues_traffic():
    cell = harness.find_cell(SPEC, CELL)
    cfg, trf = harness.cell_files(cell)
    entry = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert cell["chips"] == 1 and cell["traffic"] == "reason_poisson"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    assert cfg["source"] == entry["source"]
    assert entry["file"] == "benchmarks/configs/openpangu_ultra_moe_718b.json"
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "first_k_dense_replace": 3,
                                "n_routed_experts": 256,
                                "vocab_size": 153600}
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 16, 19200]
    assert cfg["runner"] == "serve_selfdraft"
    # the program's model and the reference hold the published widths
    kw, rcfg = cfg["model"]["kwargs"], cfg["reference_cfg"]
    model = harness.resolve(cfg["model"]["builder"])(**kw)
    for pub, mine in dict(
            hidden_size="hidden_size", num_attention_heads="num_heads",
            intermediate_size="intermediate_size",
            moe_intermediate_size="moe_intermediate_size",
            q_lora_rank="q_lora_rank", kv_lora_rank="kv_lora_rank",
            qk_nope_head_dim="qk_nope_head_dim",
            qk_rope_head_dim="qk_rope_head_dim", v_head_dim="v_head_dim",
            num_experts_per_tok="num_experts_per_tok",
            routed_scaling_factor="routed_scaling_factor",
            rms_norm_eps="rms_norm_eps", rope_theta="rope_theta",
            vocab_size="vocab_size", num_hidden_layers="num_layers",
            first_k_dense_replace="first_k_dense").items():
        assert getattr(model, mine) == rcfg[mine] == cfg[pub], pub
    # the router keeps its published width; 16 of its experts live here
    assert model.n_routed_experts == rcfg["n_routed_experts"] \
        == cfg["published"]["n_routed_experts"] == 256
    assert model.experts_held == tuple(rcfg["experts_held"]) == (0, 16)
    assert model.num_nextn_predict_layers \
        == cfg["num_nextn_predict_layers"] == 1
    assert model.max_position == cfg["max_position_embeddings"]
    assert cfg["sandwich_norm"] and not cfg["tie_word_embeddings"]
    assert cfg["n_shared_experts"] == 1 and cfg["norm_topk_prob"]
    assert set(cfg["assumed"]) == {"scoring", "sandwich_norm", "mtp_input",
                                   "kv_b_proj", "rope_pairs", "router_bias"}
    e = cfg["expert_layer"]
    assert (e["layers"], e["n_routed_experts"], e["experts_held"],
            e["hidden_size"], e["moe_intermediate_size"]) \
        == (5, 256, 16, 7680, 2048)
    assert cfg["serving_bytes"]["weight_bytes"] == 11780815360
    # the cell's traffic, to the letter of ISSUE 36 (its outputs' max
    # lowered to 768, as it says to where the longest request would not
    # finish inside the drain: PERF.md section 4)
    assert trf["engine"] == {"slots": 64, "max_len": 2048, "block_size": 16}
    assert trf["mix"]["arrivals"] == "poisson"
    assert trf["mix"]["prompt_tokens"] == {"median": 256, "sigma": 0.8,
                                           "min": 32, "max": 1024}
    assert trf["mix"]["output_tokens"] == {"median": 512, "sigma": 0.5,
                                           "min": 128, "max": 768}
    assert (trf["ramp_s"], trf["drain_grace_s"], trf["check_requests"],
            trf["trace_seconds"]) == (30, 60, 8, 3)
    rate, knee = trf["mix"]["rate_per_s"], trf["mix"]["knee_per_s"]
    assert abs(rate - 0.8 * knee) < 1e-9
    assert f"{rate} req/s" in trf["why"] and f"{knee}" in trf["why"]
    assert f"{rate} req/s" in cell["why"] and f"{knee}" in cell["why"]
    # every metric the cell reports names it; the three new ones only it;
    # three it must not join do not
    for name in JOINED:
        assert CELL in _metric(name)["workloads"], name
    for name in NEW:
        m = _metric(name)
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p50"
    for name in NOT_JOINED:
        assert CELL not in _metric(name)["workloads"], name
    assert [m["name"] for m in SPEC["per_layer"][-3:]] == list(NEW)


def test_the_configuration_file_holds_every_number_of_the_catalogs_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "openPangu-Ultra-MoE-718B")
    cfg = harness.cell_files(harness.find_cell(SPEC, CELL))[0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok"}
    assert not widths & set(cfg["reduced"])


def test_roofline_of_the_experts_held_by_hand():
    """16 held experts, each touched, 40 pairs a layer: the weights of 16
    experts once (1.51 GB) and 40 rows in and out; bytes bound it."""
    shape = harness.cell_files(harness.find_cell(SPEC, CELL))[0][
        "expert_layer"]
    peaks = harness.device_peaks("TPU v5 lite")
    got = roofline_moe.expert_products_seconds(shape, 40, 16, peaks)
    bytes_ = 16 * 3 * 7680 * 2048 * 2 + 40 * 2 * 7680 * 2
    assert got["bound"] == "bytes"
    assert got["seconds"] == bytes_ / peaks["hbm_bytes_per_s"]
    reader = harness.layer_metric_reader("held_expert_ffn_roofline_pct")
    from benchmarks import program_trace, scope_time
    run = SimpleNamespace(
        config={"expert_layer": shape}, peaks=peaks,
        trace={"main_module_runs": 10},
        facts={"counted": {"expert_load_held": {
            "ticks": 10, "max_over_mean": 2.0, "touched": 16.0,
            "routed": 40.0}}})
    real = scope_time.scope_seconds
    try:
        # 0.1 s under `moe_experts` over the 10 traced ticks
        scope_time.scope_seconds = lambda *a, **k: (0.1, 1.0)
        program_trace.of_run, of_run = (lambda: object()), \
            program_trace.of_run
        assert reader(run) == pytest.approx(
            100.0 * 5 * got["seconds"] / 0.01)
    finally:
        scope_time.scope_seconds, program_trace.of_run = real, of_run


def test_draft_accept_pct_by_hand():
    reader = harness.layer_metric_reader("draft_accept_pct")
    facts = {"counted": {"drafts_verified": {"ticks": 50, "routed": 40.0},
                         "drafts_accepted": {"ticks": 2, "routed": 1.5}}}
    assert reader(SimpleNamespace(facts=facts)) \
        == pytest.approx(100.0 * 3 / 2000)
    del facts["counted"]["drafts_accepted"]      # 0 in every tick
    assert reader(SimpleNamespace(facts=facts)) == 0.0


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The traced rehearsal, with a trace directory of its own: the
    checkout's one `.bench_trace` is emptied by every traced rehearsal of
    the suite, and the driver runs test files side by side."""
    from unittest import mock
    from benchmarks import program_trace, trace as trace_lib
    where = str(tmp_path_factory.mktemp("bench_trace"))

    def of_run():
        path = trace_lib.find_xplane(where)
        return path and program_trace._load_cached(path,
                                                   os.path.getmtime(path))

    with mock.patch.object(harness, "trace_dir", lambda: where), \
            mock.patch.object(program_trace, "of_run", of_run):
        return rehearse(trace=1)


def test_rehearsal_prints_the_per_layer_line(traced):
    line, text = traced
    assert json.loads(json.dumps(line)) == line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    # what the host clock and the program's counters give is there
    assert {"tick_ms_p50", "kv_pool_live_pct", "slot_occupancy_pct",
            "expert_load_max_over_mean", "draft_accept_pct"} \
        <= set(line["metrics"])
    # every other name on the line is one of the cell's own; the
    # device-trace readers need a device's trace, and whichever of them
    # read something here read a share
    mine = {m["name"] for m in SPEC["per_layer"]
            if bench_run.reports(m, CELL, {"tpot_ms_p50", "setup_s"})}
    assert set(NEW) | set(JOINED) - {"tpot_ms_p50"} <= mine
    assert set(line["metrics"]) <= mine and not set(NOT_JOINED) & mine
    for name in ("held_expert_ffn_roofline_pct", "mtp_module_time_pct",
                 "expert_ffn_time_pct", "latent_attention_time_pct"):
        if name in line["metrics"]:
            assert 0 < line["metrics"][name]["value"] <= 100, name
    # at a vocabulary of 512 on seeded weights hardly a draft comes true
    assert 0 <= line["metrics"]["draft_accept_pct"]["value"] < 20
    for name in ("served_off_first_share", "served_logit_gap_mean",
                 "draft_off_first_share", "draft_logit_gap_mean",
                 "token_count_mismatch"):
        assert f"check {name}: " in text


def test_rehearsal_with_every_token_altered_is_not_correct():
    line, text = rehearse(break_step="alter_token")
    assert line["correct"] is False
    assert "check served_off_first_share: 1 " in text and "FAIL" in text
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}


def test_rehearsal_with_the_modules_weights_perturbed_is_not_correct():
    """Served tokens are the same with a sound module, a broken one or
    none: only the draft checks can see it."""
    line, text = rehearse(break_step="perturb_module")
    assert line["correct"] is False and line["failed"] == 0
    rows = {r.split(":")[0].removeprefix("check "): r.endswith("ok")
            for r in text.strip().splitlines()}
    assert rows == {"served_off_first_share": True,
                    "served_logit_gap_mean": True,
                    "draft_off_first_share": False,
                    "draft_logit_gap_mean": False,
                    "token_count_mismatch": True}


def test_the_control_fails_the_cells_limits_through_the_runners_own_check():
    """The control goes through ``run`` itself: the same drive, the same
    sample, the same ``harness.Check`` against the cell's own limits file,
    with the reference at ``control.precision`` (fp8 operands, bfloat16
    activations) in the program's place, for served tokens and drafts
    alike; a sound run of the same seed passes them."""
    import jax
    from benchmarks.runners import serve_selfdraft
    cell, cfg, trf, limits = _files()
    assert cfg["control"] == dict(cfg["control"], kind="reference",
                                  precision="fp8")
    assert set(limits) == {"served_off_first_share",
                           "served_logit_gap_mean", "draft_off_first_share",
                           "draft_logit_gap_mean"}
    args = SimpleNamespace(seed=5, seconds=1.5, trace=0)
    checks = {}
    for served_by in (None, cfg["control"]["precision"]):
        res = serve_selfdraft.run(
            cell, cfg, trf, limits, args, jax.devices()[:1],
            time.perf_counter(), harness.Spans(), harness.CompileCounter(),
            served_by=served_by)
        assert res["failed"] == 0
        checks[served_by] = res["check"]
    assert checks[None].ok
    control = checks["fp8"]
    assert not control.ok
    failed = {r["name"] for r in control.rows if not r["ok"]}
    assert failed <= set(limits) and failed
    assert any(name.startswith("draft_") for name in failed)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_nothing_on_a_run_without_its_scope_or_counter(
        name, monkeypatch):
    """The other cells' runs (and the parent's of any cell): no `mtp` scope
    in the trace, no `expert_load_held` or `drafts_*` counter in the facts;
    with no trace at all likewise."""
    from benchmarks import program_trace
    reader = harness.layer_metric_reader(name)
    peaks = harness.device_peaks("TPU v5 lite")
    # traces recorded on the chip by PR 24: a GPT-1 tick, a BERT step
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
