"""The granite4h cell of the benchmark (CPU; listed in BENCHMARK.json
``paths``): its files resolve and hold the published configuration and the
issue's traffic, a rehearsal at tiny widths prints the contract's line with
the cell's per-layer metrics, a timed path broken underneath and the fp8
control come out as not correct against the cell's own limits file, the
roofline's counts match hand counts, and each new reader gives nothing
(and does not raise) on the other cells' runs."""

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

from benchmarks import harness, roofline_ssm  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

SPEC = harness.benchmark_spec()
CELL = "granite4h.shortchat_poisson"
NEW = ("ssm_mixer_time_pct", "ssm_scan_time_pct", "ssm_scan_roofline_pct",
       "shared_mlp_time_pct", "tick_lanes_live_pct")

KINDS = ["mamba", "mamba", "attention", "mamba", "mamba"]
SIZES = dict(vocab_size=4096, hidden_size=64, num_heads=4, num_kv_heads=2,
             head_dim=16, intermediate_size=128, mamba_n_heads=4,
             mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4)
OVERRIDES = {
    "config": {"model": {"kwargs": dict(
                   SIZES, num_layers=5, attention_period=3,
                   attention_offset=2, mamba_chunk_size=8,
                   max_position=4096, dtype="float32",
                   param_dtype="float32")},
               "reference_cfg": dict(SIZES, layer_types=KINDS),
               "reference_block": 16,
               "ssm_layer": {"layers": 4, "heads": 4, "head_dim": 16,
                             "d_state": 16, "conv_channels": 96}},
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


def test_the_cell_is_the_published_configuration_under_the_issues_traffic():
    cell = harness.find_cell(SPEC, CELL)
    cfg, trf = harness.cell_files(cell)
    entry = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert cell["chips"] == 1 and cfg["reduced"] == entry["reduced"] == []
    assert cfg["source"] == entry["source"] and cfg["published"] == {}
    assert cfg["layer_types"].count("mamba") == 36
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    # the program's model and the reference hold the published sizes
    kw, rcfg = cfg["model"]["kwargs"], cfg["reference_cfg"]
    model = harness.resolve(cfg["model"]["builder"])(**kw)
    assert list(model.layer_kinds()) == rcfg["layer_types"] \
        == cfg["layer_types"]
    for pub, mine in dict(
            hidden_size="hidden_size", num_attention_heads="num_heads",
            num_key_value_heads="num_kv_heads", vocab_size="vocab_size",
            shared_intermediate_size="intermediate_size",
            mamba_n_heads="mamba_n_heads", mamba_d_head="mamba_d_head",
            mamba_d_state="mamba_d_state", mamba_d_conv="mamba_d_conv",
            embedding_multiplier="embedding_multiplier",
            attention_multiplier="attention_multiplier",
            residual_multiplier="residual_multiplier",
            logits_scaling="logits_scaling",
            rms_norm_eps="rms_norm_eps").items():
        assert getattr(model, mine) == rcfg[mine] == cfg[pub], pub
    assert model.num_layers == cfg["num_hidden_layers"] == 40
    assert model.mamba_chunk_size == cfg["mamba_chunk_size"]
    assert model.max_position == cfg["max_position_embeddings"]
    assert model.mamba_n_heads * model.mamba_d_head \
        == cfg["mamba_expand"] * cfg["hidden_size"]
    assert cfg["num_local_experts"] == 0 and cfg["tie_word_embeddings"]
    # the cell's traffic, to the letter of ISSUE 34
    assert trf["engine"] == {"slots": 64, "max_len": 1024, "block_size": 16}
    assert trf["mix"]["arrivals"] == "poisson"
    assert trf["mix"]["prompt_tokens"] == {"median": 128, "sigma": 0.7,
                                           "min": 16, "max": 512}
    assert trf["mix"]["output_tokens"] == {"median": 96, "sigma": 0.5,
                                           "min": 16, "max": 256}
    assert (trf["ramp_s"], trf["drain_grace_s"], trf["check_requests"],
            trf["trace_seconds"]) == (20, 60, 8, 3)
    # bytes: K and V of 4 attention layers a token; a slot's state apart
    assert cfg["serving_bytes"]["kv_bytes_per_token"] == 4 * 2 * 512 * 2
    s = cfg["ssm_layer"]
    per_slot = s["layers"] * (
        s["heads"] * s["head_dim"] * s["d_state"] * s["state_itemsize"]
        + (s["d_conv"] - 1) * s["conv_channels"] * s["conv_itemsize"])
    assert per_slot == 76437504 and "76,437,504" in \
        cfg["serving_bytes"]["why"]
    # every metric the cell reports names it, the five new ones only it
    for m in SPEC["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p50"
    for name in ("tpot_ms_p50", "kv_pool_live_pct", "tick_device_gap_ms_p50",
                 "decode_step_roofline_pct", "tick_gap_dispatch_ms_p50",
                 "tick_gap_harvest_ms_p50", "kv_relayout_time_pct"):
        m = next(m for m in SPEC["end_to_end"] + SPEC["per_layer"]
                 if m["name"] == name)
        assert m["workloads"][-1] == CELL


def test_roofline_counts_match_hand_counts():
    dims = dict(heads=2, head_dim=3, d_state=5, conv_channels=16, d_conv=4)
    assert roofline_ssm.scan_flops(lanes=7, **dims) \
        == 7 * (5 * 30 + 2 * 4 * 16)
    assert roofline_ssm.scan_bytes(slots=3, lanes=7, state_itemsize=4,
                                   conv_itemsize=2, **dims) \
        == 2 * 3 * (30 * 4 + 3 * 16 * 2) + 7 * ((16 + 6) * 2 + 2 * 4)
    shape = harness.cell_files(harness.find_cell(SPEC, CELL))[0]["ssm_layer"]
    peaks = harness.device_peaks("TPU v5 lite")
    got = roofline_ssm.scan_seconds(shape, 64, 1024, peaks)
    # 64 states of 2.1 MB read and written and 1024 lanes: 289 MB, 0.35 ms
    assert got["bound"] == "bytes" and 3.4e-4 < got["seconds"] < 3.6e-4
    # nothing advanced, nothing to move
    assert roofline_ssm.scan_seconds(shape, 0, 0, peaks)["seconds"] == 0.0


@pytest.fixture(scope="module")
def traced():
    return rehearse(trace=1)


def test_rehearsal_prints_the_per_layer_line(traced):
    line, text = traced
    assert json.loads(json.dumps(line)) == line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert {"tick_ms_p50", "kv_pool_live_pct", "slot_occupancy_pct",
            "tick_lanes_live_pct"} <= set(line["metrics"])
    # the device-trace readers need a device's trace; whichever of them
    # read something here read a share
    for name in NEW:
        if name in line["metrics"]:
            assert 0 < line["metrics"][name]["value"] <= 100, name
    # a decoding slot uses 1 of its 8 lanes, a prefilling one more
    assert 100 / 8 / 4 < line["metrics"]["tick_lanes_live_pct"]["value"] < 60
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
    with the reference at ``control.precision`` (fp8 operands, bfloat16
    activations and state) in the program's place; a sound run of the same
    seed passes them."""
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


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_nothing_on_a_run_without_its_scope_or_counter(
        name, monkeypatch):
    """The other cells' runs (and the parent's of this one): no `ssm_*`
    scope in the trace, no counter in the facts, no `ssm_layer` in the
    configuration; with no trace at all likewise."""
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
