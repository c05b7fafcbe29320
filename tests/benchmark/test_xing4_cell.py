"""The xing4 cell of the benchmark (CPU; listed in BENCHMARK.json ``paths``):
its files resolve, the configuration holds the published widths, the new
runner names no model and no cell, a rehearsal at tiny widths prints the
contract's line with the cell's per-layer metrics, and a timed path broken
underneath comes out as not correct."""

import io
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, roofline_moe  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

SPEC = harness.benchmark_spec()
CELL = "xing4.longctx_poisson"

TINY = dict(vocab_size=256, hidden_size=64, num_layers=3, first_k_dense=1,
            num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, q_lora_rank=24, kv_lora_rank=32,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2)
OVERRIDES = {
    "config": {"model": {"kwargs": dict(TINY, max_position=4096,
                                        dtype="float32")},
               "reference_cfg": TINY, "reference_block": 16,
               "expert_layer": {"layers": 2, "n_routed_experts": 8,
                                "num_experts_per_tok": 2, "hidden_size": 64,
                                "moe_intermediate_size": 32}},
    "traffic": {"engine": {"slots": 4, "max_len": 64, "block_size": 8},
                "ramp_s": 0.5, "drain_grace_s": 60, "check_requests": 3,
                "trace_seconds": 1,
                "mix": {"rate_per_s": 6,
                        "prompt_tokens": {"median": 12, "sigma": 0.5,
                                          "min": 4, "max": 30},
                        "output_tokens": {"median": 6, "sigma": 0.5,
                                          "min": 2, "max": 12}}}}


def rehearse(trace=0, break_step=None, seed=2**31 + 91, seconds=1.5):
    args = SimpleNamespace(workload=CELL, seed=seed, seconds=seconds,
                           trace=trace)
    out = io.StringIO()
    line = bench_run.run_cell(args, rehearsal=True, overrides=OVERRIDES,
                              break_step=break_step, out=out)
    return line, out.getvalue()


def test_configuration_holds_the_published_widths_and_only_depth_is_cut():
    cell = harness.find_cell(SPEC, CELL)
    cfg, trf = harness.cell_files(cell)
    entry = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert cfg["reduced"] == entry["reduced"] \
        == ["num_hidden_layers", "first_k_dense_replace"]
    published = dict(
        hidden_size=3584, num_attention_heads=32, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=768,
        kv_lora_rank=512, vocab_size=131072, intermediate_size=9216,
        moe_intermediate_size=1024, n_routed_experts=64,
        num_experts_per_tok=4, n_shared_experts=1, hc_mult=4,
        hc_sinkhorn_iters=20, routed_scaling_factor=2, rms_norm_eps=1e-6,
        max_position_embeddings=262144)
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (6, 1)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "first_k_dense_replace": 2}
    # the program's built model and the reference hold the same sizes
    kw, rcfg = cfg["model"]["kwargs"], cfg["reference_cfg"]
    model = harness.resolve(cfg["model"]["builder"])(**kw)
    for pub, mine in dict(
            hidden_size="hidden_size", num_attention_heads="num_heads",
            qk_nope_head_dim="qk_nope_head_dim",
            qk_rope_head_dim="qk_rope_head_dim", v_head_dim="v_head_dim",
            q_lora_rank="q_lora_rank", kv_lora_rank="kv_lora_rank",
            vocab_size="vocab_size", intermediate_size="intermediate_size",
            moe_intermediate_size="moe_intermediate_size",
            n_routed_experts="n_routed_experts",
            num_experts_per_tok="num_experts_per_tok", hc_mult="hc_mult",
            num_hidden_layers="num_layers",
            first_k_dense_replace="first_k_dense",
            max_position_embeddings="max_position").items():
        assert getattr(model, mine) == cfg[pub], pub
        if mine != "max_position":
            assert rcfg[mine] == cfg[pub], pub
    assert model.experts_held is None          # every routed expert is held
    rs = cfg["rope_scaling"]
    assert (model.rope_factor, model.rope_beta_fast, model.rope_beta_slow,
            model.rope_original_max_position, model.rope_theta) \
        == (rs["factor"], rs["beta_fast"], rs["beta_slow"],
            rs["original_max_position_embeddings"], cfg["rope_theta"])
    assert rcfg["rope"]["factor"] == rs["factor"]
    # the cell's geometry, to the letter of ISSUE 27
    assert trf["engine"] == {"slots": 64, "max_len": 4096, "block_size": 16}
    assert trf["mix"]["prompt_tokens"] == {"median": 1024, "sigma": 0.7,
                                           "min": 128, "max": 3072}
    assert trf["mix"]["output_tokens"] == {"median": 256, "sigma": 0.5,
                                           "min": 64, "max": 768}
    assert (trf["ramp_s"], trf["drain_grace_s"], trf["check_requests"],
            trf["trace_seconds"]) == (25, 60, 8, 3)
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", CELL + ".json"))
    # judged by the body of the gaps' distribution, not by the widest
    assert set(limits) == {"served_off_first_share", "served_logit_gap_mean",
                           "served_logit_gap_p90"}
    # bytes a tick must read, and a cached token's
    assert cfg["serving_bytes"]["kv_bytes_per_token"] == 6 * 576 * 2
    held = cfg["parameters"]["held"]
    assert cfg["serving_bytes"]["weight_bytes"] \
        == 2 * (held - 131072 * 3584)
    assert abs(held - 4.79e9) < 0.01e9


def test_the_blocked_runner_names_no_model_and_no_cell():
    words = {c["name"] for c in SPEC["configs"]} \
        | {c["name"] for c in SPEC["workloads"]} \
        | {"bert", "gpt", "resnet", "xing4", "xing"}
    src = open(os.path.join(harness.HERE, "runners",
                            "serve_blocked.py")).read().lower()
    for w in words:
        assert w.lower() not in src, w


def test_roofline_counts_match_hand_counts():
    # 10 pairs on 3 experts of 8x4: 3 matrices of 32 values each
    assert roofline_moe.expert_products_flops(
        pairs=10, hidden_size=8, width=4) == 2 * 3 * 32 * 10
    assert roofline_moe.expert_products_bytes(
        pairs=10, touched=3, hidden_size=8, width=4, weight_itemsize=2,
        activation_itemsize=2) == 3 * 3 * 32 * 2 + 10 * 2 * 8 * 2
    shape = harness.cell_files(harness.find_cell(SPEC, CELL))[0][
        "expert_layer"]
    peaks = harness.device_peaks("TPU v5 lite")
    got = roofline_moe.expert_products_seconds(shape, 512, 64, peaks)
    # every expert touched: 64 x 3 x 3584 x 1024 x 2 B = 1.41 GB, 1.72 ms
    assert got["bound"] == "bytes" and 1.7e-3 < got["seconds"] < 1.8e-3
    assert roofline_moe.expert_products_seconds(
        shape, 1e6, 64, peaks)["bound"] == "flops"


@pytest.fixture(scope="module")
def traced():
    return rehearse(trace=1)


def test_rehearsal_prints_the_per_layer_line(traced):
    line, text = traced
    assert json.loads(json.dumps(line)) == line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert {"tick_ms_p50", "kv_pool_live_pct", "slot_occupancy_pct",
            "expert_load_max_over_mean"} <= set(line["metrics"])
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    for name in ("served_off_first_share", "served_logit_gap_mean",
                 "served_logit_gap_p90", "token_count_mismatch"):
        assert f"check {name}: " in text


def test_rehearsal_with_every_token_altered_is_not_correct():
    line, text = rehearse(break_step="alter_token")
    assert line["correct"] is False and "FAIL" in text
    assert set(line["metrics"]) == {"tpot_ms_p50", "setup_s"}


def test_the_control_is_not_correct_where_a_sound_run_is(traced):
    """The reference at fp8 put in the program's place serves, at some
    position, a token that lies below the float32 reference's best by more
    than anything a sound run serves."""
    import jax
    import numpy as np
    from benchmarks.runners import serve_blocked
    cell = harness.find_cell(SPEC, CELL)
    cfg, trf = harness.cell_files(cell)
    for target, patch in OVERRIDES.items():
        bench_run._merge({"config": cfg, "traffic": trf}[target], patch)
    wide = dict(vocab_size=4096, num_layers=4)
    bench_run._merge(cfg, {"model": {"kwargs": wide}, "reference_cfg": wide})
    assert cfg["control"] == dict(cfg["control"], kind="reference",
                                  precision="fp8")
    sut = serve_blocked.Cell(cfg, trf, jax.devices()[:1])
    key = harness.seed_key(11)
    ids = np.random.default_rng(3).integers(0, 4096, (3, 64)).astype("int32")
    # greedy continuations of the float32 reference itself are sound: 0
    sound = sut.gaps(key, ids, served_by="highest")
    control = sut.gaps(key, ids, served_by="fp8")
    assert sound.shape == control.shape == (3, 63)
    assert float(sound.max()) == 0.0
    assert float(control.max()) > 0.01 and float(np.mean(control > 0)) > 0.02


def test_the_control_fails_the_cells_limits_through_the_runners_own_check():
    """The control goes through ``run`` itself: the same drive, the same
    sample, the same ``harness.Check`` against the cell's own limits file,
    with the reference at ``control.precision`` in the program's place; a
    sound run of the same seed passes them."""
    import time

    import jax
    from benchmarks.runners import serve_blocked
    cell = harness.find_cell(SPEC, CELL)
    cfg, trf = harness.cell_files(cell)
    for target, patch in OVERRIDES.items():
        bench_run._merge({"config": cfg, "traffic": trf}[target], patch)
    wide = dict(vocab_size=4096, num_layers=4)
    bench_run._merge(cfg, {"model": {"kwargs": wide}, "reference_cfg": wide})
    # every request checked and longer outputs: ~150 judged tokens, so the
    # body of the gaps' distribution is read, as on the chip
    bench_run._merge(trf, {"check_requests": 12, "mix": {"output_tokens": {
        "median": 16, "sigma": 0.3, "min": 8, "max": 24}}})
    limits = harness.load_json(os.path.join(harness.HERE, "limits",
                                            CELL + ".json"))
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


def test_the_host_path_is_probed_nudged_and_never_slept_on(monkeypatch):
    """``settle_host`` is one probe where system calls are quick, nudges
    while they read slow and gives up after its tries; the idle loop's
    wait makes no call to ``time.sleep``."""
    import time

    from benchmarks.runners import serve_blocked as sb
    us, nudges = sb.settle_host()
    assert nudges == 0 and 0 < us < sb.SLOW_CALL_US
    readings = iter([40.0, 38.0, 5.0])
    monkeypatch.setattr(sb, "system_call_us", lambda: next(readings))
    assert sb.settle_host() == (5.0, 2)
    monkeypatch.setattr(sb, "system_call_us", lambda: 40.0)
    assert sb.settle_host(tries=3) == (40.0, 3)

    def no_sleep(_):
        raise AssertionError("the idle wait slept")
    t0 = time.perf_counter()
    monkeypatch.setattr(time, "sleep", no_sleep)
    sb._wait(0.002)
    assert time.perf_counter() - t0 >= 0.002
