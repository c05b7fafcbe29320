"""The benchmark's own tests (CPU; listed in BENCHMARK.json ``paths``).

They check the harness, not the chip: that every cell's files resolve by
name, that a rehearsal at tiny widths (injected here, never a preset of the
program) prints the contract's result line, that a timed path broken
underneath and a lower-precision control both come out as not correct, that
the open-loop generator is deterministic in the seed and times from the due
instant, that the trace reducer gives a known busy/idle split, that the
roofline arithmetic matches hand counts, and that the measuring path
refuses to run without a TPU.
"""

import io
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, loadgen, roofline  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks import trace as trace_lib  # noqa: E402

SPEC = harness.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TINY = {"vocab_size": 256, "hidden_size": 64, "num_layers": 1,
        "num_heads": 2, "intermediate_size": 128, "max_position": 64}
TINY_REF = dict(TINY, layer_norm_eps=1e-5)
OVERRIDES = {
    "bert_base.lamb_s128": {
        "config": {"model": {"kwargs": TINY}, "reference_cfg": TINY_REF},
        "traffic": {"batch_size": 8, "seq_len": 16, "items_per_row": 16,
                    "reference_rows": 4, "trace_seconds": 1}},
    "gpt1.chat_poisson": {
        "config": {"model": {"kwargs": TINY}, "reference_cfg": TINY_REF},
        "traffic": {"engine": {"slots": 4, "max_len": 64, "block_size": 8},
                    "ramp_s": 0.5, "drain_grace_s": 30, "check_requests": 3,
                    "trace_seconds": 1,
                    "mix": {"rate_per_s": 6,
                            "prompt_tokens": {"median": 12, "sigma": 0.5,
                                              "min": 4, "max": 30},
                            "output_tokens": {"median": 6, "sigma": 0.5,
                                              "min": 2, "max": 12}}}},
}


def rehearse(name, trace=0, break_step=None, seed=2**31 + 77, seconds=1.5):
    args = SimpleNamespace(workload=name, seed=seed, seconds=seconds,
                           trace=trace)
    out = io.StringIO()
    line = bench_run.run_cell(args, rehearsal=True,
                              overrides=OVERRIDES[name],
                              break_step=break_step, out=out)
    return line, out.getvalue()


# ------------------------------------------------------------ the files

@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_resolve_by_name(cell):
    cfg, trf = harness.cell_files(cell)
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", cell["name"] + ".json"))
    assert limits and all(v >= 0 for v in limits.values())
    runner = harness.load_file_module(
        os.path.join("benchmarks", "runners", cfg["runner"] + ".py"))
    assert callable(runner.run)
    ref, prefix = harness.load_reference(cfg["reference"])
    assert callable(getattr(ref, prefix + "_weights"))
    assert callable(harness.resolve(cfg["model"]["builder"]))
    if cfg["runner"] == "train":
        assert callable(harness.batch_maker(trf["batch"]))
        assert trf["steps_ahead"] >= 1      # the host's lead, in steps
        assert callable(harness.resolve(cfg["recipe"]["optimizer"]))
    by_name = {c["name"]: c for c in SPEC["configs"]}
    entry = by_name[cell["config"]]
    on_disk = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert on_disk == cfg and on_disk["reduced"] == entry["reduced"]
    assert on_disk["source"] == entry["source"]


def test_every_per_layer_metric_has_a_reader_of_its_own():
    for m in SPEC["per_layer"]:
        assert callable(harness.layer_metric_reader(m["name"])), m["name"]
    assert harness.layer_metric_reader("no_such_metric") is None


def test_runners_name_no_model_and_no_cell():
    """A new family is a configuration and a reference file, not a branch."""
    words = {c["name"] for c in SPEC["configs"]} \
        | {c["name"] for c in SPEC["workloads"]} | {"bert", "gpt", "resnet"}
    for runner in ("train", "serve"):
        src = open(os.path.join(harness.HERE, "runners",
                                runner + ".py")).read().lower()
        code = "\n".join(l for l in src.splitlines()
                         if not l.strip().startswith(("#", '"""')))
        for w in words:
            assert f'"{w.lower()}' not in code, (runner, w)


def test_benchmark_json_keeps_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int) \
        and 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]] \
        + [c["name"] for c in SPEC["workloads"]] \
        + [c["name"] for c in SPEC["configs"]]
    for n in names:
        assert NAME.match(n), n
    assert len(set(names)) == len(names)
    cells = {c["name"] for c in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
    for c in SPEC["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
        mine = [m for m in SPEC["end_to_end"]
                if c["name"] in m.get("workloads", cells)]
        assert len(mine) >= 2, c["name"]
    four = sum(c["chips"] == 4 for c in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)


# ------------------------------------------------- rehearsals, end to end

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def train_line():
    return rehearse("bert_base.lamb_s128", trace=0)


@pytest.fixture(scope="module")
def serve_line():
    return rehearse("gpt1.chat_poisson", trace=1)


def test_train_rehearsal_prints_the_result_line(train_line):
    line, text = train_line
    assert set(line) == LINE_KEYS
    assert json.loads(json.dumps(line)) == line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert set(line["metrics"]) == {"train_items_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    assert line["device"]["memory_peak_bytes"] \
        == line["device"]["memory_live_peak_bytes"] \
        + line["device"]["memory_reserved_bytes"]
    # each number compared is printed beside its limit
    for name in ("loss_gap", "grad_norm_gap", "update_norm_gap"):
        assert re.search(rf"check {name}: \S+ \(limit \S+\) ok", text)


def test_train_traced_rehearsal_prints_the_per_layer_line():
    line, text = rehearse("bert_base.lamb_s128", trace=1)
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert {"step_ms_p95", "mfu_pct", "device_idle_pct.train"} \
        <= set(line["metrics"]) <= {m["name"] for m in SPEC["per_layer"]}
    assert line["metrics"]["step_ms_p95"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert 0 < line["device"]["window_s"] < 1.5


def test_serve_traced_rehearsal_prints_the_per_layer_line(serve_line):
    line, text = serve_line
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    layer = {m["name"] for m in SPEC["per_layer"]}
    assert set(line["metrics"]) <= layer
    assert {"tick_ms_p50", "tick_ms_max", "tpot_ms_p95",
            "slot_occupancy_pct", "kv_pool_live_pct"} <= set(line["metrics"])
    assert 0 < line["metrics"]["kv_pool_live_pct"]["value"] <= 100
    assert line["metrics"]["tick_ms_max"]["value"] \
        >= line["metrics"]["tick_ms_p50"]["value"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "check served_logit_gap" in text


@pytest.mark.parametrize("name,how", [
    ("bert_base.lamb_s128", "unchanged"),
    ("gpt1.chat_poisson", "alter_token")])
def test_a_timed_path_broken_underneath_is_not_correct(name, how):
    """Everything but the look for a chip runs; the step returns its state
    unchanged, or every served token is altered where it is produced."""
    line, text = rehearse(name, break_step=how)
    assert line["correct"] is False and "FAIL" in text
    # the untraced line holds the cell's end-to-end metrics and no other
    mine = {m["name"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(line["metrics"]) == mine and "setup_s" in mine
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_lower_precision_control_is_not_correct(train_line):
    """The controls of 'How correct is decided', at a size a test can hold.
    Training: the reference put in the program's place at fp8 moves the
    worst leaf's gradient norm far beyond a sound run's.  Serving: the
    program with its own int8 path switched on serves tokens that lie
    below the reference's first place, where a sound run's do not."""
    import jax
    import numpy as np
    from benchmarks.runners import serve, train
    key = harness.seed_key(2**31 + 77)           # the rehearsal's seed
    cell = harness.find_cell(SPEC, "bert_base.lamb_s128")
    cfg, trf = harness.cell_files(cell)
    for target, patch in OVERRIDES[cell["name"]].items():
        bench_run._merge({"config": cfg, "traffic": trf}[target], patch)
    assert cfg["control"]["kind"] == "reference"
    sut = train.Cell(cfg, trf, jax.devices()[:1])
    control = train.gaps(sut.reference(key, cfg["control"]["precision"]),
                         sut.reference(key))
    sound = float(re.search(r"check grad_norm_gap: (\S+)",
                            train_line[1]).group(1))
    assert control["grad_norm_gap"] > 3 * sound

    cell = harness.find_cell(SPEC, "gpt1.chat_poisson")
    cfg, trf = harness.cell_files(cell)
    for target, patch in OVERRIDES[cell["name"]].items():
        bench_run._merge({"config": cfg, "traffic": trf}[target], patch)
    assert cfg["control"]["kind"] == "program"
    # deep and wide enough for near ties, as the cell's own sizes are, and
    # a few hundred served tokens
    wide = {"vocab_size": 4096, "num_layers": 3}
    bench_run._merge(cfg, {"model": {"kwargs": wide}, "reference_cfg": wide})
    bench_run._merge(trf, {"engine": {"slots": 8}, "mix": {
        "rate_per_s": 20, "output_tokens": {"median": 12, "max": 24}}})
    key = harness.seed_key(5)
    sut = serve.Cell(cfg, trf, jax.devices()[:1])
    plan = loadgen.schedule(trf["mix"], 9, trf["ramp_s"], 1.0, sut.vocab)
    got = {}
    for as_control in (False, True):
        eng = sut.engine(key, control=as_control)
        out = serve.drive(sut, eng, plan, 1.0, harness.Spans(),
                          SimpleNamespace(armed=False, n=0))
        ok = [c for c in eng.completions if c.request.uid in out["counted"]]
        got[as_control] = serve.served_gaps(sut, key, ok)
    assert got[False]["served_tokens"] == got[True]["served_tokens"] > 200
    assert got[True]["served_logit_gap"] \
        > 3 * got[False]["served_logit_gap"] + 1e-3


# ------------------------------------------------------- load generator

MIX = {"arrivals": "poisson", "rate_per_s": 5.0,
       "prompt_tokens": {"median": 96, "sigma": 0.6, "min": 16, "max": 384},
       "output_tokens": {"median": 64, "sigma": 0.5, "min": 16, "max": 128}}


def test_loadgen_is_deterministic_and_gives_every_seed_the_same_work():
    a = loadgen.schedule(MIX, 2**31 + 5, 10.0, 40.0, 40478)
    b = loadgen.schedule(MIX, 2**31 + 5, 10.0, 40.0, 40478)
    c = loadgen.schedule(MIX, 6, 10.0, 40.0, 40478)
    assert [(x.due_s, x.prompt, x.max_new) for x in a] \
        == [(x.due_s, x.prompt, x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in c]
    assert [x.due_s for x in a] != [x.due_s for x in c]
    window = lambda plan: [x for x in plan if x.due_s >= 10.0]
    assert len(a) == len(c) == 250 and len(window(a)) == len(window(c)) == 200
    assert sorted(len(x.prompt) for x in window(a)) \
        == sorted(len(x.prompt) for x in window(c))
    assert sorted(x.max_new for x in window(a)) \
        == sorted(x.max_new for x in window(c))
    assert all(16 <= len(x.prompt) <= 384 and 16 <= x.max_new <= 128
               for x in a)
    due = [x.due_s for x in a]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 50.0
    assert [x.index for x in a] == list(range(250))


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 977])
def test_loadgen_arrivals_clump_as_a_poisson_process_does(seed):
    """The gaps of a window are those of a Poisson process of the mix's
    rate: their mean is 1/rate, they spread as widely as their mean (an
    exponential's deviation equals its mean; evenly spaced or stratified
    arrivals would spread far less), and the fullest second of the window
    holds well over the rate."""
    import numpy as np
    plan = [x for x in loadgen.schedule(MIX, seed, 10.0, 400.0, 40478)
            if x.due_s >= 10.0]
    assert len(plan) == 2000
    gaps = np.diff([x.due_s for x in plan])
    assert gaps.mean() == pytest.approx(0.2, rel=0.01)
    assert 0.85 < gaps.std() / gaps.mean() < 1.15
    assert np.mean(gaps < 0.2 * 0.105) == pytest.approx(0.1, abs=0.03)
    per_s = np.histogram([x.due_s for x in plan], bins=400,
                         range=(10.0, 410.0))[0]
    assert per_s.max() >= 10 and per_s.min() <= 1
    assert per_s.var() == pytest.approx(per_s.mean(), rel=0.25)


def test_loadgen_knows_one_arrival_process():
    with pytest.raises(ValueError, match="unknown arrivals"):
        loadgen.schedule(dict(MIX, arrivals="uniform"), 1, 0.0, 1.0, 100)


def test_latencies_are_taken_from_the_due_instant(serve_line):
    """drive() submits a request only once its due instant has passed and
    reports how late; the engine's own arrival stamp is never earlier."""
    import jax
    from benchmarks.runners import serve
    cell = harness.find_cell(SPEC, "gpt1.chat_poisson")
    cfg, trf = harness.cell_files(cell)
    for target, patch in OVERRIDES[cell["name"]].items():
        bench_run._merge({"config": cfg, "traffic": trf}[target], patch)
    sut = serve.Cell(cfg, trf, jax.devices()[:1])
    eng = sut.engine(harness.seed_key(3))
    plan = loadgen.schedule(trf["mix"], 3, trf["ramp_s"], 1.0, sut.vocab)
    out = serve.drive(sut, eng, plan, 1.0, harness.Spans(),
                      SimpleNamespace(armed=False, n=0))
    due = {f"r{p.index}": out["origin"] + p.due_s for p in plan}
    assert out["counted"] and all(v >= 0 for v in out["late"].values())
    for c in eng.completions:
        if c.request.uid in due:
            assert c.request.t_arrival >= due[c.request.uid]
            assert c.t_first_token - due[c.request.uid] \
                >= c.ttft_s - 1e-9


# -------------------------------------------------------- trace reducer

def test_trace_reducer_gives_the_known_split():
    ms = 1_000_000
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_batch(1)", 5 * ms, 1 * ms],
                ["jit_step(2)", 10 * ms, 55 * ms],
                ["jit_step(2)", 80 * ms, 40 * ms]]},     # cut at 100: half
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %custom-call.7), "
                 "kind=kLoop", 10 * ms, 20 * ms],
                ["%output_ln.7 = bf16[8,128]{1,0} custom-call(bf16[8,128] "
                 "%p), custom_call_target=\"tpu_custom_call\"",
                 30 * ms, 10 * ms],
                ["%custom-call.9 = f32[8]{0} custom-call(f32[8]{0} %q), "
                 "custom_call_target=\"ConcatBitcast\"", 59 * ms, 1 * ms],
                ["%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %g)",
                 60 * ms, 5 * ms],
                ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %custom-call.7), "
                 "kind=kLoop", 80 * ms, 10 * ms]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.train_step", 0, 50 * ms],
            ["bench.fetch", 50 * ms, 50 * ms],
            ["something else", 0, 500 * ms]]}]},
    ]
    r = trace_lib.reduce(planes)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.046)
    # only the instruction that is itself a tpu_custom_call is a kernel:
    # not XLA's own custom calls, not an operation that reads one
    assert r["pallas_s"] == pytest.approx(0.010)
    assert r["pallas_kernels"] == {"output_ln": pytest.approx(0.010)}
    assert r["collective_s"] == pytest.approx(0.005)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    assert r["main_module"] == "jit_step(2)"
    assert r["main_module_runs"] == pytest.approx(1.5)
    gaps = dict(r["idle_gaps"])
    # idle: 0-10 and 40-59 go to train_step (a gap goes whole to the span
    # that covers most of it); 65-80, 90-100 to fetch
    assert gaps["bench.train_step"] == pytest.approx(0.029)
    assert gaps["bench.fetch"] == pytest.approx(0.025)


def test_trace_reducer_reads_a_trace_recorded_on_the_chip():
    planes = harness.load_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "recorded_trace.json"))
    r = trace_lib.reduce(planes)
    assert r["devices"] == 1 and 0 < r["busy_s"] <= r["window_s"]
    assert r["pallas_s"] > 0 and r["device_ops"]
    assert any(n.startswith("bench.") for n, _ in r["idle_gaps"])


# ------------------------------------------------------------- roofline

def test_roofline_arithmetic_matches_hand_counts():
    # BERT-base at 128: 12 layers x (4*768^2 + 2*768*3072) + 768*30522
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 30522
    assert n == 108_375_552
    f = roofline.transformer_train_flops_per_token(
        num_layers=12, hidden_size=768, intermediate_size=3072,
        vocab_size=30522, seq_len=128)
    assert f == 6.0 * n + 12.0 * 12 * 128 * 768
    # ResNet-50 at 224: 4.09 GFLOP of multiply-adds forward (x2), x3 to train
    r = roofline.resnet_train_flops_per_image(
        stage_sizes=[3, 4, 6, 3], bottleneck=True, image_size=224,
        num_classes=1000)
    assert r / 3 / 2 == pytest.approx(4.09e9, rel=0.01)
    cfg, _ = harness.cell_files(harness.find_cell(SPEC, "gpt1.chat_poisson"))
    assert roofline.decode_tick_bytes(cfg, 1000) \
        == 466313336 + 1000 * 2 * 12 * 768 * 4
    peaks = harness.device_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 \
        and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.device_peaks("some other device")


@pytest.mark.parametrize("cell", [c for c in SPEC["workloads"]
                                  if "flops" in harness.cell_files(c)[0]],
                         ids=lambda c: c["name"])
def test_a_configuration_names_its_flop_function(cell):
    """No branch on a model family: the configuration names the function
    (``file.py:function``), its own arguments and those from the traffic."""
    cfg, trf = harness.cell_files(cell)
    path, _, fn = cfg["flops"]["function"].partition(":")
    assert callable(getattr(harness.load_file_module(path), fn))
    flops = harness.flops_per_item(cfg, trf)
    assert flops > 1e8
    grown = dict(trf, **{key: trf[key] * 2
                         for key in cfg["flops"]["from_traffic"].values()})
    assert harness.flops_per_item(cfg, grown) > flops


def test_split_metrics_share_one_reader():
    a = harness.layer_metric_reader("device_idle_pct.train")
    b = harness.layer_metric_reader("device_idle_pct.serve")
    run = SimpleNamespace(trace={"busy_s": 3.0, "window_s": 4.0})
    assert a(run) == b(run) == pytest.approx(25.0)
    assert a(SimpleNamespace(trace=None)) is None
    here = os.path.join(harness.HERE, "layer_metrics")
    assert sorted(f for f in os.listdir(here)
                  if f.startswith("device_idle_pct")) \
        == ["device_idle_pct.py"]


def test_seed_key_takes_more_than_32_signed_bits():
    import numpy as np
    a, b = harness.seed_key(2**31 + 3), harness.seed_key(3)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a),
                          np.asarray(harness.seed_key(2**31 + 3)))


# ------------------------------------------------- no chip, no number

def test_measuring_path_refuses_a_backend_that_is_not_a_tpu():
    args = SimpleNamespace(workload="bert_base.lamb_s128", seed=1,
                           seconds=1.0, trace=0)
    with pytest.raises(SystemExit, match="no accelerator"):
        bench_run.run_cell(args)      # no rehearsal switch


def test_the_command_exits_nonzero_and_prints_no_result_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "gpt1.chat_poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
