"""Resilience-runtime coverage (apex_example_tpu/resilience/,
tools/supervise.py; ISSUE 4):

- schema v4 records (preemption / restart / resume, run_summary
  restart_count) + v1-v3 back-compat,
- FaultPlan parse / fire-once / NaN batch poisoning,
- PreemptionHandler flag semantics and the flight-recorder SIGTERM
  handover (release_signal),
- CheckpointManager host-state sidecar round-trip + pruning,
- jax-free Supervisor units: --resume rewrite, metrics rotation,
  preemption restart, crash backoff, restart budget,
- the acceptance loop, in-process: sigterm fault -> grace save -> exit
  75 -> resume -> loss trail bit-identical to the uninterrupted run,
- the acceptance loop, end-to-end: the same drill under
  tools/supervise.py with real train.py children,
- crash-fault forensics (flight recorder still crash_dumps), nan-fault
  overflow provenance, image-path --save-every-steps + grace.

Subprocess tests carry the ``resilience`` marker (pytest.ini);
everything here rides tier-1.
"""

import importlib.util
import json
import math
import os
import signal
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

import train as train_mod
from apex_example_tpu import obs
from apex_example_tpu.obs import schema as obs_schema
from apex_example_tpu.resilience import (EX_TEMPFAIL, FaultInjected,
                                         FaultPlan, PreemptionHandler)
from apex_example_tpu.utils.checkpoint import CheckpointManager

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_supervisor():
    """By file path, exactly as tools/supervise.py does — the package
    import would be a different (jax-carrying) code path."""
    spec = importlib.util.spec_from_file_location(
        "apex_supervisor_under_test",
        os.path.join(REPO, "apex_example_tpu", "resilience",
                     "supervisor.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _header(rank=0):
    return {"record": "run_header", "schema": obs_schema.SCHEMA_VERSION,
            "time": 0.0, "run_id": "r", "num_devices": 1,
            "process_index": rank, "platform": "cpu", "config": {}}


def _step(i, loss=1.0):
    return {"record": "step", "step": i, "epoch": 0, "loss": loss,
            "scale": 1.0, "step_time_ms": 10.0, "items_per_sec": 100.0}


def _losses(path):
    return {r["step"]: r["loss"] for r in obs.read_jsonl(path)
            if r["record"] == "step"}


def _args(steps):
    """The shared tiny-LM config (C4-shaped, single device) all the
    loop-level tests train under — identical config => comparable loss
    trails."""
    return ["--arch", "bert_tiny", "--batch-size", "8", "--seq-len", "16",
            "--epochs", "1", "--steps-per-epoch", str(steps),
            "--opt", "adam", "--opt-level", "O0", "--num-devices", "1",
            "--print-freq", str(steps)]


# ------------------------------------------------------- schema v4

def test_schema_v4_resilience_records_validate():
    pre = {"record": "preemption", "time": 1.0, "signal": "SIGTERM",
           "step": 3, "run_id": "r", "checkpoint_step": 3, "saved": True}
    restart = {"record": "restart", "time": 1.0, "attempt": 0,
               "exit_code": 75, "reason": "preemption", "backoff_s": 0.0,
               "last_step": 3, "checkpoint_step": 3, "run_id": "r"}
    resume = {"record": "resume", "time": 1.0, "attempt": 1,
              "checkpoint_step": 3, "resume_dir": "/ck", "run_id": "r"}
    summary = {"record": "run_summary", "steps": 6, "overflow_count": 0,
               "restart_count": 1, "exit_code": 0}
    for rec in (pre, restart, resume, summary):
        assert obs.validate_record(rec) == [], rec["record"]
    assert obs_schema.validate_stream(
        [_header(), _step(1), pre, summary]) == []
    # supervisor-stream shape: no step records at all
    assert obs_schema.validate_stream(
        [_header(), restart, resume, summary]) == []


def test_schema_v1_v3_streams_still_validate():
    """v4 is a strict superset: pre-PR streams keep validating."""
    v1 = [dict(_header(), schema=1), _step(1),
          {"record": "run_summary", "steps": 1, "overflow_count": 0}]
    v2 = [dict(_header(), schema=2), _step(1),
          {"record": "crash_dump", "time": 1.0, "reason": "signal:SIGTERM"},
          {"record": "run_summary", "steps": 1, "overflow_count": 0,
           "aborted": True, "abort_reason": "signal:SIGTERM"}]
    v3 = [dict(_header(), schema=3),
          {"record": "request_complete", "time": 1.0, "request_id": "r-0",
           "prompt_tokens": 4, "output_tokens": 6, "ttft_ms": 10.0,
           "tpot_ms": 1.5, "finish_reason": "length"},
          {"record": "serve_summary", "time": 2.0, "requests": 1,
           "output_tokens": 6, "tokens_per_sec": 50.0}]
    for stream in (v1, v2, v3):
        assert obs_schema.validate_stream(stream) == []


def test_schema_v4_rejects_malformed():
    assert obs.validate_record({"record": "preemption", "time": 1.0,
                                "step": 3})              # missing signal
    assert obs.validate_record({"record": "restart", "time": 1.0,
                                "attempt": "0", "exit_code": 75,
                                "reason": "crash"})      # str attempt
    assert obs.validate_record({"record": "resume", "time": 1.0,
                                "attempt": 1, "typo": 1})  # unknown field


# ------------------------------------------------------ fault plans

def test_fault_plan_parse_and_rejections():
    fp = FaultPlan.parse("sigterm@12")
    assert (fp.kind, fp.step) == ("sigterm", 12)
    for bad in ("sigterm", "bogus@3", "crash@0", "crash@x", "@3",
                "crash@"):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_fault_plan_crash_fires_once_at_exact_step():
    fp = FaultPlan("crash", 2)
    fp.maybe_fire(1)                               # not yet
    with pytest.raises(FaultInjected, match="injected crash at step 2"):
        fp.maybe_fire(2)
    fp.maybe_fire(2)                               # fired: no-op
    resumed_past = FaultPlan("crash", 2)
    resumed_past.maybe_fire(3)                     # == only: never fires
    assert not resumed_past.fired


def test_fault_plan_sigterm_and_hang_mechanisms(monkeypatch):
    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append((pid,
                                                                   sig)))
    FaultPlan("sigterm", 1).maybe_fire(1)
    assert kills == [(os.getpid(), signal.SIGTERM)]
    naps = []
    monkeypatch.setattr(time, "sleep", naps.append)
    FaultPlan("hang", 1, hang_s=123.0).maybe_fire(1)
    assert naps == [123.0]


def test_fault_plan_serve_kinds():
    """slot_fail is a SERVE-only kind: serve.py's parse accepts it,
    train.py's default parse keeps rejecting it; due()/take() is the
    caller-handled one-shot (nan token degeneration, slot_fail) — >=
    semantics, because a slot-level fault scheduled on a tick that
    cannot express it must fire at the next one that can."""
    from apex_example_tpu.resilience.faults import SERVE_KINDS
    fp = FaultPlan.parse("slot_fail@4", kinds=SERVE_KINDS)
    assert (fp.kind, fp.step) == ("slot_fail", 4)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("slot_fail@4")            # training kinds
    assert not fp.due(3)
    assert fp.due(4) and fp.due(5)                # >= until consumed
    fp.take()
    assert not fp.due(5)                          # consumed: once only
    fp.maybe_fire(4)                              # not its mechanism: noop


def test_fault_plan_nan_poisons_only_float_leaves():
    fp = FaultPlan("nan", 3)
    batch = (jnp.ones((2, 2)), jnp.zeros((2,), jnp.int32))
    assert fp.maybe_poison(2, batch) is batch      # wrong step: untouched
    x, y = fp.maybe_poison(3, batch)
    assert bool(jnp.isnan(x).all())
    assert y.dtype == jnp.int32 and int(y.sum()) == 0
    assert fp.fired
    with pytest.raises(FaultInjected, match="no floating-point leaf"):
        FaultPlan("nan", 1).maybe_poison(1, (jnp.zeros((2,), jnp.int32),))


# ------------------------------------------------ preemption handler

def test_preemption_handler_flag_and_restore():
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_usr1 = signal.getsignal(signal.SIGUSR1)
    h = PreemptionHandler()
    h.install()
    assert h.installed and not h.preempted
    os.kill(os.getpid(), signal.SIGUSR1)
    for _ in range(200):
        if h.preempted:
            break
        time.sleep(0.005)
    assert h.preempted and h.signal_name == "SIGUSR1"
    os.kill(os.getpid(), signal.SIGUSR1)           # repeat: ignored
    time.sleep(0.01)
    assert h.signal_name == "SIGUSR1"
    h.close()
    assert signal.getsignal(signal.SIGTERM) == prev_term
    assert signal.getsignal(signal.SIGUSR1) == prev_usr1


def test_preemption_takes_over_flight_recorder(tmp_path):
    """The handover: SIGTERM under --preempt-grace sets the flag instead
    of crash-dumping, and close ORDER does not matter (release_signal
    removes the recorder's claim at install time)."""
    path = str(tmp_path / "f.jsonl")
    sink = obs.JsonlSink(path, rank=0)
    recorder = obs.FlightRecorder(sink=sink)
    prev_term = signal.getsignal(signal.SIGTERM)
    recorder.install()
    h = PreemptionHandler(signals=(signal.SIGTERM,), recorder=recorder)
    h.install()
    os.kill(os.getpid(), signal.SIGTERM)
    for _ in range(200):
        if h.preempted:
            break
        time.sleep(0.005)
    assert h.preempted and h.signal_name == "SIGTERM"
    assert not recorder._dumped                    # no crash forensics
    recorder.close()                               # recorder first...
    assert signal.getsignal(signal.SIGTERM) == h._on_signal  # ...ours holds
    h.close()
    assert signal.getsignal(signal.SIGTERM) == prev_term
    assert not os.path.exists(path)                # nothing ever written


# ------------------------------------------- host-state sidecar

def test_host_state_sidecar_roundtrip_and_prune(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), max_to_keep=2)
    for step in (1, 2, 3, 4):
        mgr.save_host_state(step, {"step": step, "data_index": step})
    assert sorted(mgr.host_state_steps()) == [3, 4]    # retention window
    assert mgr.load_host_state(4) == {"step": 4, "data_index": 4}
    assert mgr.load_host_state(1) is None              # pruned
    assert mgr.load_host_state(99) is None
    mgr.close()


# ------------------------------------------------- supervisor units

def _child_script(tmp_path, name, body):
    path = tmp_path / name
    path.write_text(body)
    return str(path)


def test_supervisor_checkpoint_and_tail_helpers(tmp_path):
    sup = _load_supervisor()
    assert sup.latest_checkpoint_step(None) is None
    assert sup.latest_checkpoint_step(str(tmp_path / "missing")) is None
    ck = tmp_path / "ck"
    (ck / "3").mkdir(parents=True)
    (ck / "12").mkdir()
    (ck / "notastep").mkdir()
    (ck / "7").write_text("a file, not a step dir")
    (ck / "host_state-12.json").write_text("{}")
    assert sup.latest_checkpoint_step(str(ck)) == 12

    stream = tmp_path / "m.jsonl"
    with open(stream, "w") as fh:
        fh.write(json.dumps(_header()) + "\n")
        fh.write(json.dumps(_step(4)) + "\n")
        fh.write(json.dumps(_step(5)) + "\n")
        fh.write('{"record":"step","step":6')       # torn final line
    assert sup.tail_last_step(str(stream)) == 5
    assert sup.tail_last_step(str(tmp_path / "missing.jsonl")) is None

    assert sup._set_flag(["a", "--resume", "old"], "--resume", "ck") == \
        ["a", "--resume", "ck"]
    assert sup._set_flag(["a", "--resume=old"], "--resume", "ck") == \
        ["a", "--resume=ck"]
    assert sup._set_flag(["a"], "--resume", "ck") == ["a", "--resume", "ck"]


def test_supervisor_preemption_restart_then_success(tmp_path):
    """Exit 75 once -> one prompt restart with --resume rewritten and the
    child metrics rotated; schema-valid supervisor stream throughout."""
    sup_mod = _load_supervisor()
    marker = tmp_path / "ran_once"
    argv_log = tmp_path / "argvs.txt"
    child = _child_script(tmp_path, "child.py", f"""\
import os, sys
with open({str(argv_log)!r}, "a") as fh:
    fh.write(" ".join(sys.argv[1:]) + "\\n")
if os.path.exists({str(marker)!r}):
    sys.exit(0)
open({str(marker)!r}, "w").close()
sys.exit(75)
""")
    (tmp_path / "ck" / "5").mkdir(parents=True)        # pre-existing ckpt
    sleeps = []
    sup = sup_mod.Supervisor(
        [sys.executable, child, "--metrics-jsonl",
         str(tmp_path / "c.jsonl")],
        checkpoint_dir=str(tmp_path / "ck"),
        metrics_jsonl=str(tmp_path / "sup.jsonl"),
        max_restarts=2, backoff_s=0.01, sleep_fn=sleeps.append,
        log=lambda *a: None)
    assert sup.run() == 0
    launches = argv_log.read_text().splitlines()
    assert len(launches) == 2
    # attempt 0 already resumes the pre-existing checkpoint
    assert f"--resume {tmp_path / 'ck'}" in launches[0]
    assert ".attempt1" not in launches[0]
    assert ".attempt1" in launches[1]                  # rotated metrics
    assert sleeps == []                                # preemption: prompt
    recs = obs.read_jsonl(str(tmp_path / "sup.jsonl"))
    assert obs_schema.validate_stream(recs) == []
    assert [r["record"] for r in recs] == \
        ["run_header", "resume", "restart", "resume", "run_summary"]
    restart = recs[2]
    assert restart["exit_code"] == 75
    assert restart["reason"] == "preemption"
    assert restart["attempt"] == 0
    assert recs[3]["attempt"] == 1 and recs[3]["checkpoint_step"] == 5
    assert recs[-1]["restart_count"] == 1 and recs[-1]["exit_code"] == 0


def test_supervisor_crash_backoff_and_budget(tmp_path):
    """Crash exits restart with exponential backoff until the budget is
    spent; the supervisor then surfaces the child's status."""
    sup_mod = _load_supervisor()
    child = _child_script(tmp_path, "crasher.py", "import sys\nsys.exit(3)\n")
    sleeps = []
    sup = sup_mod.Supervisor(
        [sys.executable, child],
        metrics_jsonl=str(tmp_path / "sup.jsonl"),
        max_restarts=2, backoff_s=0.5, backoff_max_s=10.0,
        sleep_fn=sleeps.append, log=lambda *a: None)
    assert sup.run() == 3
    assert sleeps == [0.5, 1.0]                        # 0.5 * 2^k
    recs = obs.read_jsonl(str(tmp_path / "sup.jsonl"))
    assert obs_schema.validate_stream(recs) == []
    restarts = [r for r in recs if r["record"] == "restart"]
    assert len(restarts) == 2
    assert all(r["reason"] == "crash" and r["exit_code"] == 3
               for r in restarts)
    assert not any(r["record"] == "resume" for r in recs)  # no ckpt dir
    assert recs[-1]["restart_count"] == 2 and recs[-1]["exit_code"] == 3


def test_supervisor_relaunch_continues_attempt_numbering(tmp_path):
    """A relaunched supervisor must not let its attempt-0 child truncate
    a previous incarnation's streams: numbering continues past existing
    PATH/PATH.attempt* files.  An explicit --child-metrics stays the
    tail target regardless of rotation."""
    sup_mod = _load_supervisor()
    base = tmp_path / "c.jsonl"
    base.write_text(json.dumps(_step(7)) + "\n")       # predecessor's
    (tmp_path / "c.jsonl.attempt1").write_text("old forensics\n")
    child = _child_script(tmp_path, "ok.py", "import sys\nsys.exit(0)\n")
    sup = sup_mod.Supervisor(
        [sys.executable, child, "--metrics-jsonl", str(base)],
        metrics_jsonl=str(tmp_path / "sup.jsonl"),
        max_restarts=1, sleep_fn=lambda s: None, log=lambda *a: None)
    assert sup.run() == 0
    assert sup._attempt_offset == 2
    assert sup._flag_path(0) == str(base) + ".attempt2"
    assert base.read_text() != ""                      # not truncated
    assert (tmp_path / "c.jsonl.attempt1").read_text() == "old forensics\n"
    # explicit tail wins over the rotated flag path
    sup2 = sup_mod.Supervisor(
        [sys.executable, child, "--metrics-jsonl", str(base)],
        child_metrics=str(tmp_path / "real.jsonl"),
        log=lambda *a: None)
    assert sup2._metrics_path(3) == str(tmp_path / "real.jsonl")


def test_supervisor_tail_only_child_metrics_not_injected(tmp_path):
    """--child-metrics names a file to TAIL; when the child's own argv
    has no --metrics-jsonl (e.g. a wrapper that rejects unknown flags),
    restart attempts must not inject one — and tailing sticks to the
    un-rotated path."""
    sup_mod = _load_supervisor()
    marker = tmp_path / "ran_once"
    argv_log = tmp_path / "argvs.txt"
    child = _child_script(tmp_path, "wrapper.py", f"""\
import os, sys
assert "--metrics-jsonl" not in " ".join(sys.argv), sys.argv
with open({str(argv_log)!r}, "a") as fh:
    fh.write(" ".join(sys.argv[1:]) + "\\n")
if os.path.exists({str(marker)!r}):
    sys.exit(0)
open({str(marker)!r}, "w").close()
sys.exit(75)
""")
    sup = sup_mod.Supervisor(
        [sys.executable, child],
        child_metrics=str(tmp_path / "external.jsonl"),
        metrics_jsonl=str(tmp_path / "sup.jsonl"),
        max_restarts=2, sleep_fn=lambda s: None, log=lambda *a: None)
    assert not sup._child_owns_metrics
    assert sup.run() == 0                       # wrapper never saw the flag
    assert len(argv_log.read_text().splitlines()) == 2
    assert sup._metrics_path(1) == str(tmp_path / "external.jsonl")


def test_supervisor_no_resume_and_drop_flags(tmp_path):
    """Serving-child generalization: resume=False never injects
    --resume even with a checkpoint present, and drop_flags_on_restart
    strips a one-shot drill flag from restart attempts (attempt 0 keeps
    it — the drill must fire once)."""
    sup_mod = _load_supervisor()
    assert sup_mod._strip_flag(
        ["a", "--inject-fault", "sigterm@4", "b"], "--inject-fault") \
        == ["a", "b"]
    assert sup_mod._strip_flag(
        ["a", "--inject-fault=crash@2"], "--inject-fault") == ["a"]
    assert sup_mod._strip_flag(["a"], "--inject-fault") == ["a"]
    # a store_true flag must not swallow the following argument
    assert sup_mod._strip_flag(
        ["--no-drain", "--metrics-jsonl", "out.jsonl"], "--no-drain") \
        == ["--metrics-jsonl", "out.jsonl"]
    assert sup_mod._strip_flag(["x", "--no-drain"], "--no-drain") == ["x"]
    (tmp_path / "ck" / "5").mkdir(parents=True)
    sup = sup_mod.Supervisor(
        ["child", "--inject-fault", "sigterm@4"],
        checkpoint_dir=str(tmp_path / "ck"),
        resume=False, drop_flags_on_restart=["--inject-fault"],
        log=lambda *a: None)
    sup._attempt_offset = 0
    argv0 = sup._launch_argv(0)
    argv1 = sup._launch_argv(1)
    assert "--resume" not in argv0 and "--resume" not in argv1
    assert "--inject-fault" in argv0                 # attempt 0: fires
    assert "--inject-fault" not in argv1             # restarts: stripped
    # default resume path still rewrites (the training contract)
    sup2 = sup_mod.Supervisor(["child"],
                              checkpoint_dir=str(tmp_path / "ck"),
                              log=lambda *a: None)
    sup2._attempt_offset = 0
    assert "--resume" in sup2._launch_argv(0)


# ------------------------------------------------- CLI flag guards

def test_resilience_cli_guards():
    for extra in (["--inject-fault", "bogus@3"],
                  ["--inject-fault", "crash"],
                  ["--save-every-steps", "-1"],
                  ["--save-every-steps", "2"]):       # no --checkpoint-dir
        with pytest.raises(SystemExit):
            train_mod.main(["--arch", "resnet18"] + extra)


# --------------------------------- the acceptance loop, in-process

@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """Uninterrupted 6-step run under the shared config: the loss-trail
    oracle for the supervised run — and the clean-run acceptance check
    (grace armed, zero resilience records emitted).  Made the way the
    supervised child is made, a plain ``python train.py``: in this process
    conftest.py has put the Pallas kernels under the interpreter, the
    child runs their XLA forms, and the two differ in the last bits."""
    path = str(tmp_path_factory.mktemp("resilience_base") / "a.jsonl")
    rc = subprocess.call(
        [sys.executable, os.path.join(REPO, "train.py")] + _args(6)
        + ["--metrics-jsonl", path, "--preempt-grace"])
    assert rc == 0
    records = obs.read_jsonl(path)
    kinds = [r["record"] for r in records]
    assert not any(k in ("preemption", "restart", "resume")
                   for k in kinds)                     # clean run: silent
    summary = records[-1]
    assert summary["record"] == "run_summary" and "aborted" not in summary
    losses = _losses(path)
    assert sorted(losses) == [1, 2, 3, 4, 5, 6]
    return losses


def test_nan_fault_poisons_grads(tmp_path):
    """nan-kind drills poison the step's float batch leaves through the
    CLI: the loss goes NaN at exactly the chosen step (the overflow-
    provenance drill).  (The --save-every-steps wiring on the IMAGE loop
    rides test_diag's existing resnet diagnostics run — no second resnet
    compile here; the LM-loop wiring is line-identical and e2e-covered.)
    """
    path = str(tmp_path / "n.jsonl")
    rc = train_mod.main(_args(2) + ["--metrics-jsonl", path,
                                    "--preempt-grace",
                                    "--inject-fault", "nan@2"])
    assert rc == 0                                     # drill, not crash
    steps = [r for r in obs.read_jsonl(path) if r["record"] == "step"]
    assert len(steps) == 2
    assert not math.isnan(steps[0]["loss"])
    assert math.isnan(steps[1]["loss"])                # poisoned step 2


def test_crash_fault_flight_recorder_forensics(tmp_path):
    """crash-kind drills still reach the flight recorder: crash_dump with
    the injected traceback + aborted summary (the 'forensics' leg)."""
    path = str(tmp_path / "c.jsonl")
    with pytest.raises(FaultInjected):
        train_mod.main(_args(2) + ["--metrics-jsonl", path,
                                   "--flight-recorder",
                                   "--inject-fault", "crash@2"])
    recs = obs.read_jsonl(path)
    assert obs_schema.validate_stream(recs) == []
    crash = next(r for r in recs if r["record"] == "crash_dump")
    assert crash["reason"] == "exception:FaultInjected"
    assert "injected crash at step 2" in crash["traceback"]
    summary = recs[-1]
    assert summary["aborted"] is True
    assert summary["abort_reason"] == "exception:FaultInjected"
    assert len([r for r in recs if r["record"] == "step"]) == 2
    lint = _load_tool("metrics_lint")
    assert lint.lint(path, require_summary=True)[0] == 0


# ----------------------------------- end-to-end under the supervisor

@pytest.mark.resilience
def test_supervised_sigterm_e2e(tmp_path, baseline, capsys):
    """The acceptance bar, end-to-end: --inject-fault sigterm@3 under
    tools/supervise.py yields a preemption record (no crash_dump, an
    un-aborted summary) + exit 75 + exactly one restart, the grace save
    leaves a checkpoint + host-state sidecar at step 3, the resumed
    attempt continues mid-epoch, and the spliced loss trail is
    bit-identical to the uninterrupted run (covers AMP scaler state,
    opt_state, and data-stream position)."""
    # Children inherit the suite's XLA_FLAGS (8-logical-device client):
    # the XLA CPU client's device count perturbs low-bit float reduction
    # order, and the splice assertion below is BIT-exact against the
    # baseline, a child of this process too — the environments match.
    ck = str(tmp_path / "ck")
    sup_path = str(tmp_path / "sup.jsonl")
    child_metrics = str(tmp_path / "child.jsonl")
    child = [sys.executable, os.path.join(REPO, "train.py")] + _args(6) + [
        "--metrics-jsonl", child_metrics, "--preempt-grace",
        "--flight-recorder", "--checkpoint-dir", ck,
        "--inject-fault", "sigterm@3"]
    supervise = _load_tool("supervise")
    rc = supervise.main(["--metrics-jsonl", sup_path,
                         "--max-restarts", "2", "--backoff", "0.1",
                         "--"] + child)
    assert rc == 0

    sup_recs = obs.read_jsonl(sup_path)
    assert obs_schema.validate_stream(sup_recs) == []
    assert [r["record"] for r in sup_recs] == \
        ["run_header", "restart", "resume", "run_summary"]
    restart = sup_recs[1]
    assert restart["exit_code"] == EX_TEMPFAIL == 75   # the wire contract
    assert restart["reason"] == "preemption"
    assert restart["last_step"] == 3 and restart["checkpoint_step"] == 3
    resume = sup_recs[2]
    assert resume["attempt"] == 1 and resume["checkpoint_step"] == 3
    summary = sup_recs[-1]
    assert summary["restart_count"] == 1 and summary["exit_code"] == 0
    assert summary["steps"] == 6

    att0 = obs.read_jsonl(child_metrics)
    assert obs_schema.validate_stream(att0) == []
    assert "crash_dump" not in [r["record"] for r in att0]  # grace path
    pre = next(r for r in att0 if r["record"] == "preemption")
    assert pre["signal"] == "SIGTERM" and pre["step"] == 3
    assert pre["saved"] is True and pre["checkpoint_step"] == 3
    assert att0[-1]["record"] == "run_summary"
    assert "aborted" not in att0[-1]                   # resumable != broken
    att1 = obs.read_jsonl(child_metrics + ".attempt1")
    assert att1[-1]["record"] == "run_summary"
    assert sorted(_losses(child_metrics + ".attempt1")) == [4, 5, 6]
    trail = {**_losses(child_metrics),
             **_losses(child_metrics + ".attempt1")}
    assert trail == baseline                           # bit-identical

    mgr = CheckpointManager(ck)                        # the grace save
    hs = mgr.load_host_state(3)
    assert hs["step_in_epoch"] == 3 and hs["data_index"] == 3
    assert "python_random" in hs
    mgr.close()
    lint = _load_tool("metrics_lint")
    assert lint.lint(child_metrics, steps=3, require_summary=True)[0] == 0
    report = _load_tool("telemetry_report")
    assert report.main([child_metrics]) == 0
    assert report.main([sup_path]) == 0
    rep = capsys.readouterr().out
    assert "PREEMPTED RUN (graceful): SIGTERM at step 3" in rep
    assert "restarts: 1" in rep


@pytest.mark.resilience
def test_supervised_serve_drain_e2e(tmp_path):
    """The serving acceptance bar, end-to-end (ISSUE 5): a SIGTERM'd
    serve.py subprocess admits no new requests, resolves every in-flight
    request, emits serve_drain + an un-aborted serve_summary and exits
    75 (EX_TEMPFAIL); tools/supervise.py treats that as prompt-restart
    (--no-resume, --drop-flag-on-restart stripping the one-shot drill),
    rotates the serve metrics stream, and the restarted attempt serves
    to completion.

    The child runs with --trace (ISSUE 11, same subprocess pair): the
    APEX_TRACE_ID env handoff makes BOTH attempt streams and the
    supervisor's own stream carry ONE trace_id, and the merged
    trace_export timeline renders the drain + restart spans — a
    supervised SIGTERM -> drain -> restart is one continuous story."""
    child_metrics = str(tmp_path / "serve.jsonl")
    sup_path = str(tmp_path / "sup.jsonl")
    child = [sys.executable, os.path.join(REPO, "serve.py"),
             "--requests", "6", "--slots", "2", "--max-len", "16",
             "--prompt-len", "3:5", "--max-new", "3:6", "--stagger", "2",
             "--seed", "7", "--metrics-jsonl", child_metrics, "--trace",
             "--inject-fault", "sigterm@4"]
    supervise = _load_tool("supervise")
    rc = supervise.main(["--metrics-jsonl", sup_path,
                         "--max-restarts", "2", "--backoff", "0.1",
                         "--no-resume",
                         "--drop-flag-on-restart=--inject-fault",
                         "--"] + child)
    assert rc == 0

    sup_recs = obs.read_jsonl(sup_path)
    assert obs_schema.validate_stream(sup_recs) == []
    # no checkpoints, no resumes — just one drain-restart (the trace
    # stratum rides alongside: clock_sync + attempt/restart spans)
    assert [r["record"] for r in sup_recs
            if r["record"] not in ("trace_event", "clock_sync")] == \
        ["run_header", "restart", "run_summary"]
    restart = next(r for r in sup_recs if r["record"] == "restart")
    assert restart["exit_code"] == EX_TEMPFAIL == 75   # the wire contract
    assert restart["reason"] == "preemption"
    assert sup_recs[-1]["restart_count"] == 1
    assert sup_recs[-1]["exit_code"] == 0

    att0 = obs.read_jsonl(child_metrics)               # the drained attempt
    assert obs_schema.validate_stream(att0) == []
    kinds0 = [r["record"] for r in att0]
    assert "crash_dump" not in kinds0                  # grace, not crash
    drain = next(r for r in att0 if r["record"] == "serve_drain")
    assert drain["signal"] == "SIGTERM"
    assert drain["in_flight"] == drain["completed"] + drain["evicted"]
    assert drain["requeued"] >= 1
    summ0 = att0[-1]
    assert summ0["record"] == "serve_summary"
    assert "aborted" not in summ0                      # resumable != broken
    assert summ0["drained"] == drain["requeued"]
    # every request resolved with an explicit status, none admitted
    # after the drain began
    assert summ0["requests"] == 6
    assert summ0["completed"] + summ0["timed_out"] + summ0["drained"] == 6
    assert all(r.get("admitted_step", -1) <= drain["step"]
               for r in att0 if r["record"] == "request_complete")

    att1 = obs.read_jsonl(child_metrics + ".attempt1")  # rotated stream
    assert obs_schema.validate_stream(att1) == []
    kinds1 = [r["record"] for r in att1]
    assert "serve_drain" not in kinds1                 # drill was stripped
    summ1 = att1[-1]
    assert summ1["record"] == "serve_summary"
    assert summ1["completed"] == 6 and summ1["availability"] == 1.0

    lint = _load_tool("metrics_lint")
    assert lint.lint(child_metrics)[0] == 0
    assert lint.lint(child_metrics + ".attempt1")[0] == 0

    # --- cross-restart trace continuity (ISSUE 11) ---------------
    # one trace_id across the drained attempt, the restarted attempt
    # AND the supervisor's own stream (the APEX_TRACE_ID handoff)
    streams = [att0, att1, sup_recs]
    ids = {r["trace_id"] for recs in streams for r in recs
           if r["record"] in ("trace_event", "clock_sync")
           and "trace_id" in r}
    assert len(ids) == 1, ids
    # each stream carries its own clock_sync anchor
    assert all(sum(1 for r in recs if r["record"] == "clock_sync") == 1
               for recs in streams)
    # attempt 0 traced the drain; the supervisor traced the restart
    names0 = [r["name"] for r in att0 if r["record"] == "trace_event"]
    assert "drain" in names0
    sup_names = [r["name"] for r in sup_recs
                 if r["record"] == "trace_event"]
    assert sup_names == ["attempt", "restart", "attempt"]
    # the merged export is ONE structurally-clean timeline holding
    # the drain span and the restart marker
    export = _load_tool("trace_export")
    paths = [child_metrics, child_metrics + ".attempt1", sup_path]
    assert export.main(["--check"] + paths) == 0
    merged = str(tmp_path / "merged.json")
    assert export.main(paths + ["-o", merged]) == 0
    evs = json.load(open(merged))["traceEvents"]
    names = {e["name"] for e in evs}
    assert "drain" in names and "restart" in names and "attempt" in names
    assert len({e["pid"] for e in evs
                if e.get("ph") not in ("M",)}) == 3   # 3 process rows
