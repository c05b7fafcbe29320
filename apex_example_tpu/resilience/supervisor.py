"""Auto-resume supervisor: keep a training run alive across preemptions.

Pure stdlib ON PURPOSE — the supervisor's job is to restart training on
hosts where training just died, including deaths caused by a broken jax
install, so it must not import jax (or anything that transitively does;
graftlint's static ``jax-free`` rule proves this over the whole import
closure — tools/graftlint/imports.py, ISSUE 9).
``tools/supervise.py`` is the CLI; it loads this file by path so even
the package ``__init__`` (which pulls jax) is never imported.

Contract with the child (train.py):

- exit 0              done — the supervisor exits 0;
- exit 75             graceful preemption (``EX_TEMPFAIL``, the
                      ``--preempt-grace`` path): restart promptly
                      (``preempt_delay_s``, default 0 — the capacity is
                      back when the scheduler restarts us);
- any other exit      crash: restart with exponential backoff
                      (``backoff_s * 2^k`` capped at ``backoff_max_s``);
- every restart consumes one unit of the ``max_restarts`` budget — a
  flapping run eventually surfaces as a failure instead of burning quota
  forever.

On each launch attempt the child argv is rewritten:

- ``--resume <checkpoint_dir>`` is inserted (or its value replaced)
  whenever the checkpoint dir holds a step — so attempt 0 also resumes
  if a previous supervisor incarnation left a checkpoint behind;
- ``--metrics-jsonl PATH`` becomes ``PATH.attempt<K>`` for K >= 1, so
  every attempt leaves an intact, independently-lintable stream (a
  JsonlSink truncates at open — rewriting would destroy attempt K-1's
  forensics).  A RELAUNCHED supervisor continues the numbering past
  whatever ``PATH``/``PATH.attempt*`` files already exist, so a
  previous incarnation's forensics survive too.

The supervisor keeps its OWN telemetry stream (``metrics_jsonl``):
``run_header`` (platform "supervisor"), a ``resume`` record per
checkpoint-resumed launch, a ``restart`` record per restart decision
(exit code, reason, the v10 exit ``classification`` —
``preempted``/``crashed``/``stall_killed``, the field fleet tooling
keys on — backoff, the child's last step tailed from its metrics
JSONL), and a closing ``run_summary`` carrying ``restart_count`` —
schema v10 (obs/schema.py; hard-coded here to stay import-free).

SIGTERM/SIGINT to the supervisor forward to the child and stop the
restart loop: the child runs its own grace path, the supervisor exits
with the child's status (75 if the child saved — a supervisor-of-
supervisors can resume the whole tree).

Trace continuity (schema v9, obs/trace.py): every child launches with
``APEX_TRACE_ID`` set (inherited from our own environment when a
grand-supervisor set it, else our run id), so the attempt streams of a
``--trace`` child all carry ONE trace_id — a SIGTERM -> drain ->
restart renders as one continuous timeline when
``tools/trace_export.py`` merges them.  When the child argv carries
``--trace`` the supervisor also emits its own side of the story into
its stream: a ``clock_sync`` anchor, an X "attempt" span per child
lifetime and an "i" restart marker per restart decision (timestamps
are ``perf_counter``, like every trace event; the wall clock stays in
the records' ``time`` fields only).

The contract is child-agnostic: serve.py's graceful drain exits the
same 75, so the supervisor restarts a drained server promptly and a
crashed one with backoff.  Serving children differ in two ways —
``resume=False`` skips the ``--resume`` rewrite (serve.py has no resume
concept), and ``drop_flags_on_restart=['--inject-fault']`` strips a
one-shot drill from restart attempts (a served run restarts from tick
0, so the exact-tick fault would otherwise re-fire every attempt).
Metrics rotation and stall-kill work unchanged; a serve stream has no
``step`` records, so ``last_step`` simply stays unreported.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import uuid
from typing import Any, Dict, List, Optional

# Keep in sync with apex_example_tpu/obs/schema.py (SCHEMA_VERSION) and
# resilience/preemption.py (EX_TEMPFAIL) — this module must not import
# either (jax-free contract; same for obs/trace.py's APEX_TRACE_ID).
SCHEMA = 18
EX_TEMPFAIL = 75
TRACE_ID_ENV = "APEX_TRACE_ID"


def latest_checkpoint_step(directory: Optional[str]) -> Optional[int]:
    """Largest orbax step in ``directory`` (step dirs are bare integers),
    without importing orbax: the supervisor only needs to know *whether*
    and *what* to resume — the child does the restoring."""
    if not directory or not os.path.isdir(directory):
        return None
    steps = [int(name) for name in os.listdir(directory)
             if name.isdigit()
             and os.path.isdir(os.path.join(directory, name))]
    return max(steps) if steps else None


_TAIL_BYTES = 256 * 1024


def tail_last_step(path: Optional[str]) -> Optional[int]:
    """Last ``step`` record's step number in a metrics JSONL, or None.
    Reads a bounded tail of the file, not the whole thing — the runs
    the supervisor exists for write one record per optimizer step, and
    a restart decision must not pay a multi-hundred-MB front-to-back
    parse.  Tolerates a torn final line (a killed writer's legitimate
    state) and the torn FIRST line of the tail window."""
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            size = fh.tell()
            fh.seek(max(0, size - _TAIL_BYTES))
            chunk = fh.read().decode("utf-8", errors="replace")
    except OSError:  # pragma: no cover
        return None
    for line in reversed(chunk.splitlines()):
        line = line.strip()
        if not line or '"step"' not in line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and rec.get("record") == "step":
            return int(rec.get("step", 0))
    return None


def _set_flag(argv: List[str], flag: str, value: str) -> List[str]:
    """Return argv with ``flag value`` set: replaces an existing
    ``--flag v`` / ``--flag=v`` occurrence, appends otherwise."""
    out: List[str] = []
    i, found = 0, False
    while i < len(argv):
        arg = argv[i]
        if arg == flag and i + 1 < len(argv):
            out.extend([flag, value])
            i, found = i + 2, True
        elif arg.startswith(flag + "="):
            out.append(f"{flag}={value}")
            i, found = i + 1, True
        else:
            out.append(arg)
            i += 1
    if not found:
        out.extend([flag, value])
    return out


def _get_flag(argv: List[str], flag: str) -> Optional[str]:
    for i, arg in enumerate(argv):
        if arg == flag and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith(flag + "="):
            return arg.split("=", 1)[1]
    return None


def _strip_flag(argv: List[str], flag: str) -> List[str]:
    """Return argv with every ``flag value`` / ``flag=value`` / bare
    ``flag`` occurrence removed (used by ``drop_flags_on_restart`` —
    e.g. a one-shot ``--inject-fault`` drill that must not re-fire on
    the restarted attempt: a served run restarts from tick 0, so unlike
    a resumed training run the exact-step match would fire again).  The
    following token is only consumed when it is not itself a flag, so
    stripping a store_true flag never swallows an unrelated argument."""
    out: List[str] = []
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg == flag:
            i += 1
            if i < len(argv) and not argv[i].startswith("-"):
                i += 1                  # the flag's value
        elif arg.startswith(flag + "="):
            i += 1
        else:
            out.append(arg)
            i += 1
    return out


class _Stream:
    """Minimal JSONL writer (the supervisor cannot use obs.JsonlSink —
    jax-free contract).  One file, truncated at first write, flushed per
    record, compact separators like the sink's."""

    def __init__(self, path: Optional[str]):
        self.path = path
        self._fh = None

    def write(self, rec: Dict[str, Any]) -> None:
        if self.path is None:
            return
        if self._fh is None:
            parent = os.path.dirname(self.path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._fh = open(self.path, "w")
        self._fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
        self._fh.flush()

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


class Supervisor:
    """Run a training command as a child process; restart until done.

    ``child_argv`` is the full command (``[python, train.py, ...]``).
    ``checkpoint_dir``/``child_metrics`` default from the child's own
    ``--checkpoint-dir``/``--metrics-jsonl`` flags when present.
    ``sleep_fn`` is injectable for tests.
    """

    def __init__(self, child_argv: List[str],
                 checkpoint_dir: Optional[str] = None,
                 metrics_jsonl: Optional[str] = None,
                 child_metrics: Optional[str] = None,
                 max_restarts: int = 3,
                 backoff_s: float = 1.0,
                 backoff_max_s: float = 60.0,
                 preempt_delay_s: float = 0.0,
                 stall_kill_s: float = 0.0,
                 resume: bool = True,
                 drop_flags_on_restart: Optional[List[str]] = None,
                 sleep_fn=time.sleep,
                 log=print):
        if not child_argv:
            raise ValueError("supervisor needs a child command")
        self.child_argv = list(child_argv)
        # resume=False: never rewrite --resume (children without a resume
        # concept — serve.py restores params via its own flags and would
        # reject an injected --resume).  drop_flags_on_restart: child
        # flags stripped from every restart attempt's argv (one-shot
        # fault drills).
        self.resume = bool(resume)
        self.drop_flags_on_restart = list(drop_flags_on_restart or [])
        self.checkpoint_dir = checkpoint_dir \
            or _get_flag(self.child_argv, "--checkpoint-dir")
        # An EXPLICIT --child-metrics always wins for tailing (the child
        # may be a wrapper whose own --metrics-jsonl is not where the
        # real stream lands); the child's flag is only the default.
        self._explicit_tail = child_metrics
        self.child_metrics = child_metrics \
            or _get_flag(self.child_argv, "--metrics-jsonl")
        self.max_restarts = int(max_restarts)
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.preempt_delay_s = float(preempt_delay_s)
        self.stall_kill_s = float(stall_kill_s)
        self.sleep_fn = sleep_fn
        self.log = log
        self.run_id = uuid.uuid4().hex[:12]
        self.restart_count = 0
        self._stream = _Stream(metrics_jsonl)
        # Cross-restart trace continuity: children join OUR trace (or
        # the one a grand-supervisor handed us) via the env; our own
        # trace events are only emitted when the child actually traces.
        self.trace_id = os.environ.get(TRACE_ID_ENV) or self.run_id
        self._tracing = any(a == "--trace" for a in self.child_argv)
        self._trace_synced = False
        self._stop = False
        self._child: Optional[subprocess.Popen] = None
        self._stall_killed = False
        # Rotating --metrics-jsonl per attempt is only legal when the
        # CHILD's own argv carries the flag — and rotation bases on THAT
        # value, never on ``child_metrics`` (which --child-metrics may
        # override to a different, tail-only location).  A path supplied
        # via --child-metrics alone is tail-only (the child may be a
        # wrapper that rejects unknown flags).
        self._child_metrics_flag = _get_flag(self.child_argv,
                                             "--metrics-jsonl")
        self._child_owns_metrics = self._child_metrics_flag is not None
        if self._explicit_tail == self._child_metrics_flag:
            # Same path as the child's own flag: not a wrapper redirect,
            # so tailing must FOLLOW the per-attempt rotation or every
            # restarted child would be watched at a file it no longer
            # writes (--stall-kill would kill healthy children).
            self._explicit_tail = None
        self._attempt_offset = 0            # set by run(): see below

    # --------------------------------------------------------- records

    def _header(self) -> None:
        self._stream.write({
            "record": "run_header", "schema": SCHEMA, "time": time.time(),
            "run_id": self.run_id, "num_devices": 0, "process_index": 0,
            "platform": "supervisor",
            "config": {"checkpoint_dir": self.checkpoint_dir,
                       "child_metrics": self.child_metrics,
                       "max_restarts": self.max_restarts,
                       "backoff_s": self.backoff_s,
                       "backoff_max_s": self.backoff_max_s,
                       "preempt_delay_s": self.preempt_delay_s,
                       "stall_kill_s": self.stall_kill_s},
            "argv": [str(a) for a in self.child_argv]})

    def _summary(self, exit_code: int, last_step: Optional[int]) -> None:
        self._stream.write({
            "record": "run_summary", "time": time.time(),
            "steps": int(last_step or 0), "overflow_count": 0,
            "restart_count": self.restart_count,
            "exit_code": int(exit_code)})

    def _trace_event(self, ph: str, name: str, ts: float,
                     dur: Optional[float] = None,
                     args: Optional[Dict[str, Any]] = None) -> None:
        """Schema-v9 trace_event into the supervisor's own stream
        (hard-coded like every record here — the jax-free contract
        forbids importing obs/trace.py's Tracer, not matching it).
        ``ts``/``dur`` are perf_counter seconds; the lazy clock_sync
        anchors them to the wall clock for the exporter."""
        if not self._tracing:
            return
        if not self._trace_synced:
            self._stream.write({
                "record": "clock_sync", "time": time.time(),
                "ts": time.perf_counter(), "trace_id": self.trace_id,
                "run_id": self.run_id})
            self._trace_synced = True
        rec: Dict[str, Any] = {
            "record": "trace_event", "ph": ph, "name": name, "ts": ts,
            "tid": "supervisor", "trace_id": self.trace_id,
            "run_id": self.run_id}
        if dur is not None:
            rec["dur"] = dur
        if args:
            rec["args"] = args
        self._stream.write(rec)

    # ----------------------------------------------------------- child

    def _existing_attempt_offset(self) -> int:
        """First attempt index whose stream file does not exist yet.  A
        RELAUNCHED supervisor (host reboot, operator re-run) must not
        let its attempt-0 child truncate a previous incarnation's
        forensics — the JsonlSink truncates at open, so numbering
        continues past whatever is already on disk."""
        if not self._child_owns_metrics:
            return 0
        base = self._child_metrics_flag
        # Scan the directory, not a contiguous probe: a predecessor may
        # have left .attempt2 without base or .attempt1 (its own offset,
        # or a child that died before opening its stream).
        found = [0] if os.path.exists(base) else []
        parent = os.path.dirname(base) or "."
        prefix = os.path.basename(base) + ".attempt"
        try:
            names = os.listdir(parent)
        except OSError:  # pragma: no cover
            names = []
        for name in names:
            if name.startswith(prefix) and name[len(prefix):].isdigit():
                found.append(int(name[len(prefix):]))
        return max(found) + 1 if found else 0

    def _flag_path(self, attempt: int) -> str:
        """Where attempt K's child writes (its own --metrics-jsonl,
        rotated past both earlier attempts AND earlier incarnations)."""
        n = attempt + self._attempt_offset
        return self._child_metrics_flag if n == 0 \
            else f"{self._child_metrics_flag}.attempt{n}"

    def _metrics_path(self, attempt: int) -> Optional[str]:
        """Where attempt K's stream is TAILED from: an explicit
        --child-metrics always wins; otherwise the child's own rotated
        flag path; None when neither names a file."""
        if self._explicit_tail:
            return self._explicit_tail
        if not self._child_owns_metrics:
            return None
        return self._flag_path(attempt)

    def _launch_argv(self, attempt: int) -> List[str]:
        argv = list(self.child_argv)
        ckstep = latest_checkpoint_step(self.checkpoint_dir)
        # Records and logs carry the incarnation-GLOBAL attempt index so
        # they match the .attempt<N> stream filenames after a supervisor
        # relaunch (offset > 0).
        n = attempt + self._attempt_offset
        if n > 0:
            for flag in self.drop_flags_on_restart:
                argv = _strip_flag(argv, flag)
        if not self.resume:
            ckstep = None
        if ckstep is not None:
            argv = _set_flag(argv, "--resume", self.checkpoint_dir)
            self._stream.write({
                "record": "resume", "time": time.time(),
                "run_id": self.run_id, "attempt": n,
                "checkpoint_step": ckstep,
                "resume_dir": self.checkpoint_dir})
            self.log(f"supervisor: attempt {n} resumes from "
                     f"{self.checkpoint_dir} (step {ckstep})")
        if self._child_owns_metrics and attempt + self._attempt_offset > 0:
            argv = _set_flag(argv, "--metrics-jsonl",
                             self._flag_path(attempt))
        return argv

    def _wait(self, metrics_path: Optional[str]) -> int:
        """Wait for the child; with ``stall_kill_s`` > 0 AND a child
        metrics path to watch, SIGKILL a child whose stream stops
        advancing (the 'hang' fault's backstop — a wedged device never
        exits on its own).  Without a metrics path there is nothing to
        measure progress by, so stall-kill stays disarmed rather than
        killing every child that merely outlives the deadline."""
        child = self._child
        t_start = time.time()
        watch = self.stall_kill_s > 0 and metrics_path is not None
        if not watch:
            # Nothing to measure progress by: block in wait() instead of
            # polling for hours.  Signal forwarding still works — the
            # handler signals the child, whose exit unblocks the wait.
            return child.wait()
        while True:
            rc = child.poll()
            if rc is not None:
                return rc
            # File not created yet counts from launch: a child that
            # never opens its stream within the deadline is as wedged
            # as one that stopped writing to it.
            last = t_start
            if os.path.exists(metrics_path):
                try:
                    last = max(last, os.path.getmtime(metrics_path))
                except OSError:  # pragma: no cover
                    pass
            if time.time() - last > self.stall_kill_s:
                self.log(f"supervisor: no progress for "
                         f"{self.stall_kill_s:.0f}s, killing child")
                # Provenance for the restart record: reason 'stall'
                # means WE killed it — an external SIGKILL (OOM killer,
                # operator) is a plain crash.
                self._stall_killed = True
                child.kill()
                child.wait()
                return child.returncode
            time.sleep(0.2)

    # ------------------------------------------------------------- run

    def _forward_signal(self, signum, frame) -> None:
        self._stop = True
        if self._child is not None and self._child.poll() is None:
            try:
                self._child.send_signal(signum)
            except OSError:  # pragma: no cover
                pass

    def run(self) -> int:
        self._header()
        self._attempt_offset = self._existing_attempt_offset()
        if self._attempt_offset:
            self.log(f"supervisor: streams from a previous incarnation "
                     f"found; new attempts write from "
                     f".attempt{self._attempt_offset}")
        prev_handlers = {}
        if hasattr(signal, "SIGTERM"):
            import threading
            if threading.current_thread() is threading.main_thread():
                for sig in (signal.SIGTERM, signal.SIGINT):
                    try:
                        prev_handlers[sig] = signal.signal(
                            sig, self._forward_signal)
                    except (ValueError, OSError):  # pragma: no cover
                        pass
        attempt = 0
        crash_restarts = 0
        rc = 1
        last_step_seen: Optional[int] = None
        try:
            while True:
                if self._stop:
                    # A stop signal that arrived with no child alive
                    # (during the backoff sleep, or between launches)
                    # must not spawn another attempt.
                    self.log("supervisor: stopping (signal received), "
                             "no further restarts")
                    return rc
                argv = self._launch_argv(attempt)
                metrics_path = self._metrics_path(attempt)
                self._stall_killed = False
                t_launch = time.time()
                t_launch_perf = time.perf_counter()
                # Children join the supervisor's trace: a --trace
                # child's Tracer picks the id up from the env, so a
                # drain -> restart renders as ONE timeline across the
                # attempt streams (obs/trace.py).
                child_env = dict(os.environ)
                child_env[TRACE_ID_ENV] = self.trace_id
                self._child = subprocess.Popen(argv, env=child_env)
                if self._stop:
                    # A stop signal that raced the launch (after the
                    # loop-top check, before Popen) was forwarded to a
                    # child that no longer existed; deliver it to this
                    # one so its grace path still runs.
                    try:
                        self._child.send_signal(signal.SIGTERM)
                    except OSError:  # pragma: no cover
                        pass
                rc = self._wait(metrics_path)
                self._trace_event(
                    "X", "attempt", t_launch_perf,
                    dur=time.perf_counter() - t_launch_perf,
                    args={"attempt": attempt + self._attempt_offset,
                          "exit_code": int(rc)})
                # Only trust a tail the CHILD just wrote: a file whose
                # mtime predates this launch is a previous attempt's (or
                # a previous supervisor incarnation's) — a child that
                # died before opening its stream made no progress.
                last_step = None
                if metrics_path and os.path.exists(metrics_path):
                    try:
                        fresh = os.path.getmtime(metrics_path) \
                            >= t_launch - 1.0
                    except OSError:  # pragma: no cover
                        fresh = False
                    if fresh:
                        last_step = tail_last_step(metrics_path)
                if last_step is not None:
                    last_step_seen = last_step
                ckstep = latest_checkpoint_step(self.checkpoint_dir)
                if rc == 0:
                    self.log(f"supervisor: child done after "
                             f"{self.restart_count} restart(s)")
                    return 0
                if self._stop:
                    self.log(f"supervisor: stopping (forwarded signal), "
                             f"child exited {rc}")
                    return rc
                if self.restart_count >= self.max_restarts:
                    self.log(f"supervisor: restart budget "
                             f"({self.max_restarts}) exhausted, child "
                             f"exited {rc}")
                    return rc
                if rc == EX_TEMPFAIL:
                    reason, backoff = "preemption", self.preempt_delay_s
                    classification = "preempted"
                else:
                    reason = "stall" if self._stall_killed else "crash"
                    classification = "stall_killed" if self._stall_killed \
                        else "crashed"
                    backoff = min(self.backoff_s * (2 ** crash_restarts),
                                  self.backoff_max_s)
                    crash_restarts += 1
                # v10: the exit classification rides the restart record
                # so fleet tooling (fleet/replica.py's health tail,
                # tools/fleet_report.py) can tell a drain from a crash
                # without re-parsing the child's own stream.
                rec: Dict[str, Any] = {
                    "record": "restart", "time": time.time(),
                    "run_id": self.run_id,
                    "attempt": attempt + self._attempt_offset,
                    "exit_code": int(rc), "reason": reason,
                    "classification": classification,
                    "backoff_s": float(backoff)}
                if last_step is not None:
                    rec["last_step"] = last_step
                if ckstep is not None:
                    rec["checkpoint_step"] = ckstep
                self._stream.write(rec)
                self._trace_event(
                    "i", "restart", time.perf_counter(),
                    args={"attempt": attempt + self._attempt_offset,
                          "exit_code": int(rc), "reason": reason,
                          "backoff_s": float(backoff)})
                self.log(f"supervisor: child exited {rc} ({reason}) at "
                         f"step {last_step if last_step is not None else '?'}"
                         f", checkpoint at "
                         f"{ckstep if ckstep is not None else 'none'}; "
                         f"restarting in {backoff:.1f}s "
                         f"({self.restart_count + 1}/{self.max_restarts})")
                if backoff > 0:
                    self.sleep_fn(backoff)
                self.restart_count += 1
                attempt += 1
        finally:
            # The last step any attempt ACTUALLY reached (freshness-
            # gated above) — never a stale file's count, and never an
            # earlier attempt's by accident (a stop during backoff has
            # already advanced `attempt` past the last launch).
            self._summary(rc, last_step_seen)
            self._stream.close()
            for sig, prev in prev_handlers.items():
                try:
                    signal.signal(sig, prev)
                except (ValueError, OSError):  # pragma: no cover
                    pass


def main(argv: Optional[List[str]] = None) -> int:  # pragma: no cover
    """Module-level entry so ``python -m`` style invocation works when
    loaded by path; the real CLI (argparse surface) is tools/supervise.py.
    """
    sys.stderr.write("use tools/supervise.py\n")
    return 2
