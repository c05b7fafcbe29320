"""Stall watchdog: a hung run emits evidence instead of nothing.

A deadlocked collective, an unresponsive device, or a host-side hang
leaves the telemetry stream silent — the worst possible signal.  The
watchdog is a daemon thread that watches the gap since the last completed
step; when the gap exceeds a configurable deadline it

- dumps every thread's python stack (what IS the host waiting on?),
- writes a schema-valid ``stall`` record to the run's JSONL sink, and
- optionally arms a one-shot profiler trace (``trace_dir``), so the
  device timeline of the stall itself gets captured.

One stall record per gap: after firing, the watchdog stays quiet until a
step completes (which also stops the armed trace — the "window" is
stall-start to first-recovered-step), then re-arms for the next gap.  A
clean ``close()`` disarms it so a run that simply *ends* never reads as
a stall.

The deadline includes the first step's trace+compile time — size it
accordingly (or start the clock late by calling ``notify_step(0)`` after
warmup).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from apex_example_tpu.obs import metrics as metrics_lib
from apex_example_tpu.obs.flight import format_thread_stacks


class StallWatchdog:
    """Host-side stall detector bound to a run's JSONL sink.

    Wire-up shape (what train.make_telemetry does)::

        watchdog = StallWatchdog(sink, deadline_s=120)
        watchdog.start()
        emitter.add_observer(watchdog.on_record)   # per-step heartbeat
        ...
        watchdog.close()                           # clean exit: disarm
    """

    def __init__(self, sink: metrics_lib.JsonlSink, deadline_s: float,
                 run_id: Optional[str] = None,
                 trace_dir: Optional[str] = None,
                 poll_s: Optional[float] = None,
                 clock=time.perf_counter):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.sink = sink
        self.deadline_s = float(deadline_s)
        self.run_id = run_id
        self.trace_dir = trace_dir
        self._clock = clock
        # Poll fast enough to resolve the deadline without busy-waiting.
        self._poll_s = poll_s if poll_s is not None \
            else min(max(self.deadline_s / 4.0, 0.01), 1.0)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._last = clock()                    # guarded-by: _lock
        self._last_step = 0                     # guarded-by: _lock
        self._fired = False                     # guarded-by: _lock
        self._tracing = False                   # guarded-by: _lock
        self._trace_used = False                # watchdog thread only
        self.stall_count = 0                    # guarded-by: _lock
        self._thread = threading.Thread(target=self._run,
                                        name="apex-stall-watchdog",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    # ------------------------------------------------------- heartbeat

    def on_record(self, record, metrics=None) -> None:
        """TelemetryEmitter observer form of :meth:`notify_step`."""
        if record.get("record") == "step":
            self.notify_step(int(record.get("step", 0)))

    def notify_step(self, step: int) -> None:
        """A step completed: reset the deadline clock and re-arm."""
        with self._lock:
            self._last = self._clock()
            self._last_step = step
            self._fired = False
            was_tracing, self._tracing = self._tracing, False
        if was_tracing:
            self._stop_trace()

    # ---------------------------------------------------------- thread

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            # Check and mark under ONE lock hold: setting _fired outside
            # the gap check would let a notify_step landing in between
            # have its re-arm clobbered, permanently disarming the
            # watchdog for the NEXT (real) stall.
            with self._lock:
                gap = self._clock() - self._last
                step = self._last_step
                fire = gap >= self.deadline_s and not self._fired
                if fire:
                    self._fired = True
                    # Count under the SAME lock hold as the fire
                    # decision: the watchdog thread writes this while
                    # the main thread polls it, and the unguarded
                    # increment was graftlint's first lock-discipline
                    # true positive (ISSUE 9).
                    self.stall_count += 1
            if fire:
                self._emit_stall(gap, step)

    def _emit_stall(self, gap: float, step: int) -> None:
        rec = {"record": "stall",
               "time": metrics_lib.now(),
               "seconds_since_step": round(gap, 3),
               "step": int(step),
               "deadline_s": self.deadline_s,
               "thread_stacks": format_thread_stacks()}
        if self.run_id:
            rec["run_id"] = self.run_id
        if self.trace_dir and not self._trace_used:
            # One-shot profiler window: stall-start .. first recovered
            # step (or close()).  Never re-armed — a flapping run must
            # not accrete trace directories.
            try:
                import jax
                jax.profiler.start_trace(self.trace_dir)
            except Exception:
                pass
            else:
                with self._lock:
                    self._tracing = True
                self._trace_used = True
                rec["trace_dir"] = self.trace_dir
        self.sink.write(rec)

    def _stop_trace(self) -> None:
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:  # pragma: no cover
            pass

    # ----------------------------------------------------------- close

    def close(self) -> None:
        """Clean-exit disarm: stop the thread; a run that ends is not a
        stall.  Stops a still-armed trace so the capture isn't lost."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)
        with self._lock:
            was_tracing, self._tracing = self._tracing, False
        if was_tracing:
            self._stop_trace()
