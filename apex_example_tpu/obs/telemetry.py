"""Per-step telemetry: the emitter that turns a train loop's metrics dict
into schema-valid JSONL records (obs/schema.py).

The emitter owns the one deliberate cost of telemetry: fetching device
scalars each step is a host sync, so the whole layer is flag-gated
(``--metrics-jsonl``) and the default path never pays it.  Because the
fetch blocks until the step's metrics are materialized, the wall time
measured *after* the fetch includes device execution — that is what
``step_time_ms`` means.

First-step compile time is detected, not measured: the first step's wall
time is trace+compile+execute while steady-state steps are execute-only,
so ``run_summary.compile_est_ms = first_step_ms - median(rest)``.
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Dict, List, Optional

import jax

from apex_example_tpu.obs import metrics as metrics_lib
from apex_example_tpu.obs.schema import SCHEMA_VERSION

# Memory-stats keys worth shipping (device.memory_stats() returns a much
# larger dict on TPU; these are the capacity-planning ones).
_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size")


def device_memory_stats() -> Optional[Dict[str, int]]:
    """Subset of the first local device's memory_stats(), or None where
    the backend doesn't report (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    out = {k: int(stats[k]) for k in _MEMORY_KEYS if k in stats}
    return out or None


def _scalar_metrics(metrics: Dict[str, Any]) -> Dict[str, float]:
    """Fetch every scalar in a step's metrics dict to python floats (this
    is the blocking device sync telemetry pays for)."""
    out = {}
    for key, value in metrics.items():
        try:
            out[key] = float(value)
        except (TypeError, ValueError):
            continue                      # non-scalar aux, skip
    return out


class TelemetryEmitter:
    """Emits run_header / step / run_summary records to a JsonlSink and
    (optionally) a MetricsRegistry + TensorBoardAdapter.

    Usage shape (what train.py does)::

        emitter = TelemetryEmitter(JsonlSink(path), registry=reg)
        emitter.run_header(config=vars(args), arch=args.arch)
        for ...:
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            emitter.on_step(global_step=gs, epoch=e, metrics=metrics,
                            items=batch_items, t_start=t0)
        emitter.close()
    """

    def __init__(self, sink: metrics_lib.JsonlSink,
                 registry: Optional[metrics_lib.MetricsRegistry] = None,
                 memory_every: int = 10):
        self.sink = sink
        self.registry = registry or metrics_lib.MetricsRegistry()
        self.memory_every = memory_every
        self.run_id = uuid.uuid4().hex[:12]
        self._step_times_ms: List[float] = []
        self._overflows = 0
        self._steps = 0
        self._items = 0
        self._t_run0 = time.perf_counter()
        self._closed = False
        # Diagnostics hookup (obs/flight.py, obs/watchdog.py,
        # obs/numerics.py): each callback sees (step_record, raw_metrics)
        # after the record lands in the sink.
        self._observers: List = []

    def add_observer(self, callback) -> None:
        """``callback(record, metrics)`` runs after every emitted step —
        the flight recorder's ring, the watchdog's heartbeat, and the
        numerics monitor all subscribe here so the train loops stay a
        single ``emitter.on_step`` call."""
        self._observers.append(callback)

    def run_header(self, config: Dict[str, Any], argv: Optional[list] = None,
                   **extra) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "record": "run_header",
            "schema": SCHEMA_VERSION,
            "time": metrics_lib.now(),
            "run_id": self.run_id,
            "num_devices": jax.device_count(),
            "num_processes": jax.process_count(),
            "process_index": jax.process_index(),
            "platform": jax.default_backend(),
            "config": {k: v for k, v in config.items()
                       if isinstance(v, (str, int, float, bool, type(None)))},
        }
        if argv is not None:
            rec["argv"] = [str(a) for a in argv]
        rec.update(extra)
        self.sink.write(rec)
        return rec

    def on_step(self, *, global_step: int, epoch: int,
                metrics: Dict[str, Any], items: int,
                t_start: float) -> Dict[str, Any]:
        """Fetch, record, emit one step.  ``t_start`` is the
        ``perf_counter`` taken immediately before the step dispatch; the
        elapsed time is measured after the metric fetch so it covers
        device execution."""
        values = _scalar_metrics(metrics)
        elapsed_ms = (time.perf_counter() - t_start) * 1e3
        self._steps += 1
        self._items += items
        self._step_times_ms.append(elapsed_ms)
        if values.get("grads_finite", 1.0) < 1.0:
            self._overflows += 1

        rec: Dict[str, Any] = {
            "record": "step",
            "time": metrics_lib.now(),
            "step": int(global_step),
            "epoch": int(epoch),
            "step_time_ms": round(elapsed_ms, 3),
            "items_per_sec": round(items / max(elapsed_ms / 1e3, 1e-9), 1),
            "overflow_count": self._overflows,
            # schema-required even when a step builder omits them — the
            # contract fields consumers key on.
            "loss": values.get("loss", 0.0),
            "scale": values.get("scale", 1.0),
        }
        for key in ("grad_norm", "grads_finite", "top1", "ppl",
                    "masked_acc", "lr", "head_rows"):
            if key in values:
                rec[key] = values[key]
        if self.memory_every and (self._steps - 1) % self.memory_every == 0:
            mem = device_memory_stats()
            if mem:
                rec["memory"] = mem

        reg = self.registry
        reg.counter("steps").inc()
        reg.counter("items").inc(items)
        reg.histogram("step_time_ms").observe(elapsed_ms)
        reg.gauge("loss").set(rec["loss"])
        reg.gauge("scale").set(rec["scale"])
        if "grad_norm" in rec:
            reg.gauge("grad_norm").set(rec["grad_norm"])

        self.sink.write(rec)
        for callback in self._observers:
            callback(rec, metrics)
        return rec

    def summary(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "record": "run_summary",
            "time": metrics_lib.now(),
            "steps": self._steps,
            "overflow_count": self._overflows,
        }
        if self._step_times_ms:
            first = self._step_times_ms[0]
            rec["first_step_ms"] = round(first, 3)
            rest = sorted(self._step_times_ms[1:])
            if rest:
                steady = rest[len(rest) // 2]
                rec["steady_step_ms"] = round(steady, 3)
                # first step = trace + compile + execute; steady = execute.
                rec["compile_est_ms"] = round(max(first - steady, 0.0), 3)
            wall_s = time.perf_counter() - self._t_run0
            rec["items_per_sec"] = round(self._items / max(wall_s, 1e-9), 1)
        snap = self.registry.snapshot()
        span_hists = {
            name: summ
            for name, summ in snap.items()
            if name.startswith("span.") and isinstance(summ, dict)}
        if span_hists:
            rec["spans"] = span_hists
        # Measured compile totals (obs/costmodel.py feeds the histogram
        # under --cost-model): the first-vs-steady compile_est_ms above
        # stays as a cross-check, but consumers should prefer these.
        compile_hist = snap.get("compile_time_ms")
        if isinstance(compile_hist, dict) and compile_hist.get("count"):
            rec["compile_events"] = int(compile_hist["count"])
            rec["compile_ms_total"] = round(compile_hist["sum"], 3)
        return rec

    def preemption(self, signal_name: str, *, step: int,
                   checkpoint_step: Optional[int] = None,
                   saved: bool = False) -> Dict[str, Any]:
        """The graceful-preemption record (schema v4; the resilience
        grace path, resilience/preemption.py): written BEFORE the normal
        close, so the stream reads header, steps..., preemption,
        run_summary — and the summary stays un-aborted (a preempted run
        is resumable, not broken)."""
        rec: Dict[str, Any] = {
            "record": "preemption",
            "time": metrics_lib.now(),
            "run_id": self.run_id,
            "signal": str(signal_name),
            "step": int(step),
            "saved": bool(saved),
        }
        if checkpoint_step is not None:
            rec["checkpoint_step"] = int(checkpoint_step)
        self.sink.write(rec)
        return rec

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._steps:
            self.sink.write(self.summary())
        self.sink.close()

    def abort(self, reason: str) -> None:
        """The crash-path close (obs/flight.py): always write the run
        summary — even at 0 steps — marked ``aborted: true``, so stream
        consumers can tell a killed run from one that ended well."""
        if self._closed:
            return
        self._closed = True
        rec = self.summary()
        rec["aborted"] = True
        rec["abort_reason"] = reason
        self.sink.write(rec)
        self.sink.close()
