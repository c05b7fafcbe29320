"""Hot-path overhead attribution — pure stdlib, importable without jax.

Splits every serve tick / train step into named phases and folds each
phase into the obs/slo.py log-bucket sketches (ISSUE 17), so the
question ROADMAP item 5 will be judged on — "host-side gap between
device spans -> ~0" — is measurable before anyone refactors the loop.

Phases
------
A serve tick (serve/engine.py ``step``) decomposes into::

    admit             expire/shed/deadline-evict + queue admission
    dispatch_enqueue  host marshalling + handing the step to the
                      runtime (up to the point the compiled call
                      returns its unresolved outputs)
    device_wait       the host sync on the sampled tokens
                      (``np.asarray``): the device's run plus the
                      device-to-host copy (on CPU jax dispatch is
                      synchronous, so device_wait reads ~0 and the
                      device time hides in dispatch_enqueue; on a real
                      TPU the split is the whole point — see README)
    harvest           per-slot token handling, eviction, completion
    spool_io          handoff spool writes inside harvest (measured
                      around ``handoff_sink`` and subtracted from
                      harvest so disagg IO is not mistaken for
                      scheduler cost)
    telemetry         gauge emission, SLO fold

and a train step (train.py main loop) into::

    data_wait   batch_fn / input pipeline
    dispatch    the compiled train-step call up to its return
    device      explicit block_until_ready on state + metrics
    telemetry   emitter.on_step (blocking metric fetch) + printing
    checkpoint  the save-every-steps window (0.0 when skipped)

The caller measures ``wall_ms`` independently (one perf_counter pair
around the whole tick) and passes the phases it timed; because the
engine's boundaries are contiguous timestamps the phase sum telescopes
to the wall time — ``tools/perf_ledger.py`` enforces agreement within
1% as a tamper check.

Records
-------
``tick_profile``      one per sampled tick (every ``sample_every``-th;
                      sampling bounds stream growth at high tick
                      rates) — per-phase milliseconds, the tick wall
                      time and its ``host_gap_ms`` (wall minus the
                      device phase).  Carries a perf_counter ``ts`` so
                      trace_export can render a host-gap counter track
                      against the clock_sync anchor.
``overhead_summary``  one per run — per-phase cumulative totals +
                      sketch summaries (count/p50/p90/p99/min/max),
                      the cumulative ``host_gap_ms`` and the
                      ``host_overhead_frac`` = host_gap / wall that
                      replica heartbeats advertise and fleet_report
                      ranks.

Self-contained BY CONTRACT (the obs/slo.py pattern): stdlib-only, so
thin tools load it by FILE PATH without executing the jax-carrying
package ``__init__``.  graftlint's jax-free rule names it in
CONTRACT_FILES; keep it that way.  The sketch helpers come from
obs/slo.py — imported relatively when the package is live, loaded by
file path when this module itself was file-path-loaded.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

try:
    from .slo import (DEFAULT_ALPHA, sketch_add, sketch_new,
                      sketch_summary)
except ImportError:                      # file-path load: no package
    import importlib.util
    import os

    def _load_slo():
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "slo.py")
        spec = importlib.util.spec_from_file_location("_tickprof_slo",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    _slo = _load_slo()
    DEFAULT_ALPHA = _slo.DEFAULT_ALPHA
    sketch_add = _slo.sketch_add
    sketch_new = _slo.sketch_new
    sketch_summary = _slo.sketch_summary

SERVE_PHASES = ("admit", "dispatch_enqueue", "device_wait", "harvest",
                "spool_io", "telemetry")
# The serve tick's phases as serve/engine.py reads them: contiguous, in
# this order, each boundary taken once (obs/spans.py ``Phases``).  Under
# these names they are ``jax.profiler.TraceAnnotation`` host events on
# the device trace's clock, children of one ENGINE_TICK event; the value
# is the ``tick_profile`` phase each folds into (``spool_io`` is cut out
# of engine.harvest by its own pair of readings round the handoff sink).
ENGINE_TICK = "engine.tick"
ENGINE_PHASES = {
    "engine.admit": "admit",              # mature/expire/shed/evict/admit
    "engine.marshal": "dispatch_enqueue",  # the packed array, key, one put
    "engine.enqueue": "dispatch_enqueue",  # the compiled call's return
    "engine.sync": "device_wait",         # device run + device-to-host
    "engine.harvest": "harvest",          # per-slot loop, finishes
    "engine.gauges": "telemetry",         # histograms, gauges, SLO fold
}
# Child spans inside those phases, one level down: every hand-off between
# the tick's host thread and the runtime, and the host work before the
# first.  The value is the phase a child lies in, whole.  A child is an
# annotation only: it moves no boundary and feeds no record.  The
# hand-offs a tick are ENGINE_HANDOFF_SPANS counted as they are made
# (``engine.tick``'s ``handoffs=``): ``engine.enqueue`` is one whole.
ENGINE_HANDOFFS = {
    "engine.build": "engine.marshal",  # the tick's one packed array: host work
    "engine.rng": "engine.marshal",    # the step's key: taken as prepared
    #                                    (ENGINE_KEY_AHEAD), split here only
    #                                    where nothing is (the first step)
    "engine.put": "engine.marshal",    # THE host-to-device put (arg="packed",
    #                                    bytes=): one a tick
    "engine.fetch": "engine.sync",     # one device-to-host read (out=, bytes=);
    #                                    all requested at once, the first waits
}
ENGINE_HANDOFF_SPANS = ("engine.rng", "engine.put", "engine.enqueue",
                        "engine.fetch")
# Inside engine.sync, before the first engine.fetch: the split of the key
# the NEXT step will use, made while the chip runs this tick's program.
# In neither table above: nothing waits for it, so it is no hand-off of
# the count, and the chip is busy under it, so no reader of idle time has
# anything to lay to it.
ENGINE_KEY_AHEAD = "engine.key_ahead"
TRAIN_PHASES = ("data_wait", "dispatch", "device", "checkpoint",
                "telemetry")

# The phase whose time is DEVICE time; everything else is host
# overhead.  host_gap_ms = wall - this phase.
DEVICE_PHASE = {"serve": "device_wait", "train": "device"}

DEFAULT_SAMPLE_EVERY = 16


class TickProfiler:
    """Per-tick phase accounting + cumulative sketches.

    ``observe_tick(ts, wall_ms, **phase_ms)`` folds one tick; every
    ``sample_every``-th call emits a ``tick_profile`` record through
    ``emit`` (a JsonlSink.write or None).  ``summary_record()`` builds
    the closing ``overhead_summary``.
    """

    def __init__(self, kind: str = "serve",
                 sample_every: int = DEFAULT_SAMPLE_EVERY,
                 emit: Optional[Callable[[Dict[str, Any]], Any]] = None,
                 run_id: Optional[str] = None,
                 alpha: float = DEFAULT_ALPHA):
        if kind not in DEVICE_PHASE:
            raise ValueError(f"kind must be serve|train, got {kind!r}")
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, "
                             f"got {sample_every}")
        self.kind = kind
        self.phases = (SERVE_PHASES if kind == "serve"
                       else TRAIN_PHASES)
        self.device_phase = DEVICE_PHASE[kind]
        self.sample_every = int(sample_every)
        self.emit = emit
        self.run_id = run_id
        self.ticks = 0
        self.sampled = 0
        self.wall_ms = 0.0
        self._totals = {p: 0.0 for p in self.phases}
        self._sk = {p: sketch_new(alpha) for p in self.phases}
        self._wall_sk = sketch_new(alpha)
        self._gap_sk = sketch_new(alpha)

    # ------------------------------------------------------------ fold

    def observe_tick(self, ts: float, wall_ms: float,
                     **phase_ms: float) -> Optional[Dict[str, Any]]:
        """Fold one tick.  ``ts``: perf_counter at tick start (the
        trace clock domain); ``wall_ms``: the tick's independently
        measured wall time; keyword args: per-phase milliseconds
        (missing phases count 0.0, unknown phases raise).  Returns the
        emitted ``tick_profile`` record on sampled ticks, else None."""
        unknown = set(phase_ms) - set(self.phases)
        if unknown:
            raise ValueError(f"unknown phase(s) {sorted(unknown)}; "
                             f"{self.kind} phases are {self.phases}")
        wall = float(wall_ms)
        self.wall_ms += wall
        sketch_add(self._wall_sk, wall)
        tick_phases: Dict[str, float] = {}
        for p in self.phases:
            v = float(phase_ms.get(p, 0.0))
            tick_phases[p] = v
            self._totals[p] += v
            sketch_add(self._sk[p], v)
        gap = wall - tick_phases[self.device_phase]
        sketch_add(self._gap_sk, gap)
        tick = self.ticks
        self.ticks += 1
        if self.emit is None or tick % self.sample_every:
            return None
        self.sampled += 1
        rec = {
            "record": "tick_profile",
            "time": time.time(),
            "ts": float(ts),
            "kind": self.kind,
            "tick": tick,
            "wall_ms": wall,
            "host_gap_ms": gap,
            "phases": tick_phases,
        }
        if self.run_id is not None:
            rec["run_id"] = self.run_id
        self.emit(rec)
        return rec

    # ------------------------------------------------------- accessors

    def device_ms(self) -> float:
        """Cumulative device-phase milliseconds."""
        return self._totals[self.device_phase]

    def host_gap_ms(self) -> float:
        """Cumulative wall minus device-phase milliseconds."""
        return self.wall_ms - self.device_ms()

    def host_overhead_frac(self) -> float:
        """host_gap / wall over the whole run (0.0 before any tick)."""
        if self.wall_ms <= 0.0:
            return 0.0
        return self.host_gap_ms() / self.wall_ms

    def phase_summary(self) -> Dict[str, Dict[str, Any]]:
        """phase -> sketch summary + cumulative ``total_ms``."""
        out: Dict[str, Dict[str, Any]] = {}
        for p in self.phases:
            s = sketch_summary(self._sk[p])
            s["total_ms"] = self._totals[p]
            out[p] = s
        return out

    def summary_record(self) -> Dict[str, Any]:
        """The closing ``overhead_summary`` record (schema v15)."""
        rec = {
            "record": "overhead_summary",
            "time": time.time(),
            "kind": self.kind,
            "ticks": self.ticks,
            "wall_ms": self.wall_ms,
            "device_ms": self.device_ms(),
            "host_gap_ms": self.host_gap_ms(),
            "host_overhead_frac": self.host_overhead_frac(),
            "phases": self.phase_summary(),
            "sample_every": self.sample_every,
            "sampled": self.sampled,
            "wall": sketch_summary(self._wall_sk),
            "host_gap": sketch_summary(self._gap_sk),
        }
        if self.run_id is not None:
            rec["run_id"] = self.run_id
        return rec
