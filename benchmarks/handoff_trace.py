"""The chip's idle time between two ticks' programs, by the innermost of the
program's own spans that covers it.

``program_trace.tick_gaps`` lays each idle stretch to the serve tick's six
phases.  Since PR 38 the engine names what it does inside two of them, one
level down (``apex_example_tpu/obs/tickprof.py`` ``ENGINE_HANDOFFS``, quoted
below; a test holds the two together): inside ``engine.marshal`` the host's
own work (``engine.build``), the key split (``engine.rng``) and one
``engine.put`` for every array handed to the runtime; inside ``engine.sync``
one ``engine.fetch`` for every output brought back.  This module lays every
idle nanosecond to the innermost span covering it: a child first, then the
phase (what its children leave: "the rest of marshal"), then ``engine.tick``,
then ``harness``.  The stretches are ``tick_gaps``' own (the same runs of the
tick's program, the same busy time, ``program_trace._minus``), so a gap's
parts add up to its ``idle`` there to the nanosecond.

``innermost`` knows no name: it takes any levels of ``(name, start, end)``
spans, innermost first, so that ``breakdown.idle_gaps`` can be pointed at it
(ROADMAP W2).  Everything works on ``program_trace``'s plain form.  The
events' metadata (``arg=``, ``out=``, ``bytes=``, ``handoffs=``) is not in
that form; ``load_meta`` reads it from the file for the notes alone.  A trace
without the children (the parent of PR 38), without a TPU plane or of a
training cell gives ``None`` wherever a reader asks, and nothing raises.
"""

from __future__ import annotations

import functools
import os
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import harness
from benchmarks import program_trace as pt
from benchmarks import trace as trace_lib

# obs/tickprof.py ENGINE_HANDOFFS: the child and the phase it lies in
CHILDREN = {"engine.build": "engine.marshal", "engine.rng": "engine.marshal",
            "engine.put": "engine.marshal", "engine.fetch": "engine.sync"}
# obs/tickprof.py ENGINE_HANDOFF_SPANS: what the engine counts a tick
HANDOFFS = ("engine.rng", "engine.put", "engine.enqueue", "engine.fetch")
META_KEYS = {"engine.put": "arg", "engine.fetch": "out"}

Span = Tuple[str, int, int]
Interval = Tuple[int, int]
Meta = Dict[Tuple[str, int], Dict[str, Any]]


# ---------------------------------------------------------- any spans

def innermost(idle: Sequence[Interval], levels: Sequence[Sequence[Span]],
              rest: str) -> Dict[str, int]:
    """The nanoseconds of ``idle`` (sorted, disjoint intervals) under each
    span name, every nanosecond counted once: under the first of ``levels``
    (innermost first) that has a span covering it, and under ``rest`` if
    none has.  The spans of one level do not overlap one another."""
    parts: Dict[str, int] = {}
    left = list(idle)
    for spans in levels:
        taken = []
        for name, start, end in spans:
            for lo, hi in left:
                lo, hi = max(start, lo), min(end, hi)
                if hi > lo:
                    taken.append((lo, hi))
                    parts[name] = parts.get(name, 0) + hi - lo
        taken.sort()
        left = [piece for whole in left for piece in pt._minus(whole, taken)]
    parts[rest] = sum(hi - lo for lo, hi in left)
    return parts


# ------------------------------------------------------ the serve tick

def child_spans(planes) -> List[Span]:
    """The ``engine.*`` children of the trace, in time order."""
    return [(n, s, s + d) for n, s, d
            in pt._host_events(planes, lambda n: n in CHILDREN)]


def program_runs(planes) -> Tuple[List[Interval], List[Interval]]:
    """As ``program_trace.tick_gaps`` takes them: the runs of the tick's
    program (the module with most device time) and the device's busy
    intervals; two empty lists without a TPU plane."""
    modules = pt._device_lines(planes, trace_lib.MODULES_LINE)
    ops = pt.device_ops(planes)
    if not modules or not ops:
        return [], []
    time_by_name: Dict[str, int] = {}
    for name, _, dur in modules[0]:
        time_by_name[name] = time_by_name.get(name, 0) + dur
    main = max(time_by_name, key=time_by_name.get)
    runs = sorted((s, s + d) for n, s, d in modules[0] if n == main)
    return runs, trace_lib._union([(ev[1], ev[1] + ev[2]) for ev in ops])


def gap_parts(planes, runs_and_busy=None) -> List[Dict[str, int]]:
    """``program_trace.tick_gaps`` one level down: per gap between two
    runs of the tick's program the idle nanoseconds under each child, under
    each phase outside its children, under ``engine.tick`` outside its
    phases and under ``harness``; their sum ``idle`` and the ``period``."""
    runs, busy = runs_and_busy or program_runs(planes)
    ticks = pt.engine_ticks(planes)
    levels = [child_spans(planes),
              [ph for t in ticks for ph in t["phases"]],
              [(pt.ENGINE_TICK, t["start"], t["end"]) for t in ticks]]
    out = []
    for (prev_start, prev_end), (next_start, _) in zip(runs, runs[1:]):
        near = [[sp for sp in spans
                 if sp[2] > prev_end and sp[1] < next_start]
                for spans in levels]
        parts = innermost(pt._minus((prev_end, next_start), busy), near,
                          pt.HARNESS)
        parts["idle"] = sum(parts.values())
        parts["period"] = next_start - prev_start
        out.append(parts)
    return out


def gap_ms_p50(planes, name: str) -> Optional[float]:
    """Median over the traced gaps of the chip's idle time under the spans
    called ``name`` and under nothing inside them, in ms.  ``None`` without
    a device trace and where the trace holds no such span."""
    if planes is None or not pt._host_events(planes, lambda n: n == name):
        return None
    gaps = gap_parts(planes)
    if not gaps:
        return None
    return statistics.median(g.get(name, 0) for g in gaps) / 1e6


def tick_handoffs(planes) -> List[int]:
    """Per traced tick that ran a step, its hand-off events."""
    made = pt._host_events(planes, lambda n: n in HANDOFFS)
    return [sum(1 for _, s, d in made
                if t["start"] <= s and s + d <= t["end"])
            for t in pt.engine_ticks(planes)]


def launch_leads(planes, runs: Optional[List[Interval]] = None
                 ) -> List[Optional[int]]:
    """Per traced tick that ran a step, the nanoseconds from
    ``engine.enqueue`` opening to the start of the tick's program on the
    device (``None``: no run starts inside the tick).  The call cannot
    follow what it launches: a negative lead is the least by which the
    trace's host and device clocks are apart."""
    if runs is None:
        runs, _ = program_runs(planes)
    out = []
    for t in pt.engine_ticks(planes):
        opened = next(s for n, s, _ in t["phases"] if n == "engine.enqueue")
        out.append(next((start - opened for start, _ in runs
                         if t["start"] <= start < t["end"]), None))
    return out


def handoffs_p50(planes) -> Optional[float]:
    """Median over the traced ticks of the hand-offs between the tick's
    host thread and the runtime.  ``None`` without a device trace and on a
    trace without the children (its ``engine.enqueue`` alone is no count)."""
    if planes is None or not pt.device_ops(planes) \
            or not child_spans(planes):
        return None
    counts = tick_handoffs(planes)
    return float(statistics.median(counts)) if counts else None


# ------------------------------------------------------------ the file

def load_meta(path: str) -> Meta:
    """(name, start_ns) -> the metadata of every ``engine.*`` host event of
    one ``.xplane.pb`` that carries any."""
    from jax.profiler import ProfileData
    out: Meta = {}
    for plane in ProfileData.from_file(path).planes:
        if trace_lib.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    stats = dict(e.stats)
                    if stats:
                        out[(e.name, int(e.start_ns))] = stats
    return out


@functools.lru_cache(maxsize=2)
def _noted(path: str, mtime: float) -> None:
    planes = pt.of_run()
    if planes is not None:
        report(planes, load_meta(path) if child_spans(planes) else None)


def of_run() -> Optional[List[Dict[str, Any]]]:
    """``program_trace.of_run()``, with this module's detail printed to the
    run's notes the first time a reader asks."""
    path = trace_lib.find_xplane(os.path.join(harness.ROOT, ".bench_trace"))
    if path is None:
        return None
    _noted(path, os.path.getmtime(path))
    return pt.of_run()


# ------------------------------------------------------------ the notes

def _kind(span: Span, meta: Meta) -> str:
    """``engine.put[tok]`` where the event says which, else its name."""
    key = META_KEYS.get(span[0])
    which = meta.get((span[0], span[1]), {}).get(key) if key else None
    return f"{span[0]}[{which}]" if which is not None else span[0]


def tick_parts(planes, meta: Meta) -> List[Dict[str, int]]:
    """Per traced tick that ran a step, the host nanoseconds of each child
    (by ``arg=``/``out=`` where ``meta`` has it), of each phase outside its
    children, and ``tick``, the whole."""
    kids = child_spans(planes)
    out = []
    for t in pt.engine_ticks(planes):
        parts = {n: e - s for n, s, e in t["phases"]}
        for sp in kids:
            if t["start"] <= sp[1] and sp[2] <= t["end"]:
                kind = _kind(sp, meta)
                parts[kind] = parts.get(kind, 0) + sp[2] - sp[1]
                parts[CHILDREN[sp[0]]] -= sp[2] - sp[1]
        parts["tick"] = t["end"] - t["start"]
        out.append(parts)
    return out


def report(planes, meta: Optional[Meta] = None) -> None:
    """The detail behind the five metrics, for whoever reads the run's
    standard error: means, which add up where medians do not.  A trace
    without the children gets the lines that need none."""
    meta = meta or {}
    runs, busy = program_runs(planes)       # once: a pass over every op
    gaps, old = gap_parts(planes, (runs, busy)), pt.tick_gaps(planes)
    ticks = tick_parts(planes, meta)
    if not gaps or not ticks:
        return
    short = lambda k: k.split(".", 1)[-1] \
        + (" (rest)" if k in CHILDREN.values() else "")
    mean = lambda k, of=gaps: statistics.fmean(g.get(k, 0) for g in of) / 1e6
    med = lambda k: statistics.median(t.get(k, 0) for t in ticks)
    if child_spans(planes):
        keys = list(CHILDREN) + list(pt.ENGINE_PHASES) \
            + [pt.ENGINE_TICK, pt.HARNESS]
        harness.note(
            f"device idle between ticks over {len(gaps)} gaps by innermost "
            f"span, ms mean: {mean('idle'):.3f} = " + " + ".join(
                f"{short(k)} {mean(k):.3f}" for k in keys))
        for phase in sorted(set(CHILDREN.values())):
            kids = [k for k, into in CHILDREN.items() if into == phase]
            harness.note(
                f"of the mean idle under {phase} ({mean(phase, old):.3f} ms "
                "by tick_gaps): " + " + ".join(
                    f"{short(k)} {mean(k):.3f}" for k in kids)
                + f" = {sum(mean(k) for k in kids):.3f}, remainder "
                f"{mean(phase):.3f}")
        counts = tick_handoffs(planes)
        said = [m["handoffs"] for (n, _), m
                in sorted(meta.items(), key=lambda kv: kv[0][1])
                if n == pt.ENGINE_TICK and "handoffs" in m]
        harness.note(
            f"hand-offs a tick over {len(counts)} traced ticks: p50 "
            f"{statistics.median(counts):g}, min {min(counts)}, max "
            f"{max(counts)}; engine.tick's handoffs= says "
            + (f"{statistics.median(said):g} (p50 of {len(said)})" if said
               else "nothing (no metadata read)"))
        kinds = sorted({k for t in ticks for k in t
                        if k.startswith(tuple(CHILDREN))})
        harness.note("children over the traced ticks, host ms p50/max: "
                     + ", ".join(
                         f"{short(k)} {med(k) / 1e6:.3f}/"
                         f"{max(t.get(k, 0) for t in ticks) / 1e6:.3f}"
                         for k in kinds))
    leads = launch_leads(planes, runs)
    known = [v for v in leads if v is not None]
    if known:
        harness.note(
            "the tick's program starts "
            f"{statistics.median(known) / 1e6:.3f} ms (p50; min "
            f"{min(known) / 1e6:.3f}, max {max(known) / 1e6:.3f}) after "
            "engine.enqueue opens"
            + (": the call cannot follow its program, so this trace's "
               "host and device clocks are apart by at least that much, "
               "and marshal's and sync's idle shares are shifted by it"
               if statistics.median(known) < 0 else ""))
    # S14: a stall caught in the traced window gets a name
    usual = med("tick")
    for i, t in enumerate(ticks):
        if t["tick"] > 2 * usual:
            over = {k: v - med(k) for k, v in t.items() if k != "tick"}
            worst = max(over, key=over.get)
            harness.note(
                f"traced tick {i} took {t['tick'] / 1e6:.3f} ms on the host "
                f"(p50 {usual / 1e6:.3f}): {over[worst] / 1e6:.3f} ms of the "
                f"excess under {short(worst)}"
                + (f"; its program started {leads[i] / 1e6:.3f} ms after "
                   "engine.enqueue opened" if leads[i] is not None else ""))
