"""What the program's own spans and scopes say in a traced run: the serve
tick's host phases against the device's idle gaps, and device time by named
scope.

``benchmarks/trace.py`` reads the harness's spans (``bench.*``) and names
operations by XLA's instruction names; this module reads what the program
emits itself, so that a layer keeps its name through a recompile:

- host events ``engine.tick`` and its six children (the names are quoted
  below from ``apex_example_tpu/obs/tickprof.py``; a test holds the two
  together), written by ``ServeEngine.step()`` as
  ``jax.profiler.TraceAnnotation`` on the clock of the device's lines;
- the scope path of every device operation: its HLO ``op_name``
  (``jit(step)/.../layer_3/attention/kv_gather/gather``), which on this
  chip's traces is the ``tf_op`` stat of the event's metadata record (see
  ``op_names``; PERF.md section 3).  A fusion carries the path XLA gave the fusion instruction
  (its root's).  A component counts as scope ``s`` when it is ``s`` itself
  or ``s`` inside JAX's transform wrappers (``jvp(loss)``,
  ``transpose(jvp(loss))``); ``jit(...)`` components are function names and
  never scopes.

Everything works on a plain form (``planes`` -> ``lines`` -> events
``[name, start_ns, duration_ns]`` or, for a device operation,
``[name, start_ns, duration_ns, scope_path]``) so that it can be checked on
a small trace recorded on the chip and kept beside the tests.  A program
without these spans and scopes (the parent of the PR that added them) gives
``None`` wherever one is needed, and so does a trace without a TPU plane.
"""

from __future__ import annotations

import functools
import os
import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmarks import harness
from benchmarks import trace as trace_lib

ENGINE_TICK = "engine.tick"
DISPATCH_PHASES = ("engine.admit", "engine.marshal", "engine.enqueue")
HARVEST_PHASES = ("engine.sync", "engine.harvest", "engine.gauges")
ENGINE_PHASES = DISPATCH_PHASES + HARVEST_PHASES
HARNESS = "harness"     # an idle stretch that no engine.tick covers
# obs/spans.py PHASES, the device-side entries
SCOPES = ("fwd_bwd", "grad_allreduce", "unscale_check", "optimizer",
          "mlm_head", "loss", "dequant_weights", "kv_cow", "kv_write",
          "kv_gather", "paged_attention", "sample")
UNSCOPED = "(no scope)"
SCOPE_STAT = "tf_op"
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()*([^()]*)\)*$")

Event = List[Any]
Interval = Tuple[int, int]


# ------------------------------------------------------------ the file

def load(path: str) -> List[Dict[str, Any]]:
    """The plain form of one ``.xplane.pb``, cut to what is read here: the
    device planes' ``XLA Ops`` (with scope paths) and ``XLA Modules`` lines,
    and the host lines' ``engine.*`` and ``bench.*`` events."""
    from jax.profiler import ProfileData
    paths = op_names(path)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(trace_lib.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name == trace_lib.OPS_LINE:
                events = [[e.name, int(e.start_ns), int(e.duration_ns),
                           paths.get(e.name, "")] for e in line.events]
            elif device and line.name == trace_lib.MODULES_LINE:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
            elif not device:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(("engine.", "bench."))]
            else:
                continue
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


# An operation's ``op_name`` is neither in its event's text nor among the
# event's stats on this runtime: it is a stat (``tf_op``) of the event's
# *metadata* record, which ``ProfileData`` (jax 0.9.0) does not hand out.
# So the one map needed, instruction text -> ``tf_op``, is read from the
# file's protobuf wire format directly (tsl's xplane.proto: XSpace.planes=1;
# XPlane.name=2, .event_metadata=4, .stat_metadata=5, both maps of key=1 /
# value=2; XEventMetadata.name=2, .stats=5; XStatMetadata.name=2;
# XStat.metadata_id=1, .str_value=5, .ref_value=7).  Lines and events are
# skipped whole, so this costs milliseconds.

def _fields(buf: memoryview):
    """(field number, value) of one message: an int for a varint field, a
    memoryview for a length-delimited one; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _map_entries(plane: memoryview, field: int):
    for no, entry in _fields(plane):
        if no == field:
            pair = dict(_fields(entry))
            yield pair.get(1, 0), pair.get(2, memoryview(b""))


def op_names(path: str) -> Dict[str, str]:
    """Instruction text -> HLO ``op_name`` (the metadata's ``tf_op`` stat,
    its trailing ``:<op type>`` taken off) over the file's device planes."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name = "".join(bytes(v).decode() for k, v in _fields(plane)
                       if k == 2)
        if not trace_lib.DEVICE_PLANE.match(name):
            continue
        stat_names = {key: bytes(dict(_fields(meta)).get(2, b"")).decode()
                      for key, meta in _map_entries(plane, 5)}
        for _, meta in _map_entries(plane, 4):
            text, op = "", ""
            for k, v in _fields(meta):
                if k == 2:
                    text = bytes(v).decode()
                elif k == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    op = bytes(stat[5]).decode() if 5 in stat \
                        else stat_names.get(stat.get(7), "")
            if op:
                out[text] = op.rsplit(":", 1)[0]
    return out


@functools.lru_cache(maxsize=2)
def _load_cached(path: str, mtime: float) -> List[Dict[str, Any]]:
    planes = load(path)
    report(planes)
    return planes


def of_run() -> Optional[List[Dict[str, Any]]]:
    """The trace of this checkout's traced run (``<checkout>/.bench_trace``,
    where the runners keep it), read once however many readers ask, with
    the detail printed to the run's notes; ``None`` if there is none."""
    path = trace_lib.find_xplane(os.path.join(harness.ROOT, ".bench_trace"))
    if path is None:
        return None
    return _load_cached(path, os.path.getmtime(path))


# ---------------------------------------------------------- the pieces

def _device_lines(planes, line_name: str) -> List[List[Event]]:
    return [line["events"] for plane in planes
            if trace_lib.DEVICE_PLANE.match(plane["name"])
            for line in plane["lines"]
            if line["name"] == line_name and line["events"]]


def device_ops(planes) -> List[Event]:
    """The first device's operations, or [] without a TPU plane."""
    lines = _device_lines(planes, trace_lib.OPS_LINE)
    return lines[0] if lines else []


def _host_events(planes, wanted) -> List[Event]:
    return sorted((ev for plane in planes
                   if not trace_lib.DEVICE_PLANE.match(plane["name"])
                   for line in plane["lines"] for ev in line["events"]
                   if wanted(ev[0])), key=lambda ev: ev[1])


def engine_ticks(planes) -> List[Dict[str, Any]]:
    """The traced ticks that ran a step: ``{"start", "end", "phases":
    [(name, start, end), ...]}`` for every ``engine.tick`` with all six
    phases inside it, in time order."""
    phases = _host_events(planes, lambda n: n in ENGINE_PHASES)
    out = []
    for _, start, dur in _host_events(planes, lambda n: n == ENGINE_TICK):
        inside = [(n, s, s + d) for n, s, d in phases
                  if start <= s and s + d <= start + dur]
        if [n for n, _, _ in inside] == list(ENGINE_PHASES):
            out.append({"start": start, "end": start + dur,
                        "phases": inside})
    return out


def tick_gaps(planes) -> List[Dict[str, float]]:
    """The device's idle time between consecutive runs of the tick's
    program (the module with most device time), one entry per gap: the
    nanoseconds of it under each ``engine.*`` phase, under ``harness``
    (outside any tick: the benchmark's own loop), their sum ``idle``, and
    the ``period`` from the one run's start to the next's.
    Operations of other programs inside a gap (the two RNG programs) are
    busy time, not idle."""
    modules = _device_lines(planes, trace_lib.MODULES_LINE)
    ops = device_ops(planes)
    if not modules or not ops:
        return []
    time_by_name: Dict[str, int] = {}
    for name, _, dur in modules[0]:
        time_by_name[name] = time_by_name.get(name, 0) + dur
    main = max(time_by_name, key=time_by_name.get)
    runs = sorted((s, s + d) for n, s, d in modules[0] if n == main)
    busy = trace_lib._union([(ev[1], ev[1] + ev[2]) for ev in ops])
    ticks = engine_ticks(planes)
    overlap = lambda lo, hi, s, e: max(0, min(e, hi) - max(s, lo))
    out = []
    for (prev_start, prev_end), (next_start, _) in zip(runs, runs[1:]):
        parts = dict.fromkeys(ENGINE_PHASES + (ENGINE_TICK, HARNESS), 0)
        for lo, hi in _minus((prev_end, next_start), busy):
            in_ticks = in_phases = 0
            for t in ticks:
                in_ticks += overlap(lo, hi, t["start"], t["end"])
                for name, s, e in t["phases"]:
                    parts[name] += overlap(lo, hi, s, e)
                    in_phases += overlap(lo, hi, s, e)
            # inside a tick but between two phases: the tick's own
            parts[ENGINE_TICK] += in_ticks - in_phases
            parts[HARNESS] += (hi - lo) - in_ticks
        parts["idle"] = sum(parts.values())
        parts["period"] = next_start - prev_start
        out.append(parts)
    return out


def idle_share(gaps: List[Dict[str, float]]) -> float:
    """Idle time of all ``gaps`` over the time from the first run's start
    to the last run's start: what ``device_idle_pct`` reads, without the
    traced window's two cut ticks."""
    span = sum(g["period"] for g in gaps)
    return sum(g["idle"] for g in gaps) / span if span else 0.0


def _minus(whole: Interval, taken: List[Interval]) -> List[Interval]:
    """``whole`` without the sorted, disjoint intervals ``taken``."""
    out, at = [], whole[0]
    for s, e in taken:
        if e <= at:
            continue
        if s >= whole[1]:
            break
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if whole[1] > at:
        out.append((at, whole[1]))
    return out


@functools.lru_cache(maxsize=None)
def scopes_of(path: str) -> Tuple[str, ...]:
    """The scopes of ours on an operation's path, outermost first (a trace
    holds each path thousands of times: cached)."""
    out = []
    for comp in path.split("/"):
        if comp.startswith(("jit(", "pjit(")):
            continue
        found = _WRAPPED.match(comp)
        if found and found.group(1) in SCOPES:
            out.append(found.group(1))
    return tuple(out)


def scope_time(planes) -> Optional[Dict[str, Any]]:
    """Device time by named scope: ``busy_ns`` (sum over the first device's
    operations; they do not overlap on ``XLA Ops``), ``innermost`` (each
    operation once, under the last scope of ours on its path, or UNSCOPED)
    and ``unscoped_ops`` (those without one, by what XLA calls them).
    ``None`` without device operations."""
    ops = device_ops(planes)
    if not ops:
        return None
    innermost: Dict[str, int] = {}
    unscoped_ops: Dict[str, int] = {}
    for ev in ops:
        name, dur = ev[0], ev[2]
        mine = scopes_of(ev[3]) if len(ev) > 3 else ()
        key = mine[-1] if mine else UNSCOPED
        innermost[key] = innermost.get(key, 0) + dur
        if not mine:
            # XLA's instruction family and where its op_name ends, numbers
            # taken off: "copy @ cache['layer_N']['attention']"
            tail = re.sub(r"\d+", "N", "/".join(
                ev[3].split("/")[-2:])) if len(ev) > 3 and ev[3] else "-"
            key = f"{trace_lib.family(name)} @ {tail}"
            unscoped_ops[key] = unscoped_ops.get(key, 0) + dur
    return {"busy_ns": sum(ev[2] for ev in ops), "innermost": innermost,
            "unscoped_ops": unscoped_ops}


# ------------------------------------------------- what the readers ask

def gap_ms_p50(planes, phases: Optional[Tuple[str, ...]] = None
               ) -> Optional[float]:
    """Median over the traced gaps of the device's idle time between two
    runs of the tick's program, in ms: all of it, or the part under
    ``phases``.  ``None`` without a device trace, and for a part when the
    trace holds no ``engine.*`` span."""
    if planes is None:
        return None
    gaps = tick_gaps(planes)
    if not gaps or (phases and not engine_ticks(planes)):
        return None
    if phases is None:
        return statistics.median(g["idle"] for g in gaps) / 1e6
    return statistics.median(
        sum(g.get(p, 0) for p in phases) for g in gaps) / 1e6


def scope_pct(planes, scopes: Tuple[str, ...]) -> Optional[float]:
    """Device time of operations under any of ``scopes`` over device-busy
    time, in %.  ``None`` without a device trace or when no operation
    carries one of them (a program that has no such scope)."""
    if planes is None:
        return None
    ops = device_ops(planes)
    hit = sum(ev[2] for ev in ops
              if len(ev) > 3 and set(scopes_of(ev[3])) & set(scopes))
    busy = sum(ev[2] for ev in ops)
    return 100.0 * hit / busy if hit and busy else None


# ------------------------------------------------------------ the notes

def report(planes) -> None:
    """The detail behind the six metrics, for whoever reads the run's
    standard error."""
    ticks = engine_ticks(planes)
    steps = _host_events(planes, lambda n: n == "bench.engine_step")
    # gaps between ticks: of a serving run only, whatever its program says
    gaps = tick_gaps(planes) if ticks or steps else []
    med = lambda v: statistics.median(v) / 1e6
    if ticks:
        per = {p: [e - s for t in ticks for n, s, e in t["phases"]
                   if n == p] for p in ENGINE_PHASES}
        harness.note(f"engine phases over {len(ticks)} traced ticks, host "
                     "ms p50/max: " + ", ".join(
                         f"{p.split('.')[1]} {med(v):.3f}/{max(v) / 1e6:.3f}"
                         for p, v in per.items()))
        share = [sum(e - s for _, s, e in t["phases"]) / d
                 for t in ticks for _, s, d in steps
                 if s <= t["start"] and t["end"] <= s + d and d]
        if share:
            harness.note("the six phases cover "
                         f"{100 * statistics.median(share):.2f}% of their "
                         "bench.engine_step (p50)")
    if gaps:
        keys = ENGINE_PHASES + (ENGINE_TICK, HARNESS)
        harness.note("device idle over all traced gaps, the profiler's "
                     "start included: "
                     f"{100 * idle_share(gaps):.2f}% of the time from "
                     "the first run of the tick's program to the last")
        harness.note(f"device idle between ticks over {len(gaps)} gaps, ms "
                     f"p50: {med([g['idle'] for g in gaps]):.3f} = "
                     + " + ".join(
                         f"{k.split('.')[-1]} "
                         f"{med([g.get(k, 0) for g in gaps]):.3f}"
                         for k in keys)
                     + (" (no engine.* span in this trace: all of it reads "
                        "as harness)" if not ticks else ""))
    st = scope_time(planes)
    if st and st["busy_ns"]:
        pct = lambda ns: f"{100.0 * ns / st['busy_ns']:.2f}%"
        top = sorted(st["innermost"].items(), key=lambda kv: -kv[1])
        harness.note("device time by innermost scope: " + ", ".join(
            f"{k} {pct(v)}" for k, v in top))
        bare = sorted(st["unscoped_ops"].items(), key=lambda kv: -kv[1])[:8]
        if bare:
            harness.note("largest operations with no scope: " + ", ".join(
                f"{k} {pct(v)}" for k, v in bare))
