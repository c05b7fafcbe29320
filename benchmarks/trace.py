"""From the profiler's trace to numbers: device busy and idle time, time by
operation, Pallas kernels' and collectives' time, and the longest idle gaps
by what the host was doing.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` only.  The
reduction works on a plain form (``planes`` -> ``lines`` -> events
``[name, start_ns, duration_ns]``) so that it can be checked on a small
recorded trace kept beside the tests.

What a v5e trace looks like (looked at by hand, PR 23): one plane per chip
named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation, in order, not overlapping, *named by the instruction's whole
text* (``%fusion.24 = bf16[...] fusion(...), kind=kOutput, ...``);
``XLA Modules`` holds one event per executed program
(``jit_train_step(<fingerprint>)``); ``Steps`` groups them.  A Pallas
kernel is the instruction whose text says
``custom_call_target="tpu_custom_call"``; its instruction name is the
innermost ``jax.named_scope`` or flax module it was called under
(``%optimizer.1204``, ``%attention_ln.32``), which is how kernels can be
told apart today although no ``pallas_call`` passes ``name=``.  Host threads
are lines of host planes, and a ``jax.profiler.TraceAnnotation`` is an event
of its own name on the thread that made it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
MODULES_LINE = "XLA Modules"
PALLAS = 'custom_call_target="tpu_custom_call"'


def short(name: str) -> str:
    """An operation's instruction name, without the rest of its text."""
    return name.split(" = ", 1)[0].lstrip("%")


def family(name: str) -> str:
    """``attention_ln.32`` -> ``attention_ln``: XLA's number taken off."""
    return re.sub(r"\.\d+$", "", short(name))


def load_xplane(path: str) -> List[Dict[str, Any]]:
    """The plain form of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def host_spans(planes) -> List[Tuple[str, int, int]]:
    """(name, start, end) of the harness's annotations, on any host line."""
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name, start, start + dur))
    return out


def reduce(planes: List[Dict[str, Any]],
           window_s: Optional[float] = None) -> Dict[str, Any]:
    """The numbers of one traced window.

    The window is the stretch the harness's own spans cover (first start to
    last end), on the trace's clock; without spans it is the extent of the
    device's operations.  ``window_s`` (the host clock's length of the
    traced stretch) is used only when the trace holds no span at all."""
    spans = host_spans(planes)
    devices = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [ev for line in plane["lines"] if line["name"] == OPS_LINE
               for ev in line["events"]]
        if ops:
            devices.append(ops)
    if not devices:
        return {"busy_s": 0.0, "window_s": window_s or 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "pallas_s": 0.0,
                "collective_s": 0.0, "pallas_kernels": {},
                "main_module": None, "main_module_runs": 0.0}
    if spans:
        lo = min(s for _, s, _ in spans)
        hi = max(e for _, _, e in spans)
    else:
        lo = min(ev[1] for ops in devices for ev in ops)
        hi = max(ev[1] + ev[2] for ops in devices for ev in ops)
    busy, pallas, coll = [], [], []
    by_name: Dict[str, float] = {}
    kernels: Dict[str, float] = {}
    for ops in devices:
        inside = [(n, max(s, lo), min(s + d, hi)) for n, s, d in ops
                  if min(s + d, hi) > max(s, lo)]
        busy.append(_total(_union([(s, e) for _, s, e in inside])))
        coll.append(sum(e - s for n, s, e in inside
                        if COLLECTIVE.match(short(n))))
        mine = 0
        for n, s, e in inside:
            sec = (e - s) / 1e9 / len(devices)
            by_name[short(n)] = by_name.get(short(n), 0.0) + sec
            if PALLAS in n:
                mine += e - s
                kernels[family(n)] = kernels.get(family(n), 0.0) + sec
        pallas.append(mine)
    # how many runs of the main program (the one with most device time)
    # the window holds, counting a run cut by the window's edge by its part
    runs: Dict[str, List[float]] = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != MODULES_LINE:
                continue
            for n, s, d in line["events"]:
                part = min(s + d, hi) - max(s, lo)
                if part > 0 and d > 0:
                    r = runs.setdefault(n, [0.0, 0.0])
                    r[0] += part / d / len(devices)
                    r[1] += part / 1e9 / len(devices)
    main = max(runs.items(), key=lambda kv: kv[1][1], default=(None, [0, 0]))
    # idle gaps of the first device, each laid to the harness span that
    # covers most of it
    first = _clip(_union([(s, s + d) for _, s, d in devices[0]]), lo, hi)
    gaps, at = [], lo
    for s, e in first:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    blame: Dict[str, float] = {}
    spans.sort(key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    longest = max((e - s for _, s, e in spans), default=0)
    for gs, ge in gaps:
        best, cover = "host (no span)", 0
        i = bisect.bisect_left(starts, gs - longest)
        while i < len(spans) and spans[i][1] < ge:
            name, s, e = spans[i]
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = name, c
            i += 1
        blame[best] = blame.get(best, 0.0) + (ge - gs) / 1e9
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    n = len(devices)
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": n, "device_ops": top(by_name),
            "idle_gaps": top(blame), "pallas_s": sum(pallas) / n / 1e9,
            "collective_s": sum(coll) / n / 1e9, "pallas_kernels": kernels,
            "main_module": main[0], "main_module_runs": main[1][0]}


def reduce_dir(trace_dir: str, window_s: Optional[float]) -> Dict[str, Any]:
    path = find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return reduce(load_xplane(path), window_s)
