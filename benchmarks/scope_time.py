"""Device time under named scopes that ``program_trace.SCOPES`` does not
list.  ``program_trace.scope_pct`` knows the scopes the program had when it
was written; a model added later brings scopes of its own, and its readers
ask here, by the same rule: an operation belongs to a scope if the scope's
name is a component of its ``tf_op`` path (JAX's wrappers stripped,
``jit(...)`` components never scopes).  Nothing here raises on a trace, or
a program, that has no such scope: the readers then return nothing.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

from benchmarks import program_trace


@functools.lru_cache(maxsize=None)
def _components(path: str) -> frozenset:
    out = set()
    for comp in path.split("/"):
        if comp.startswith(("jit(", "pjit(")):
            continue
        found = program_trace._WRAPPED.match(comp)
        if found:
            out.add(found.group(1))
    return frozenset(out)


def scope_seconds(planes, scopes: Tuple[str, ...],
                  op_prefixes: Tuple[str, ...] = ()
                  ) -> Tuple[Optional[float], float]:
    """(seconds of the first device's operations under any of ``scopes``,
    seconds of all of them); the first is ``None`` where nothing carries
    one of the names.  ``op_prefixes``: operations XLA names so count as
    well, whatever their path (the grouped-product kernels XLA makes of
    ``lax.ragged_dot`` are ``%ragged-dot-*`` and carry no ``tf_op`` path,
    so no scope reaches them)."""
    if planes is None:
        return None, 0.0
    ops = program_trace.device_ops(planes)
    want = set(scopes)
    hit = sum(ev[2] for ev in ops
              if (len(ev) > 3 and ev[3] and _components(ev[3]) & want)
              or (op_prefixes and ev[0].startswith(op_prefixes)))
    return (hit / 1e9 if hit else None), sum(ev[2] for ev in ops) / 1e9


def scope_pct(scopes: Tuple[str, ...],
              op_prefixes: Tuple[str, ...] = ()) -> Optional[float]:
    """Of this checkout's traced run: device time under ``scopes`` over
    device-busy time, in %."""
    hit, busy = scope_seconds(program_trace.of_run(), scopes, op_prefixes)
    return 100.0 * hit / busy if hit and busy else None
