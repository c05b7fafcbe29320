"""Compiled-graph cost observability: harvest what XLA already knows.

Every jitted step function the repo runs is compiled exactly once per
(function, abstract signature) — and at that moment XLA has computed the
program's FLOPs, HBM bytes accessed, transcendental count and buffer
sizes.  Today none of it reaches the telemetry stream; the only byte
accounting is a hand-enumerated arithmetic script
(tools/byte_accounting.py) and MFU comes from closed-form models
(utils/flops.py).  This module closes the loop: an instrumentation
layer that routes a jitted function through the AOT path
(``fn.lower(*args).compile()``), executes the resulting ``Compiled``
object from then on — the run compiles nothing it would not have
compiled anyway, the dispatch-cache compile simply moves here — and
turns each compilation into two schema-v6 records:

``compile_event``  one per compilation — wall time of lower and
                   compile, a lowering hash (the compile-cache
                   identity: same hash ⇒ same program ⇒ a recompile is
                   a cache miss, not new work), and the per-name
                   compile ordinal ``n_compiles`` the recompile-
                   regression guard counts.
``cost_model``     the harvested ``cost_analysis()`` (flops, bytes
                   accessed, transcendentals) and ``memory_analysis()``
                   (argument/output/temp/generated-code bytes) plus the
                   analytic roofline position: arithmetic intensity,
                   compute-vs-HBM time at the peak constants, the
                   binding-side verdict, and the MFU ceiling that
                   intensity admits.  Backends that omit an analysis
                   (CPU reports ``generated_code_size_in_bytes`` 0 and
                   some backends raise) degrade those fields to
                   ``null`` rather than dropping the record.

The roofline constants default to the v5e row of the one peaks table
(``utils.flops.DEVICE_PEAKS``: 197 TFLOP/s bf16, 819 GB/s HBM).  They are
the TARGET's published constants, not a reading of the device the process
runs on: on the CPU rig the verdict is "what this program would be bound
by on the TPU target" — the program costs are backend-portable.

``tools/cost_report.py`` (jax-free) joins the ``cost_model`` records
against measured ``step_time_ms`` from the same stream: per-function
roofline tables, analytic-vs-measured gap, recompile tallies — the
decision-grade input the parallelism auto-planner (ROADMAP item 4)
needs.

Usage (what train.py/bench.py/serve.py do under ``--cost-model``)::

    cm = CostModel(sink=jsonl_sink, registry=registry, run_id=run_id)
    costmodel.set_default(cm)
    ...
    step_fn = costmodel.instrument("train_step", step_fn)   # no-op
    ...                                                     # without a
    costmodel.set_default(None)                             # default

``instrument`` is deliberately forgiving: a callable without the AOT
surface (``.lower``), or one whose lowering fails, falls back to direct
calls — instrumentation must never break a run it observes.
"""

from __future__ import annotations

import hashlib
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from apex_example_tpu.obs.metrics import now
from apex_example_tpu.utils.flops import DEVICE_PEAKS, V5E

_TARGET = DEVICE_PEAKS[V5E]

# Retention cap for the per-function StableHLO text kept for the
# recompile-cause diff: past this size graftlint's diff_lowerings
# refuses to diff anyway (its MAX_DIFF_CHARS), so holding multi-MB
# serve-step lowerings in a long-lived process would buy nothing.
_MAX_HLO_RETAIN_CHARS = 2_000_000

# CompiledMemoryStats attribute -> cost_model field.
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def _leaf_sig(leaf):
    """Hashable abstract descriptor of one argument leaf.  Arrays key on
    (shape, dtype, weak_type) — weak_type included because the compiled
    executable rejects a weak/strong mismatch the way a jit dispatch
    would transparently recompile for.  Python scalars key on their bare
    type (jit traces them weakly-typed and value-independent, so the
    value must not split the key).  No string building: this runs on
    EVERY instrumented call, and host overhead here would land inside
    the measured step_time_ms the roofline report joins against."""
    aval = getattr(leaf, "aval", None)
    if aval is not None:
        return (aval.shape, aval.dtype, getattr(aval, "weak_type", False))
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), dtype, False)
    return type(leaf)


def signature(args: tuple, kwargs: dict) -> Tuple:
    """The abstract call signature a jit dispatch would key on (tree
    structure + per-leaf shape/dtype/weak-type, all hashable objects —
    no serialization).  Two calls with the same signature share one
    compiled executable; a new signature is a recompile."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_sig(l) for l in leaves))


def _first_computation(analysis) -> Dict[str, float]:
    """cost_analysis() returns a list of per-computation dicts on some
    jax versions and a bare dict on others; the entry point's is
    first."""
    if isinstance(analysis, (list, tuple)):
        return dict(analysis[0]) if analysis else {}
    return dict(analysis) if analysis else {}


def text_hash(text: str) -> str:
    """The lowering-hash formula over already-extracted StableHLO text
    (one place, shared with the instrumented AOT path that also keeps
    the text for the recompile-cause diff)."""
    return "sha256:" + hashlib.sha256(text.encode()).hexdigest()[:32]


def lowering_hash(lowered) -> Optional[str]:
    """Stable identity of the lowered program (StableHLO text digest):
    two compilations with the same hash compiled the same program — the
    compile-cache identity recompile forensics key on."""
    try:
        text = lowered.as_text()
    except Exception:
        return None
    return text_hash(text)


def compile_counts(records) -> Dict[str, int]:
    """``compile_event`` records per instrumented function name, from an
    iterable of parsed JSONL records — the recompile-regression guard's
    helper (the tier-1 tests assert every count is exactly 1)."""
    counts: Dict[str, int] = {}
    for rec in records:
        if isinstance(rec, dict) and rec.get("record") == "compile_event":
            name = rec.get("name", "?")
            counts[name] = counts.get(name, 0) + 1
    return counts


class CostModel:
    """Builds instrumented wrappers and owns the roofline constants +
    record emission.  ``sink`` (an obs JsonlSink) receives the records;
    ``registry`` (a MetricsRegistry) additionally gets a
    ``compile_time_ms`` histogram and a ``compiles`` counter, which the
    telemetry emitter folds into the run summary as measured compile
    totals."""

    def __init__(self, sink=None, registry=None, run_id: Optional[str] = None,
                 peak_flops: float = _TARGET.bf16_flops,
                 hbm_gbps: float = _TARGET.hbm_bytes_per_s / 1e9):
        self.sink = sink
        self.registry = registry
        self.run_id = run_id
        self.peak_flops = float(peak_flops)
        self.hbm_gbps = float(hbm_gbps)
        self._counts: Dict[str, int] = {}
        self._wrapped: Dict[Tuple[str, int], "InstrumentedFn"] = {}
        self.events: List[Dict[str, Any]] = []
        # Last StableHLO text PER NAME (not per wrapper: re-instrumenting
        # a name with a fresh fn object shares the per-name compile
        # count, so it must share the diff baseline too — the second
        # compile of a name always gets its recompile_cause).  Texts
        # past the retention cap are dropped; the name is remembered so
        # oversized recompiles still get an honest (diff-less) cause.
        self._last_hlo: Dict[str, str] = {}
        self._hlo_dropped: Dict[str, bool] = {}

    # ------------------------------------------------------- wrapping

    def instrument(self, name: str, fn: Callable) -> Callable:
        """Wrap ``fn`` (idempotent per (name, fn): repeated calls — e.g.
        generate() re-fetching the same lru-cached decode loop — reuse
        one wrapper and with it one compiled executable)."""
        if isinstance(fn, InstrumentedFn):
            return fn
        key = (name, id(fn))
        wrapped = self._wrapped.get(key)
        if wrapped is None:
            wrapped = InstrumentedFn(self, name, fn)
            self._wrapped[key] = wrapped
        return wrapped

    @property
    def compile_counts(self) -> Dict[str, int]:
        return dict(self._counts)

    def recompile_cause(self, name: str,
                        text: Optional[str]) -> Optional[str]:
        """Diff ``name``'s new lowering text against the retained
        previous one (None on the first compile of a name), then roll
        the retention forward."""
        if text is None:
            return None
        prev = self._last_hlo.get(name)
        cause = None
        if prev is not None:
            cause = _recompile_cause(prev, text)
        elif self._hlo_dropped.get(name):
            cause = ("previous lowering exceeded the retention cap "
                     f"({_MAX_HLO_RETAIN_CHARS} chars) — no diff; "
                     "compare lowering_hash values instead")
        if len(text) > _MAX_HLO_RETAIN_CHARS:
            self._last_hlo.pop(name, None)
            self._hlo_dropped[name] = True
        else:
            self._last_hlo[name] = text
            self._hlo_dropped[name] = False
        return cause

    # ------------------------------------------------------- emission

    def _write(self, rec: Dict[str, Any]) -> None:
        self.events.append(rec)
        if self.sink is not None:
            self.sink.write(rec)

    def on_compile(self, name: str, *, compile_ms: float, lower_ms: float,
                   lhash: Optional[str],
                   recompile_cause: Optional[str] = None) -> None:
        self._counts[name] = self._counts.get(name, 0) + 1
        rec: Dict[str, Any] = {
            "record": "compile_event",
            "time": now(),
            "name": name,
            "compile_ms": round(compile_ms, 3),
            "lower_ms": round(lower_ms, 3),
            "n_compiles": self._counts[name],
            "platform": jax.default_backend(),
        }
        if lhash:
            rec["lowering_hash"] = lhash
        if recompile_cause:
            # schema v8: the recompile-regression gate's diagnosis — the
            # first structurally divergent op between this lowering and
            # the previous one for the same name (graftlint's HLO diff).
            rec["recompile_cause"] = recompile_cause
        if self.run_id:
            rec["run_id"] = self.run_id
        if self.registry is not None:
            self.registry.histogram("compile_time_ms").observe(compile_ms)
            self.registry.counter("compiles").inc()
        self._write(rec)

    def on_cost(self, name: str, compiled, lhash: Optional[str]) -> None:
        """Harvest + emit the ``cost_model`` record for one compiled
        executable; every analysis the backend omits degrades to
        ``null`` fields."""
        try:
            cost = _first_computation(compiled.cost_analysis())
        except Exception:
            cost = {}
        flops = cost.get("flops")
        bytes_accessed = cost.get("bytes accessed")
        rec: Dict[str, Any] = {
            "record": "cost_model",
            "time": now(),
            "name": name,
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "transcendentals": cost.get("transcendentals"),
            "peak_flops": self.peak_flops,
            "hbm_gbps": self.hbm_gbps,
        }
        mem = None
        try:
            mem = compiled.memory_analysis()
        except Exception:
            pass
        for attr, field in _MEMORY_FIELDS:
            value = getattr(mem, attr, None) if mem is not None else None
            rec[field] = int(value) if value is not None else None
        # flops may be an explicit 0.0 (a data-movement-only program):
        # the roofline is still well-defined (AI 0, hbm-bound).  Only
        # bytes_accessed == 0 makes the division meaningless.
        if flops is not None and bytes_accessed:
            ai = flops / bytes_accessed
            ridge = self.peak_flops / (self.hbm_gbps * 1e9)
            compute_ms = flops / self.peak_flops * 1e3
            hbm_ms = bytes_accessed / (self.hbm_gbps * 1e9) * 1e3
            rec["arithmetic_intensity"] = round(ai, 3)
            rec["ridge_flops_per_byte"] = round(ridge, 3)
            rec["compute_ms"] = round(compute_ms, 6)
            rec["hbm_ms"] = round(hbm_ms, 6)
            rec["analytic_min_ms"] = round(max(compute_ms, hbm_ms), 6)
            rec["roofline"] = ("compute-bound" if compute_ms >= hbm_ms
                               else "hbm-bound")
            # The MFU this intensity admits at the roofline — the
            # CEILING measured MFU can reach, not the achievement
            # (cost_report computes that from measured step times).
            rec["mfu_ceiling_pct"] = round(100.0 * min(1.0, ai / ridge), 2)
        if lhash:
            rec["lowering_hash"] = lhash
        if self.run_id:
            rec["run_id"] = self.run_id
        self._write(rec)


class InstrumentedFn:
    """A jitted callable re-routed through the AOT path.

    First call per abstract signature: ``lower`` + ``compile`` (timed,
    hashed, harvested), then the ``Compiled`` executes; later calls
    dispatch straight to it.  A signature never seen before is a
    recompile and emits a second ``compile_event`` for the same name —
    exactly the regression the guard exists to catch.  Anything that
    breaks the AOT path (no ``.lower``, lowering failure) degrades to
    direct calls: observation must never take down the run.
    """

    def __init__(self, cost_model: CostModel, name: str, fn: Callable):
        self._cm = cost_model
        self.name = name
        self._fn = fn
        self._compiled: Dict[Tuple, List[Any]] = {}
        self._sole = None        # fast path when exactly one sig exists
        self._degraded = False
        self._call_warned = False

    def __call__(self, *args, **kwargs):
        if self._degraded:
            return self._fn(*args, **kwargs)
        if self._sole is not None:
            # Steady-state fast path — the one-signature case the
            # recompile guard enforces: no per-call pytree flatten.
            # Host overhead here would land inside the measured
            # step_time_ms the roofline report joins against, so the
            # signature is only computed when the executable rejects
            # the args (exactly where a jit dispatch would go back to
            # its cache key too).
            try:
                return self._sole(*args, **kwargs)
            except TypeError:
                pass                         # not this signature
        key = signature(args, kwargs)
        for compiled in self._compiled.get(key, []):
            if compiled is self._sole:
                continue                     # already rejected above
            try:
                return compiled(*args, **kwargs)
            except TypeError:
                # An aval distinction the signature key missed (e.g. an
                # exotic sharding difference): the executable rejects
                # the args BEFORE running; try the key's other
                # executables before compiling another.
                continue
        # Unseen signature, or a key collision every cached executable
        # rejects — exactly where a jit dispatch would transparently
        # recompile, so compile (an honest compile_event) rather than
        # take down the run.
        compiled = self._aot_compile(args, kwargs)
        if compiled is None:                # degraded mid-flight
            return self._fn(*args, **kwargs)
        self._store(key, compiled)
        return compiled(*args, **kwargs)

    def _store(self, key, compiled) -> None:
        # APPEND under the key: two colliding-but-incompatible call
        # forms keep both executables, instead of evicting each other
        # into a compile ping-pong on alternating calls.
        self._compiled.setdefault(key, []).append(compiled)
        n = sum(len(v) for v in self._compiled.values())
        self._sole = compiled if n == 1 else None

    def __getattr__(self, attr):
        # Passthrough (lower/trace/etc.) so the wrapper stays a drop-in.
        # Private names raise instead of delegating — that also keeps a
        # half-constructed instance from recursing on self._fn.
        if attr.startswith("_"):
            raise AttributeError(attr)
        return getattr(self._fn, attr)

    def _aot_compile(self, args, kwargs):
        try:
            t0 = time.perf_counter()
            lowered = self._fn.lower(*args, **kwargs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception as e:
            # The run keeps going on direct calls, but an explicitly
            # requested --cost-model must not fail SILENTLY: say why
            # records are missing (package logger, not stdout —
            # default-verbosity output stays unchanged).
            from apex_example_tpu.obs.logging import get_logger
            if self._compiled:
                # The function AOT-compiles in general — THIS call's
                # args don't lower.  Degrade the call, not the
                # function: cached executables keep serving and later
                # signatures still compile + get recorded.
                if not self._call_warned:
                    self._call_warned = True
                    get_logger(__name__).warning(
                        "cost-model: one call form of %r failed to "
                        "AOT-compile (%s: %s); that form runs "
                        "uninstrumented — its dispatch-cache compile "
                        "is not recorded as a compile_event",
                        self.name, type(e).__name__, e)
                return None
            self._degraded = True
            get_logger(__name__).warning(
                "cost-model instrumentation disabled for %r "
                "(%s: %s); falling back to direct calls — no "
                "compile_event/cost_model records for it",
                self.name, type(e).__name__, e)
            return None
        text: Optional[str] = None
        try:
            text = lowered.as_text()
        except Exception:
            pass
        lhash = text_hash(text) if text is not None else None
        # Per-NAME diff baseline on the CostModel: the compile ordinal
        # is per name, so the diagnosis must be too.
        cause = self._cm.recompile_cause(self.name, text)
        self._cm.on_compile(self.name, compile_ms=(t2 - t1) * 1e3,
                            lower_ms=(t1 - t0) * 1e3, lhash=lhash,
                            recompile_cause=cause)
        self._cm.on_cost(self.name, compiled, lhash)
        return compiled


def _recompile_cause(prev_text: str, new_text: str) -> Optional[str]:
    """Name the first divergent op between two lowerings of one step
    (the graftlint HLO diff, jax-free text analysis).  Degrades to None
    when the linter package is not importable — the tally still lands,
    only the diagnosis is lost."""
    try:
        from tools.graftlint.hlo import diff_lowerings
    except Exception:
        return None
    try:
        diff = diff_lowerings(prev_text, new_text)
    except Exception:  # pragma: no cover — diagnosis must never crash
        return None
    if diff is None:
        return ("lowerings structurally identical — this recompile is "
                "a dispatch-cache miss, not a program change")
    return str(diff["summary"])


# ------------------------------------------------------ default instance

_default: Optional[CostModel] = None


def set_default(cost_model: Optional[CostModel]) -> None:
    """Install (or clear, with None) the process-default cost model the
    deep call sites — the serve engine's decode step, generate()'s
    decode loop — pick up without flag plumbing."""
    global _default
    _default = cost_model


def get_default() -> Optional[CostModel]:
    return _default


def instrument(name: str, fn: Callable) -> Callable:
    """Wrap ``fn`` under the default cost model; identity when none is
    installed (the un-flagged path stays zero-cost)."""
    if _default is None or fn is None:
        return fn
    return _default.instrument(name, fn)
