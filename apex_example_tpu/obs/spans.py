"""One span vocabulary for host timelines and the device trace.

Three kinds of region exist in this stack and they need different tools:

- **Traced (device) regions** — code under ``jit``.  Host timing there
  is meaningless (it measures tracing, once); the right annotation is
  ``jax.named_scope``, which lands the label in every operation's
  ``op_name`` and so in the profiler's device trace.
  :func:`device_span` is that, re-exported so the names come from the
  single :data:`PHASES` table below.
- **Host regions** — the train loop's data fetch, step dispatch,
  checkpoint IO.  :func:`span` times those with ``perf_counter``, nests,
  and (optionally) feeds a ``span.<name>`` histogram in a
  :class:`~apex_example_tpu.obs.metrics.MetricsRegistry`.
- **The contiguous phases of one loop iteration** — the serve tick.
  :class:`Phases` reads each boundary once and gives every consumer the
  same readings; each phase is also a ``jax.profiler.TraceAnnotation``,
  so that an open profiler session shows what the host was doing on the
  same clock as the device's operations.  ``Phases.child`` names what
  happens inside a phase (the serve tick's hand-offs to the runtime:
  ``engine.build``, ``engine.rng``, ``engine.put``, ``engine.fetch``,
  tickprof.ENGINE_HANDOFFS; and the next step's key, split while the chip
  runs this one's program, tickprof.ENGINE_KEY_AHEAD) as an annotation
  alone: a child never changes a boundary.

Using the same names on both sides ("fwd_bwd" as a host span around a
block that is "fwd_bwd" in the device trace) is the point: a perf PR
reads one vocabulary across JSONL telemetry and the profiler's trace.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import List, Optional

import jax

from apex_example_tpu.obs import trace as trace_lib

# Canonical labels.  The host-side entries are emitted by the train
# loop through span(); the device-side ones through device_span by
# engine.make_train_step, the loss functions of workloads.py, the models'
# heads, ops/paged_cache.py (kv_cow, kv_write, kv_gather), the paged branch
# of models/bert.py, models/layers.py (the served decoders' shared layers:
# latent_attention, shared_expert, the latent's kv_write, the plain
# forward's gqa_attention), models/xing4.py, models/granite_hybrid.py,
# models/pangu_moe.py, models/trinity.py, models/lfm2.py, ops/lane_pack.py,
# ops/attention.py (paged_gqa_attention),
# the dropless layer of
# transformer/expert_parallel.py and serve/engine._slot_step.
# The serve tick's host phases are tickprof.ENGINE_PHASES, their
# children tickprof.ENGINE_HANDOFFS (a jax-free table each: the key, the
# tick's one put, each read of a result) and tickprof.ENGINE_KEY_AHEAD
# (the next step's key, split under engine.sync while the chip is busy).
# Keep README's "Span naming" paragraph in sync.
PHASES = (
    "data",             # host: batch synthesis / prefetcher fetch
    "step",             # host: step dispatch (+ fetch when telemetry is on)
    "fwd_bwd",          # device: forward + scaled backward
    "grad_allreduce",   # device: DDP gradient reduction
    "unscale_check",    # device: unscale + finite check
    "optimizer",        # device: fused optimizer apply
    "mlm_head",         # device: the LM head (transform + vocabulary logits)
    "loss",             # device: the loss on the model's outputs
    "dequant_weights",  # device: serve step, low-bit weights to compute dtype
    "kv_cow",           # device: paged decode, copy-on-write block copies
    "kv_write",         # device: paged decode, K/V scatter through the table
    "kv_gather",        # device: paged decode, each slot's K/V view gathered
    "paged_attention",  # device: paged decode, attention + output projection
    "sample",           # device: serve step, last-lane take + sampling
    "latent_attention",  # device: models/layers.py, MLA (absorbed or expanded)
    "hc_mix",           # device: hyper-connection coefficients, Sinkhorn, mixing
    "moe_route",        # device: dropless experts, router + top-k + gates
    "moe_dispatch",     # device: dropless experts, sort by expert + gather
    "moe_experts",      # device: dropless experts, the grouped products
    "moe_combine",      # device: dropless experts, back to token order + sum
    "shared_expert",    # device: the shared expert beside the routed ones
    "ssm_mixer",        # device: models/granite_hybrid.py, a Mamba-2 mixer whole
    "ssm_scan",         # device: inside it, conv + chunked recurrence + state
    "shared_mlp",       # device: the dense SwiGLU MLP of every hybrid layer
    "gqa_attention",    # device: grouped-query attention over the paged K/V
    "paged_gqa_attention",  # device: ops/attention.py, the paged GQA kernel
                        # (or its XLA form): scores, mask, softmax, sum
    "lane_pack",        # device: ops/lane_pack.py, packed rows <-> [slots, lanes]
    "sandwich_norm",    # device: models/pangu_moe.py, the four norms a layer
    "mtp",              # device: its next-token module whole (layer + head)
    "draft_verify",     # device: serve step, compare-and-select after the head
    "short_conv",       # device: models/lfm2.py, a gated short convolution whole
    "dense_mlp",        # device: models/lfm2.py, the leading layers' dense SwiGLU
)

device_span = jax.named_scope

_tls = threading.local()
_default_registry = None


def set_default_registry(registry) -> None:
    """Registry every subsequent span records into (None disables)."""
    global _default_registry
    _default_registry = registry


class Span:
    """One timed host region; ``dur_ms`` is set when the context exits."""

    __slots__ = ("name", "t0", "dur_ms", "children", "span_id")

    def __init__(self, name: str):
        self.name = name
        self.t0 = time.perf_counter()
        self.dur_ms: Optional[float] = None
        self.children: List["Span"] = []
        # Allocated up front when a tracer is armed (--trace): children
        # exit FIRST, so the parent's id must exist before its own X
        # event is emitted.
        self.span_id: Optional[str] = None

    @property
    def dur_s(self) -> float:
        return (self.dur_ms or 0.0) / 1e3


def _stack() -> List[Span]:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def current_span() -> Optional[Span]:
    stack = _stack()
    return stack[-1] if stack else None


@contextmanager
def span(name: str, registry=None):
    """Time a host region.

    Nested spans attach to their parent (``Span.children``); completed
    spans feed ``span.<dotted.path>`` histograms in ``registry`` (or the
    default registry).

    Yields the :class:`Span`; read ``sp.dur_ms`` after the ``with`` for
    the measured duration.

    With a default :class:`~apex_example_tpu.obs.trace.Tracer` armed
    (``--trace``), each completed span additionally lands as a
    schema-v9 ``trace_event`` (ph "X", tid = the host thread's name,
    parented on the enclosing span) — the histograms above are
    unchanged; the timeline is strictly additive.
    """
    stack = _stack()
    sp = Span(name)
    parent = stack[-1] if stack else None
    if parent is not None:
        parent.children.append(sp)
    tracer = trace_lib.get_default()
    if tracer is not None:
        sp.span_id = tracer.next_id()
    stack.append(sp)
    try:
        yield sp
    finally:
        sp.dur_ms = (time.perf_counter() - sp.t0) * 1e3
        stack.pop()
        reg = registry if registry is not None else _default_registry
        if reg is not None:
            path = ".".join([s.name for s in stack] + [name])
            reg.histogram(f"span.{path}").observe(sp.dur_ms)
        if tracer is not None:
            tracer.complete(
                name, sp.t0, sp.dur_ms / 1e3, cat="span",
                tid=threading.current_thread().name,
                span_id=sp.span_id,
                parent_id=parent.span_id if parent is not None else None)


class _Silent:
    """What ``Phases.child`` gives when nothing is annotated."""

    __slots__ = ()

    def __enter__(self) -> "_Silent":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **meta) -> None:
        return None


_SILENT = _Silent()


class Phases:
    """The contiguous phases of one loop iteration, each boundary read
    once.

    Opening reads ``perf_counter`` and starts ``first``; ``enter(name)``
    reads it again, ends the running phase there and starts ``name``;
    ``close()`` ends the last.  ``names[i]`` ran from ``at[i]`` to
    ``at[i + 1]``, so whatever wants a tick's timing (the Tracer's X
    events, ``TickProfiler.observe_tick``, a token's stamp) takes it from
    these readings and the parts telescope to the whole.

    With ``annotate`` the iteration (``root``, carrying ``meta``) and each
    phase are also ``jax.profiler.TraceAnnotation`` events: while a
    profiler session is open they land in its ``.xplane.pb`` as host
    events on the clock of the device's lines; with none open each is an
    inactive check (under a microsecond).  A context manager, so that an
    exception leaves no annotation open.

    ``child(name)`` opens a span inside the running phase: an annotation
    and nothing else.  A child never changes a boundary: ``names``,
    ``at`` and ``ms`` do not know it, so the records fed from them read
    the same with children as without.
    """

    __slots__ = ("names", "at", "_root", "_open")

    def __init__(self, root: str, first: str, annotate: bool = True,
                 **meta):
        self._root = self._open = None
        if annotate:
            self._root = jax.profiler.TraceAnnotation(root, **meta)
            self._root.__enter__()
            self._open = jax.profiler.TraceAnnotation(first)
            self._open.__enter__()
        self.names = [first]
        self.at = [time.perf_counter()]

    def enter(self, name: str) -> float:
        """End the running phase and start ``name``; the boundary."""
        now = time.perf_counter()
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = jax.profiler.TraceAnnotation(name)
            self._open.__enter__()
        self.names.append(name)
        self.at.append(now)
        return now

    def child(self, name: str, **meta):
        """A span inside the running phase, carrying ``meta``: a context
        manager whose ``set_metadata`` takes what is known only at its
        end.  Close it before the next ``enter``.  Without ``annotate``
        it is nothing at all."""
        if self._root is None:
            return _SILENT
        return jax.profiler.TraceAnnotation(name, **meta)

    def set_meta(self, **meta) -> None:
        """Further metadata for the iteration's annotation."""
        if self._root is not None:
            self._root.set_metadata(**meta)

    def close(self) -> float:
        """End the last phase and the iteration; the end (idempotent)."""
        if len(self.at) == len(self.names):
            self.at.append(time.perf_counter())
            if self._root is not None:
                self._open.__exit__(None, None, None)
                self._root.__exit__(None, None, None)
        return self.at[-1]

    def ms(self, *names: str) -> float:
        """Milliseconds spent in ``names`` (closed phases)."""
        return sum(self.at[i + 1] - self.at[i]
                   for i, n in enumerate(self.names) if n in names) * 1e3

    def __enter__(self) -> "Phases":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
