"""The fleet router: admission + dispatch over N serve replicas.

Pure stdlib ON PURPOSE — **jax-free by contract** like
resilience/supervisor.py (graftlint's static rule proves the import
closure): routing must keep working while individual replicas' jax is
dying, so nothing here may touch the serve package.  Replica handles
(fleet/replica.py) are duck-typed, never imported.

What the router owns:

- **Dispatch policies** (``--policy``): ``round_robin`` (cycle the
  routable set), ``least_pending`` (the smallest queued backlog, from
  each replica's tailed/live gauges), ``least_kv`` (the least live KV
  — the tailed dtype-accurate ``kv_bytes_live`` byte gauge of a v12
  replica, falling back to the raw ``blocks_live`` block count for
  older children).  A replica is
  routable when its handle reports healthy/starting AND its circuit
  breaker admits traffic.  When nothing is routable the request parks
  in the router backlog and is re-dispatched as capacity returns —
  admission never silently drops.
- **Requeue-on-drain**: a replica exiting 75 hands its still-queued
  requests back with status "drained"; the router requeues each to a
  SIBLING, exactly once per drain report (a duplicate report of the
  same drain is counted, not re-dispatched).  Drains are the expected
  steady state under rolling restarts, so they never trip the breaker.
- **Deadline-aware retry**: a request lost to a replica crash is
  re-dispatched while its wall-clock deadline allows and the retry
  budget lasts; past either it terminates first-class (``timeout`` /
  ``failed``) instead of spinning.
- **Disaggregated roles** (ISSUE 15): a replica handle carrying
  ``role="prefill"`` receives prompts like any other; one carrying
  ``role="decode"`` is never dispatched to — its outbox reports the
  terminals for requests the KV-handoff SPOOL fed it.  A prefill
  replica's status-"handoff" event parks the uid on the spool (no
  re-route: the spool is the inter-role channel); a decode worker
  that acked a handoff and then died reports it ``lost``, and the
  router re-routes the request through a prefill replica from
  scratch.  The ``fleet_summary`` carries the disagg topology and
  redelivery accounting (``prefill_replicas`` / ``decode_replicas`` /
  ``handoffs`` / ``handoff_redelivered`` / ``in_spool``).
- **Circuit breaking**: a crashed or stalled replica's breaker opens
  (exponential backoff), half-opens after the backoff to admit ONE
  probe request, and closes again only when the probe completes ok —
  the classic pattern, deterministic enough to unit-test.
- **SLO plane** (ISSUE 16): armed with an ``slo`` spec, every
  fleet-terminal event is scored good/bad against the latency targets
  (latencies ride the v14 outbox/harvest events) and folded into
  event-count tumbling windows — one schema-v14 ``slo_window`` record
  per ``slo_window`` terminals (plus ``slo_breach`` past burn 1.0);
  replica heartbeat sketches merge into periodic ``fleet_rollup``
  records (fleet percentiles + per-replica p50 skew/straggler), and
  the ``fleet_summary`` carries ``slo_verdict`` / worst-window burn —
  what chaos scenarios fold into their pass/fail.

Every decision lands in the router's own schema-v10 stream: one
``route`` record per dispatch (policy, attempt, reason), a
``replica_state`` record per observed transition (with the
supervisor's exit ``classification`` when known), and a closing
``fleet_summary`` (per-status totals, retry/requeue accounting,
``lost`` — the zero-lost acceptance counter — fleet availability,
per-replica breakdown, routing-balance stats).  With ``trace=True``
the same stream carries hard-coded schema-v9 trace events (the
supervisor's pattern: clock_sync + instants/X spans on the "router"
row), and the router exports ``APEX_TRACE_ID`` so every replica tree
it spawns joins ONE Perfetto timeline.

Thread-safety: ``submit`` may be called from a load-generator thread
while the main thread polls; all shared state (``_replicas`` metadata
incl. breaker fields, ``_inflight``, ``_backlog``, ``_done``) is
guarded by ``_lock`` — annotated for graftlint's lock-discipline rule.
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

# Keep in sync with apex_example_tpu/obs/schema.py (SCHEMA_VERSION) —
# jax-free contract forbids importing it (same stance as the
# supervisor's hard-coded records).
SCHEMA = 18
TRACE_ID_ENV = "APEX_TRACE_ID"

POLICIES = ("round_robin", "least_pending", "least_kv",
            "prefix_affinity")

# Statuses a replica can report that end a request for good at the
# fleet level (drained and lost are re-routed instead; "handoff" parks
# the uid on the KV spool — a decode replica's outbox finishes it;
# "migrated" (ISSUE 20) parks the same way on the live-migration spool
# — a PEER resumes the mid-flight request token-identically and its
# events finish the uid).
_TERMINAL = ("ok", "timeout", "shed", "cancelled", "failed", "rejected")

_SLO_MOD = None


def _load_slo():
    """obs/slo.py loaded by FILE PATH (cached): the module is stdlib
    self-contained by contract, so this never executes the jax-carrying
    package ``__init__`` chain — the metrics_lint _load_schema pattern.
    Loaded lazily, only when a router is armed with an --slo spec."""
    global _SLO_MOD
    if _SLO_MOD is None:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "obs", "slo.py")
        spec = importlib.util.spec_from_file_location("_fleet_slo", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _SLO_MOD = mod
    return _SLO_MOD


_PREFIX_MOD = None


def _load_prefix():
    """sched/prefix.py loaded by FILE PATH (cached), same stance as
    ``_load_slo``: the module is stdlib self-contained by the graftlint
    contract, so loading it never walks the jax-carrying package
    ``__init__``.  Only a prefix_affinity router pays the import."""
    global _PREFIX_MOD
    if _PREFIX_MOD is None:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            os.pardir, "sched", "prefix.py")
        spec = importlib.util.spec_from_file_location(
            "_fleet_prefix", os.path.abspath(path))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PREFIX_MOD = mod
    return _PREFIX_MOD


class _Stream:
    """Minimal JSONL writer (the jax-free contract rules out
    obs.JsonlSink — the supervisor carries the same copy, minus the
    lock: here a load-generator thread may submit() — and therefore
    emit route records — while the poll thread writes, so each line is
    one atomic write under an internal lock or the stream tears."""

    def __init__(self, path: Optional[str]):
        self.path = path
        self._lock = threading.Lock()
        self._fh = None                 # guarded-by: _lock

    def write(self, rec: Dict[str, Any]) -> None:
        if self.path is None:
            return
        line = json.dumps(rec, separators=(",", ":")) + "\n"
        with self._lock:
            if self._fh is None:
                parent = os.path.dirname(self.path)
                if parent:
                    os.makedirs(parent, exist_ok=True)
                self._fh = open(self.path, "w")
            self._fh.write(line)
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


class _Meta:
    """Per-replica routing state.  Every field is guarded by the
    router's ``_lock`` (reached only through ``self._replicas``)."""

    def __init__(self, handle):
        self.handle = handle
        self.dispatches = 0
        self.inflight = 0
        self.counts: Dict[str, int] = {}
        self.health: Dict[str, Any] = {"state": "starting"}
        self.emitted_state: Optional[str] = None
        # Circuit breaker: closed -> open (backoff) -> half_open
        # (single probe) -> closed | open.
        self.breaker = "closed"
        self.fail_streak = 0
        self.opened_at = 0.0
        self.probe_uid: Optional[str] = None

    def bump(self, status: str) -> None:
        self.counts[status] = self.counts.get(status, 0) + 1


class FleetRouter:
    """Route request specs across replica handles; see module doc."""

    def __init__(self, replicas, policy: str = "round_robin",
                 metrics_jsonl: Optional[str] = None, sink=None,
                 run_id: Optional[str] = None, max_retries: int = 2,
                 breaker_backoff_s: float = 0.25,
                 breaker_backoff_max_s: float = 5.0,
                 stall_after_s: Optional[float] = None,
                 default_deadline_s: Optional[float] = None,
                 spool_timeout_s: Optional[float] = None,
                 slo=None, slo_window: int = 16,
                 slo_rollup_s: float = 2.0,
                 tenant_specs=None, prefix_block_size: int = 8,
                 rebalance_kv_ratio: Optional[float] = None,
                 rebalance_cooldown_s: float = 1.0,
                 trace: bool = False, log=print):
        if policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, "
                             f"got {policy!r}")
        if not replicas:
            raise ValueError("fleet needs at least one replica")
        self.policy = policy
        self.max_retries = int(max_retries)
        self.breaker_backoff_s = float(breaker_backoff_s)
        self.breaker_backoff_max_s = float(breaker_backoff_max_s)
        self.stall_after_s = stall_after_s
        self.default_deadline_s = default_deadline_s
        # Disagg self-healing (ISSUE 15): a uid parked on the spool
        # longer than this is presumed eaten by a decode worker that
        # died AFTER acking its claim (the one crash window the lease
        # cannot redeliver — the spool file is gone and no process
        # will ever report it) and is re-routed through a prefill
        # replica from scratch, under the normal retry budget.  None =
        # off; size it well past the handoff lease so live redelivery
        # always gets first go.
        self.spool_timeout_s = spool_timeout_s
        self.log = log
        self.run_id = run_id or uuid.uuid4().hex[:12]
        self._stream = sink if sink is not None else _Stream(metrics_jsonl)
        # Reentrant: the SLO fold helpers (_slo_absorb /
        # _slo_close_window) take the lock themselves so the guard is
        # lexical, and their callers already hold it.
        self._lock = threading.RLock()
        self._order = [r.name for r in replicas]
        # Disagg roles (ISSUE 15): prompts route only to prefill-capable
        # replicas; decode replicas are harvested (their outbox carries
        # the spool-fed terminals) but never dispatched to.
        self._roles = {r.name: getattr(r, "role", "both")
                       for r in replicas}
        if all(role == "decode" for role in self._roles.values()):
            raise ValueError("fleet needs at least one prefill-capable "
                             "replica (every handle is role=decode)")
        self._replicas = {r.name: _Meta(r) for r in replicas}  # guarded-by: _lock
        self._inflight: Dict[str, Dict[str, Any]] = {}  # guarded-by: _lock
        self._backlog: deque = deque()                  # guarded-by: _lock
        self._done: Dict[str, str] = {}                 # guarded-by: _lock
        # uid -> replica still holding a LIVE booking for a uid that
        # terminated via an abandoned copy's late report (its own
        # report releases it — see _absorb's duplicate branch).
        self._stale: Dict[str, str] = {}                # guarded-by: _lock
        self._rr = 0
        self._submitted = 0
        self._retries = 0
        self._drained_requeued = 0
        self._duplicates = 0
        self._router_terminal = 0     # timeouts/failures decided HERE
        self._handoffs = 0            # uids parked on the KV spool
        self._handoff_redelivered = 0  # terminals from redelivered
        #                                handoff admissions (v13)
        # Live migration + elasticity (ISSUE 20, all guarded-by _lock):
        self._migrations = 0          # uids shipped mid-flight
        self._migration_completed = 0  # ...that reached a terminal
        self._migration_redelivered = 0  # terminals from redelivered
        #                                  migration admissions
        self._rebalance_migrations = 0  # migrations THIS router asked
        self._scale_up = 0            # autoscale events (note_autoscale)
        self._scale_down = 0
        self._retired: set = set()    # names out of the routable set
        # KV-pressure rebalance: when the hottest both-role replica's
        # kv_bytes_live exceeds rebalance_kv_ratio x the fleet mean,
        # ask it to migrate one live request to the spool (cooldown
        # hysteresis between asks).  None = off.
        if rebalance_kv_ratio is not None and rebalance_kv_ratio <= 1.0:
            raise ValueError(f"rebalance_kv_ratio must be > 1.0, "
                             f"got {rebalance_kv_ratio}")
        if rebalance_cooldown_s < 0:
            raise ValueError(f"rebalance_cooldown_s must be >= 0, "
                             f"got {rebalance_cooldown_s}")
        self.rebalance_kv_ratio = rebalance_kv_ratio
        self.rebalance_cooldown_s = float(rebalance_cooldown_s)
        self._last_rebalance = 0.0
        self.results: Dict[str, Dict[str, Any]] = {}    # uid -> final event
        # SLO plane (ISSUE 16): with a spec armed, every fleet-terminal
        # event is scored good/bad; verdicts accumulate in _slo_scored
        # (the PURE input summary_record's windows/verdict are computed
        # from — two summary calls agree bit-for-bit) while the window
        # fold in _slo_w backs the emitted slo_window/slo_breach
        # records at every slo_window-event boundary.
        self._slo = None
        self._slo_mod = None
        self.slo_window = int(slo_window)
        self.slo_rollup_s = float(slo_rollup_s)
        self._slo_scored: List[Optional[bool]] = []     # guarded-by: _lock
        self._slo_w: Optional[Dict[str, Any]] = None    # guarded-by: _lock
        self._slo_emitted = 0                           # guarded-by: _lock
        self._slo_last_rollup = time.time()
        if slo:
            if self.slo_window < 1:
                raise ValueError(f"slo_window must be >= 1, "
                                 f"got {slo_window}")
            self._slo_mod = _load_slo()
            self._slo = self._slo_mod._normalize_spec(slo)
        # Multi-tenant plane (ISSUE 19): with --tenants armed, every
        # fleet-terminal event also folds into its tenant's ledger —
        # per-tenant status counts plus (slo armed too) a per-tenant
        # scored list, so fleet_summary carries per-tenant availability
        # and SLO verdicts (the noisy_neighbor assertion surface).
        self._tenants = dict(tenant_specs) if tenant_specs else None
        self._tenant_counts: Dict[str, Dict[str, int]] = {}  # guarded-by: _lock
        self._tenant_scored: Dict[str, List[Optional[bool]]] = {}  # guarded-by: _lock
        # prefix_affinity routing state: block size must match the
        # replicas' KV page size or the chain keys never line up.
        if prefix_block_size < 1:
            raise ValueError(f"prefix_block_size must be >= 1, "
                             f"got {prefix_block_size}")
        self.prefix_block_size = int(prefix_block_size)
        self._prefix_mod = _load_prefix() \
            if policy == "prefix_affinity" else None
        self.scenario: Optional[str] = None
        self.verdict: Optional[str] = None
        self._t0 = time.perf_counter()
        # Trace continuity: the router's trace id is inherited from a
        # parent (APEX_TRACE_ID) or minted here, and EXPORTED so every
        # replica tree spawned after construction joins the timeline.
        self.trace_id = os.environ.get(TRACE_ID_ENV) or self.run_id
        self._tracing = bool(trace)
        # Own lock (not _lock: trace_event is called from inside and
        # outside _lock holders alike): a submit-thread route event and
        # a poll-thread state event racing the lazy clock_sync would
        # both write one — and trace_export --check requires EXACTLY
        # one per stream.
        self._trace_lock = threading.Lock()
        self._trace_synced = False
        if self._tracing:
            os.environ[TRACE_ID_ENV] = self.trace_id
        self._header()

    # --------------------------------------------------------- records

    def _header(self) -> None:
        config: Dict[str, Any] = {
            "policy": self.policy,
            "replicas": list(self._order),
            "max_retries": self.max_retries,
            "breaker_backoff_s": self.breaker_backoff_s,
            "stall_after_s": self.stall_after_s,
            "default_deadline_s": self.default_deadline_s}
        if self._slo is not None:
            # The SPEC announcement ci_gate --slo-stream keys on: a
            # stream with slo_window records but no announced spec (or
            # two) cannot be checked for verdict consistency.
            config["slo"] = dict(self._slo)
            config["slo_window"] = self.slo_window
        if self._tenants is not None:
            # Tenant-spec announcement (v17): ci_gate --tenant-stream
            # checks the fairness ledger against the budgets declared
            # HERE, not against out-of-band flags.
            tcfg: Dict[str, Any] = {}
            for name, ts in self._tenants.items():
                ent: Dict[str, Any] = {
                    "weight": float(getattr(ts, "weight", 1.0)),
                    "slo_class": getattr(ts, "slo_class", "batch")}
                budget = getattr(ts, "budget", None)
                if budget is not None:
                    ent["budget"] = int(budget)
                tcfg[name] = ent
            config["tenants"] = tcfg
        self._stream.write({
            "record": "run_header", "schema": SCHEMA, "time": time.time(),
            "run_id": self.run_id, "num_devices": 0, "process_index": 0,
            "platform": "fleet-router",
            "config": config})

    def _route_rec(self, uid: str, replica: str, attempt: int,
                   reason: str, from_replica: Optional[str]) -> None:
        rec: Dict[str, Any] = {
            "record": "route", "time": time.time(), "request_id": uid,
            "replica": replica, "policy": self.policy,
            "attempt": attempt, "reason": reason, "run_id": self.run_id}
        if from_replica:
            rec["from_replica"] = from_replica
        self._stream.write(rec)
        self.trace_event("i", "route",
                         args={"request_id": uid, "replica": replica,
                               "reason": reason})

    def _state_rec(self, replica: str, state: str,
                   health: Optional[Dict[str, Any]] = None,
                   detail: Optional[str] = None) -> None:
        rec: Dict[str, Any] = {
            "record": "replica_state", "time": time.time(),
            "replica": replica, "state": state, "run_id": self.run_id}
        if health:
            rec["tick"] = int(health.get("tick", 0))
            rec["pending"] = int(health.get("pending", 0))
            rec["blocks_live"] = int(health.get("blocks_live", 0))
            if health.get("classification"):
                rec["classification"] = str(health["classification"])
            if health.get("exit_code") is not None:
                rec["exit_code"] = int(health["exit_code"])
            # v15: re-emit the host-overhead fraction a --tick-profile
            # replica advertises, so fleet streams carry it even when
            # the children's own streams are not collected.
            if health.get("host_overhead_frac") is not None:
                rec["host_overhead_frac"] = float(
                    health["host_overhead_frac"])
            # v17: re-emit the prefix-cache advertisement and the
            # per-tenant admission ledger an armed replica heartbeats —
            # absent on unarmed replicas, so legacy streams are
            # byte-shaped as before.
            if health.get("prefix_keys") is not None:
                rec["prefix_keys"] = list(health["prefix_keys"])
                rec["prefix_shared_tokens"] = int(
                    health.get("prefix_shared_tokens", 0))
                rec["prefix_prompt_tokens"] = int(
                    health.get("prefix_prompt_tokens", 0))
            if health.get("tenant_admitted") is not None:
                rec["tenant_admitted"] = {
                    k: int(v) for k, v
                    in health["tenant_admitted"].items()}
        if detail:
            rec["detail"] = detail
        self._stream.write(rec)
        self.trace_event("i", "replica_state",
                         args={"replica": replica, "state": state})

    def trace_event(self, ph: str, name: str,
                    ts: Optional[float] = None,
                    dur: Optional[float] = None,
                    args: Optional[Dict[str, Any]] = None) -> None:
        """Hard-coded schema-v9 trace_event into the router stream
        (supervisor pattern — the jax-free contract forbids importing
        obs/trace.py, not matching it).  No-op unless ``trace=True``."""
        if not self._tracing:
            return
        with self._trace_lock:
            if not self._trace_synced:
                self._stream.write({
                    "record": "clock_sync", "time": time.time(),
                    "ts": time.perf_counter(), "trace_id": self.trace_id,
                    "run_id": self.run_id})
                self._trace_synced = True
        rec: Dict[str, Any] = {
            "record": "trace_event", "ph": ph, "name": name,
            "ts": time.perf_counter() if ts is None else ts,
            "tid": "router", "trace_id": self.trace_id,
            "run_id": self.run_id}
        if dur is not None:
            rec["dur"] = dur
        if args:
            rec["args"] = args
        self._stream.write(rec)

    # -------------------------------------------------------- breaker

    def _backoff(self, streak: int) -> float:
        return min(self.breaker_backoff_s * (2 ** max(streak - 1, 0)),
                   self.breaker_backoff_max_s)

    def _open_breaker(self, meta: _Meta) -> None:
        """Caller holds ``_lock`` (meta is only reachable through the
        guarded ``_replicas`` map)."""
        meta.breaker = "open"
        meta.fail_streak += 1
        meta.opened_at = time.time()
        meta.probe_uid = None

    def _routable(self, meta: _Meta, now: float) -> bool:
        """Caller holds ``_lock``."""
        if meta.health.get("state") not in ("starting", "healthy"):
            return False
        if meta.breaker == "closed":
            return True
        if meta.breaker == "open":
            if now - meta.opened_at >= self._backoff(meta.fail_streak):
                meta.breaker = "half_open"
                meta.probe_uid = None
                return True
            return False
        return meta.probe_uid is None          # half_open: one probe

    # ------------------------------------------------------- dispatch

    def _pick(self, metas: Dict[str, _Meta], now: float,
              avoid: Tuple[str, ...],
              refused: Tuple[str, ...],
              spec: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Policy selection over the routable set.  Caller holds
        ``_lock`` and passes the guarded ``_replicas`` map in (so the
        guarded name is only ever touched inside the lock).  ``avoid``
        is a preference (the replica a retry/requeue is leaving —
        routed back to only when it is the sole survivor); ``refused``
        is hard (it already refused this spec in this dispatch).
        ``spec`` is the request being placed — prefix_affinity scores
        candidates by it; the other policies ignore it."""
        names = [n for n in self._order
                 if n not in refused
                 and n not in self._retired
                 and self._roles.get(n, "both") != "decode"
                 and self._routable(metas[n], now)]
        preferred = [n for n in names if n not in avoid]
        names = preferred or names
        if not names:
            return None
        if self.policy == "round_robin":
            ordered = self._order[self._rr:] + self._order[:self._rr]
            for n in ordered:
                if n in names:
                    self._rr = (self._order.index(n) + 1) \
                        % len(self._order)
                    return n
            return None

        # least_kv keys on the dtype-accurate byte gauge a v12 replica
        # heartbeats (kv_bytes_live: int8 arenas report their true
        # footprint, so a quantized replica with the same block count
        # advertises the headroom it really has) — but ONLY when every
        # candidate reports it: a pre-v12 child carries no such field,
        # and letting its absence key as 0 bytes would route every
        # request to the oldest replica no matter how loaded it is.
        # Mixed fleets degrade to the block count for everyone.
        # prefix_affinity (v17): candidates are scored by how deep the
        # incoming prompt's block-chain keys overlap the hot-prefix
        # keys each replica ADVERTISES in its heartbeat
        # (replica_state.prefix_keys).  Deepest overlap wins — its KV
        # cache already holds the shared blocks, so routing there turns
        # the fleet's shared-prefix traffic into copy-on-write hits
        # instead of N cold recomputes.  Zero overlap everywhere (cold
        # keys, unarmed replicas, pre-v17 children) degrades to the
        # least_kv load key below — never a dead end.
        if self.policy == "prefix_affinity":
            mod = self._prefix_mod
            prompt = (spec or {}).get("prompt") or ()
            hashes = mod.chain_hashes(prompt, self.prefix_block_size) \
                if prompt else []

            def aff(n: str) -> int:
                adv = metas[n].health.get("prefix_keys")
                if not hashes or not adv:
                    return 0
                return mod.overlap(hashes, adv)
            best = max(aff(n) for n in names)
            if best > 0:
                names = [n for n in names if aff(n) == best]

        use_bytes = self.policy in ("least_kv", "prefix_affinity") \
            and all(metas[n].health.get("kv_bytes_live") is not None
                    for n in names)

        def load_key(n: str):
            if self.policy == "least_pending":
                load = metas[n].health.get("pending", 0)
            elif use_bytes:
                load = metas[n].health["kv_bytes_live"]
            else:
                load = metas[n].health.get("blocks_live", 0)
            return (load, metas[n].inflight, self._order.index(n))
        return min(names, key=load_key)

    def _dispatch(self, uid: str, reason: str,
                  exclude: Tuple[str, ...] = ()) -> Optional[str]:
        """Hand ``uid`` to a replica chosen by the policy; park it in
        the backlog when nothing is routable.  Returns the replica
        name, or None when backlogged/already-terminal."""
        refused: Tuple[str, ...] = ()
        while True:
            now = time.time()
            with self._lock:
                entry = self._inflight.get(uid)
                if entry is None:
                    return None                     # already terminal
                name = self._pick(self._replicas, now, exclude, refused,
                                  entry["spec"])
                if name is None:
                    self._backlog.append(uid)
                    return None
                meta = self._replicas[name]
                meta.dispatches += 1
                meta.inflight += 1
                if meta.breaker == "half_open":
                    meta.probe_uid = uid
                    # Entry-level probe stamp: meta.probe_uid is
                    # cleared by _open_breaker when the health refresh
                    # notices the crash/stall BEFORE the lost event is
                    # absorbed, so the no-charge probe_loss rule needs
                    # a marker that survives the breaker transition.
                    entry["probe"] = name
                else:
                    entry.pop("probe", None)
                entry["replica"] = name
                attempt = entry["attempts"]
                entry["attempts"] += 1
                handle = meta.handle
                spec = entry["spec"]
                src = entry.get("from")
            if handle.submit(spec):
                self._route_rec(uid, name, attempt, reason, src)
                return name
            # Refused at the door (draining/dead under us): undo the
            # booking, remember the refusal, try the next candidate.
            with self._lock:
                meta = self._replicas[name]
                meta.dispatches -= 1
                meta.inflight = max(meta.inflight - 1, 0)
                if meta.probe_uid == uid:
                    meta.probe_uid = None
                ent = self._inflight.get(uid)
                if ent is not None:
                    ent["replica"] = None
                    ent["attempts"] -= 1
                    ent.pop("probe", None)
            refused = refused + (name,)

    # --------------------------------------------------------- intake

    def submit(self, spec: Dict[str, Any]) -> None:
        """Admit one request spec (a plain dict with at least ``uid``,
        ``prompt`` and ``max_new_tokens``) and dispatch it."""
        uid = spec["uid"]
        deadline_s = spec.get("deadline_s", self.default_deadline_s)
        with self._lock:
            if uid in self._inflight or uid in self._done:
                raise ValueError(f"duplicate uid {uid!r}")
            self._inflight[uid] = {
                "spec": spec, "replica": None, "attempts": 0,
                "retries": 0, "from": None,
                "deadline": (time.time() + deadline_s)
                if deadline_s else None}
            self._submitted += 1
        self._dispatch(uid, "dispatch")

    # ----------------------------------------------------- elasticity

    def add_replica(self, handle) -> None:
        """Join a replica to the fleet mid-run (ISSUE 20: the elastic
        pool's scale-up action).  Routable immediately in state
        "starting" — the inbox/queue buffers until it speaks."""
        with self._lock:
            if handle.name in self._replicas:
                raise ValueError(f"duplicate replica {handle.name!r}")
            self._order.append(handle.name)
            self._roles[handle.name] = getattr(handle, "role", "both")
            self._replicas[handle.name] = _Meta(handle)
            self._retired.discard(handle.name)
        self._state_rec(handle.name, "starting")

    def retire_replica(self, name: str) -> None:
        """Remove a replica from the ROUTABLE set (scale-down).  It is
        still polled and harvested — late terminals, drain requeues and
        migrated events must keep landing — the caller owns the actual
        wind-down (typically ``interrupt(mode="migrate")`` so its live
        work ships to peers, then ``stop()``)."""
        with self._lock:
            if name not in self._replicas:
                raise ValueError(f"unknown replica {name!r}")
            self._retired.add(name)
        self._state_rec(name, "draining", detail="retired")

    def note_autoscale(self, direction: str, replica: str,
                       reason: str = "") -> None:
        """Record one elastic-pool action (ISSUE 20): the controller
        calls this alongside add_replica/retire_replica so the
        fleet_summary's scale_up_events/scale_down_events ledger — the
        autoscale_flap oscillation bound — reflects every decision."""
        if direction not in ("up", "down"):
            raise ValueError(f"autoscale direction must be up|down, "
                             f"got {direction!r}")
        with self._lock:
            if direction == "up":
                self._scale_up += 1
            else:
                self._scale_down += 1
        if self.log:
            self.log(f"fleet: autoscale {direction} -> {replica}"
                     + (f" ({reason})" if reason else ""))

    def backlog(self) -> int:
        """Work submitted but not yet admitted to a slot anywhere: the
        router's parked backlog plus every routable replica's reported
        ``pending`` gauge.  The elastic pool's primary scale signal
        (spool depth)."""
        with self._lock:
            return len(self._backlog) + sum(
                int(self._replicas[n].health.get("pending", 0) or 0)
                for n in self._order if n not in self._retired)

    def ttft_p50_ms(self) -> Optional[float]:
        """Fleet-wide TTFT p50 merged from the replicas' heartbeat
        sketches, or None when the SLO plane is unarmed / no sketch has
        samples yet.  The elastic pool's latency scale signal."""
        mod = self._slo_mod
        if mod is None:
            return None
        with self._lock:
            snaps = [self._replicas[n].health.get("slo_sketch")
                     for n in self._order]
        merged = None
        for snap in snaps:
            s = (snap or {}).get("ttft_ms")
            if not isinstance(s, dict) or not s.get("count"):
                continue
            if merged is not None and merged.get("alpha") != s.get("alpha"):
                continue                # mixed-resolution fleet: skip
            merged = mod.sketch_merge(merged, s) if merged is not None \
                else dict(s, buckets=dict(s["buckets"]))
        if merged is None:
            return None
        return float(mod.sketch_percentile(merged, 50))

    def _maybe_rebalance(self) -> None:
        """KV-pressure rebalance (ISSUE 20): when the hottest routable
        both-role replica's dtype-accurate ``kv_bytes_live`` gauge
        exceeds ``rebalance_kv_ratio`` x the fleet mean, ask its handle
        to migrate ONE live request to the spool (``migrate(1)``,
        asynchronous — the effect lands as a "migrated" event).  One
        ask per ``rebalance_cooldown_s``: hysteresis against chasing a
        gauge that is already moving."""
        now = time.time()
        if now - self._last_rebalance < self.rebalance_cooldown_s:
            return
        with self._lock:
            gauges = [(n, self._replicas[n].health.get("kv_bytes_live"))
                      for n in self._order
                      if n not in self._retired
                      and self._roles.get(n, "both") == "both"
                      and self._replicas[n].health.get("state")
                      == "healthy"]
        gauges = [(n, g) for n, g in gauges if g is not None]
        if len(gauges) < 2:
            return
        mean = sum(g for _, g in gauges) / len(gauges)
        if mean <= 0:
            return
        hot_name, hot = max(gauges, key=lambda t: (t[1], t[0]))
        if hot / mean < self.rebalance_kv_ratio:
            return
        with self._lock:
            handle = self._replicas[hot_name].handle
        migrate = getattr(handle, "migrate", None)
        if migrate is None:
            return
        try:
            migrate(1)
        except ValueError:
            return                      # no migration spool on it
        self._last_rebalance = now
        with self._lock:
            self._rebalance_migrations += 1
        if self.log:
            self.log(f"fleet: rebalance — migrating 1 from {hot_name} "
                     f"(kv skew {hot / mean:.2f}x mean)")

    # --------------------------------------------------------- absorb

    def _absorb(self, ev: Dict[str, Any]) -> None:
        uid = ev.get("uid")
        status = ev.get("status")
        src = ev.get("replica")
        with self._lock:
            entry = self._inflight.get(uid)
            if entry is None:
                # Late/duplicate report for an already-terminal uid (a
                # stall-rescued request's original copy finishing, a
                # replayed outbox line): counted, never re-applied.
                # Inflight accounting: decrement ONLY when this report
                # releases a booking still counted live (recorded in
                # _stale when the uid terminated from a different
                # replica) — a report from a replica whose booking was
                # already released at rescue/drain time must not eat an
                # unrelated request's slot (review finding, ISSUE 12).
                if uid in self._done:
                    booked = self._stale.get(uid) == src
                    meta = self._replicas.get(src) if booked else None
                    if booked and status == "handoff":
                        # The decode worker's terminal overtook the
                        # prefill replica's report of the handoff that
                        # fed it (two outboxes polled in turn; a short
                        # request on a fast tick): the handoff happened
                        # and its booking is still live — count it as
                        # one, not as a duplicate.
                        self._handoffs += 1
                        if meta is not None:
                            meta.bump("handoff")
                    else:
                        self._duplicates += 1
                    if booked:
                        del self._stale[uid]
                        if meta is not None:
                            meta.inflight = max(meta.inflight - 1, 0)
                return
            meta = self._replicas.get(src or entry["replica"])
            if status in _TERMINAL:
                self._done[uid] = status
                self._tenant_fold(entry["spec"], status, ev)
                del self._inflight[uid]
                self.results[uid] = ev
                if self._slo is not None:
                    self._slo_absorb(status, ev)
                if entry.get("migrated"):
                    # v18: a request that was live-migrated at least
                    # once reached its terminal — the conservation
                    # counter drain_zero_evictions scores on.
                    self._migration_completed += 1
                if ev.get("redelivered"):
                    # v13/v18: this terminal came from a REDELIVERED
                    # spool admission — the crash-safe lease finished
                    # a request its first consumer dropped.
                    if entry.get("migrated"):
                        self._migration_redelivered += 1
                    else:
                        self._handoff_redelivered += 1
                if meta is not None:
                    meta.bump(status)
                    if entry["replica"] == src:
                        meta.inflight = max(meta.inflight - 1, 0)
                    elif entry["replica"] is not None:
                        # Terminal reported by an ABANDONED copy while
                        # another replica still holds a live booking:
                        # that booking is released when its own report
                        # arrives (the duplicate branch above).
                        self._stale[uid] = entry["replica"]
                    if meta.probe_uid == uid:
                        # The half-open probe's verdict: ok closes the
                        # breaker, anything else re-opens it.
                        if status == "ok":
                            meta.breaker = "closed"
                            meta.fail_streak = 0
                        else:
                            self._open_breaker(meta)
                        meta.probe_uid = None
                return
            if status == "handoff":
                # Disagg (ISSUE 15): the prefill replica cached the
                # prompt, sampled the first token and parked the KV on
                # the spool — its booking releases, but nothing
                # re-routes: the spool IS the channel, and a decode
                # replica's outbox will report the terminal status.
                if src is not None and entry["replica"] != src:
                    self._duplicates += 1
                    return
                entry["replica"] = None
                entry["from"] = src
                entry["stage"] = "spool"
                entry["spooled_at"] = time.time()
                self._handoffs += 1
                if meta is not None:
                    meta.inflight = max(meta.inflight - 1, 0)
                    meta.bump("handoff")
                    if meta.probe_uid == uid:
                        # The probe did its prefill job; the breaker
                        # closes on handoff like on ok.
                        meta.breaker = "closed"
                        meta.fail_streak = 0
                        meta.probe_uid = None
                return
            if status == "migrated":
                # Live migration (ISSUE 20): the source shipped the
                # MID-FLIGHT request — KV blocks, generated tokens,
                # sampler state — to the migration spool.  Its booking
                # releases but nothing re-routes: a peer's leased claim
                # resumes it token-identically and that peer's events
                # finish the uid (the handoff parking shape, plus a
                # sticky "migrated" mark so the terminal counts into
                # the migration conservation ledger).
                if src is not None and entry["replica"] != src:
                    self._duplicates += 1
                    return
                entry["replica"] = None
                entry["from"] = src
                entry["stage"] = "spool"
                entry["spooled_at"] = time.time()
                entry["migrated"] = True
                self._migrations += 1
                if meta is not None:
                    meta.inflight = max(meta.inflight - 1, 0)
                    meta.bump("migrated")
                    if meta.probe_uid == uid:
                        # Shipping its live work IS forward progress;
                        # the breaker closes on migrate like on ok.
                        meta.breaker = "closed"
                        meta.fail_streak = 0
                        meta.probe_uid = None
                return
            # drained / lost: the uid lives on — but only the replica
            # that currently holds it may hand it back (exactly-once
            # per drain: duplicate reports find the entry already
            # moved).  Exception: a SPOOL-stage uid has no holding
            # replica at all — a decode worker that acked its handoff
            # and then died reports it lost, and the router re-routes
            # it through a prefill replica from scratch (the spool file
            # is gone; claimed-but-unacked handoffs redeliver via the
            # lease instead and never reach this branch).
            spool_lost = status == "lost" \
                and entry.get("stage") == "spool" \
                and entry["replica"] is None
            if src is not None and entry["replica"] != src \
                    and not spool_lost:
                self._duplicates += 1
                return
            entry["replica"] = None
            entry["from"] = src
            entry.pop("stage", None)
            entry.pop("spooled_at", None)
            # A spool-lost migrated uid re-serves from scratch: its
            # migration never completed, so the sticky mark must not
            # count the re-serve's terminal into the migration ledger.
            entry.pop("migrated", None)
            probe_loss = status == "lost" and src is not None \
                and entry.pop("probe", None) == src
            if meta is not None:
                if not spool_lost:
                    meta.inflight = max(meta.inflight - 1, 0)
                meta.bump(status)
                if meta.probe_uid == uid:
                    self._open_breaker(meta)
                    meta.probe_uid = None
            now = time.time()
            if status == "drained":
                self._drained_requeued += 1
                action = "requeue_drain"
            else:                                        # lost
                if entry["deadline"] is not None \
                        and now > entry["deadline"]:
                    self._router_done(self._done, self._inflight,
                                      uid, "timeout", src)
                    return
                if probe_loss:
                    # A half-open probe that went down WITH its target
                    # was the ROUTER's gamble, not the request's fault:
                    # re-opening the breaker is the whole verdict, and
                    # the uid keeps its retry budget.  Charging it lets
                    # a permanently wedged replica (hang drill: never
                    # crashes, eats every probe for stall_after_s) burn
                    # the same request's max_retries through repeated
                    # probes until the router kills it "failed" — the
                    # PR-16 straggler-flake root cause.
                    action = "retry"
                elif entry["retries"] >= self.max_retries:
                    self._router_done(self._done, self._inflight,
                                      uid, "failed", src)
                    return
                else:
                    entry["retries"] += 1
                    self._retries += 1
                    action = "retry"
        self._dispatch(uid, action,
                       exclude=(src,) if src else ())

    def _router_done(self, done: Dict[str, str],
                     inflight: Dict[str, Dict[str, Any]], uid: str,
                     status: str, src: Optional[str]) -> None:
        """A terminal decision made by the ROUTER (deadline passed /
        retry budget exhausted).  The caller holds ``_lock`` and passes
        the guarded maps in."""
        done[uid] = status
        self._tenant_fold(inflight[uid]["spec"], status, {})
        del inflight[uid]
        self._router_terminal += 1
        self.results[uid] = {"uid": uid, "status": status,
                             "replica": src, "router_decided": True}
        if self._slo is not None:
            # Router-decided terminals (deadline timeout / retry budget
            # exhausted) are fleet failures too — scored bad like any
            # replica-reported non-ok.
            self._slo_absorb(status, {})

    # --------------------------------------------------------- tenants

    def _tenant_fold(self, spec: Optional[Dict[str, Any]], status: str,
                     ev: Dict[str, Any]) -> None:
        """Fold one fleet-terminal event into its tenant's ledger.
        Takes ``_lock`` (reentrant — callers already inside the absorb
        critical section just re-enter, the _slo_absorb idiom).  No-op
        unless --tenants armed, so legacy fleets pay nothing.  With an
        SLO spec armed too, the event is ALSO scored into the tenant's
        own list — the pure input the per-tenant verdicts in
        fleet_summary are computed from (same score_windows discipline
        as the fleet-level verdict, so two summary calls agree
        bit-for-bit)."""
        if self._tenants is None:
            return
        tenant = (spec or {}).get("tenant", "default")
        with self._lock:
            counts = self._tenant_counts.setdefault(tenant, {})
            counts[status] = counts.get(status, 0) + 1
            if self._slo is not None:
                verdict = self._slo_mod.score_event(
                    self._slo, status, ttft_ms=ev.get("ttft_ms"),
                    tpot_ms=ev.get("tpot_ms"))
                self._tenant_scored.setdefault(tenant, []).append(
                    verdict)

    # ------------------------------------------------------------- slo

    def _slo_absorb(self, status: str, ev: Dict[str, Any]) -> None:
        """Score one fleet-terminal event against the armed SLO spec
        and fold it into the current tumbling window.  Takes ``_lock``
        (reentrant — callers already inside the absorb critical section
        just re-enter).  Latencies ride the replica events themselves
        (``ttft_ms``/``tpot_ms``, v14 outbox/harvest fields); a
        router-decided terminal carries none and scores bad."""
        mod = self._slo_mod
        verdict = mod.score_event(self._slo, status,
                                  ttft_ms=ev.get("ttft_ms"),
                                  tpot_ms=ev.get("tpot_ms"))
        with self._lock:
            self._slo_scored.append(verdict)
            w = self._slo_w
            if w is None:
                w = self._slo_w = {
                    "requests": 0, "good": 0, "bad": 0, "counts": {},
                    "ttft": mod.sketch_new(mod.DEFAULT_ALPHA),
                    "tpot": mod.sketch_new(mod.DEFAULT_ALPHA)}
            w["requests"] += 1
            w["counts"][status] = w["counts"].get(status, 0) + 1
            if verdict is True:
                w["good"] += 1
            elif verdict is False:
                w["bad"] += 1
            if status == "ok":
                if ev.get("ttft_ms") is not None:
                    mod.sketch_add(w["ttft"], ev["ttft_ms"])
                if ev.get("tpot_ms") is not None:
                    mod.sketch_add(w["tpot"], ev["tpot_ms"])
            if w["requests"] >= self.slo_window:
                self._slo_close_window()

    def _slo_close_window(self) -> None:
        """Emit the current window as an ``slo_window`` record (plus an
        ``slo_breach`` past burn 1.0).  Takes ``_lock`` (reentrant; the
        stream's internal lock never takes ours, so writing here cannot
        deadlock).  Windows are event-count tumbling (every
        ``slo_window`` fleet-terminal events) — deterministic for a
        fixed workload, unlike wall-clock windows."""
        mod = self._slo_mod
        with self._lock:
            w = self._slo_w
            if w is None or w["requests"] == 0:
                return
            self._slo_w = None
            idx = self._slo_emitted
            self._slo_emitted += 1
        burn = mod.burn_rate(w["good"], w["bad"],
                             self._slo["availability"])
        rec: Dict[str, Any] = {
            "record": "slo_window", "time": time.time(),
            "window": idx, "requests": w["requests"],
            "good": w["good"], "bad": w["bad"], "burn_rate": burn,
            "counts": dict(w["counts"]), "run_id": self.run_id}
        if w["ttft"]["count"]:
            rec["ttft_ms"] = mod.sketch_summary(w["ttft"])
        if w["tpot"]["count"]:
            rec["tpot_ms"] = mod.sketch_summary(w["tpot"])
        self._stream.write(rec)
        if burn > 1.0:
            self._stream.write({
                "record": "slo_breach", "time": time.time(),
                "window": idx, "burn_rate": burn,
                "requests": w["requests"], "good": w["good"],
                "bad": w["bad"],
                "budget": 1.0 - self._slo["availability"],
                "run_id": self.run_id})

    def _slo_rollup(self, force: bool = False) -> None:
        """Merge the replicas' heartbeat latency sketches
        (``replica_state.slo_sketch``, tailed into each meta's health
        snapshot) into one fleet-level ``fleet_rollup`` record —
        cross-replica percentiles without re-pooling raw samples, plus
        per-replica p50 skew and the straggler's name.  Wall-clock
        rate-limited to ``slo_rollup_s`` (``force`` bypasses the
        limiter — the close-time last-chance rollup); emitted only when
        at least one replica contributed data (determinism tests
        compare score dicts, never rollup timing)."""
        now = time.time()
        if not force and now - self._slo_last_rollup < self.slo_rollup_s:
            return
        self._slo_last_rollup = now
        mod = self._slo_mod
        with self._lock:
            snaps = [(n, self._replicas[n].health.get("slo_sketch"))
                     for n in self._order]
        merged: Dict[str, Any] = {}
        per_replica: Dict[str, Any] = {}
        for name, sk in snaps:
            if not isinstance(sk, dict):
                continue
            for key in ("ttft_ms", "tpot_ms"):
                s = sk.get(key)
                if not isinstance(s, dict) or not s.get("count"):
                    continue
                if key in merged and merged[key]["alpha"] != s["alpha"]:
                    continue        # unmergeable error bounds: skip
                merged[key] = mod.sketch_merge(merged[key], s) \
                    if key in merged \
                    else dict(s, buckets=dict(s["buckets"]))
                if key == "ttft_ms":
                    per_replica[name] = {
                        "count": int(s["count"]),
                        "p50": mod.sketch_percentile(s, 50)}
        total = sum(v["count"] for v in per_replica.values())
        if total == 0:
            return
        rec: Dict[str, Any] = {
            "record": "fleet_rollup", "time": now,
            "replicas": len(per_replica), "count": total,
            "per_replica": per_replica, "run_id": self.run_id}
        if "ttft_ms" in merged:
            rec["ttft_ms"] = mod.sketch_summary(merged["ttft_ms"])
        if "tpot_ms" in merged:
            rec["tpot_ms"] = mod.sketch_summary(merged["tpot_ms"])
        if len(per_replica) >= 2:
            p50s = sorted((v["p50"], n) for n, v in per_replica.items())
            med = p50s[len(p50s) // 2][0]
            if med > 0:
                rec["skew"] = round(p50s[-1][0] / med, 3)
                rec["straggler"] = p50s[-1][1]
        self._stream.write(rec)

    # ----------------------------------------------------------- poll

    def _refresh_health(self) -> None:
        """Snapshot every handle's health (outside the lock — proc
        handles do bounded file tails) and act on transitions: crashed
        replicas open their breaker and surface their in-flight uids
        as lost; stalled replicas (no progress for ``stall_after_s``
        while holding work) are treated the same."""
        snaps = []
        with self._lock:
            handles = [(n, self._replicas[n].handle)
                       for n in self._order]
        for name, handle in handles:
            snaps.append((name, handle.state()))
        rescue: List[Dict[str, Any]] = []
        for name, snap in snaps:
            with self._lock:
                meta = self._replicas[name]
                meta.health = snap
                state = snap.get("state")
                stalled = (self.stall_after_s is not None
                           and state == "healthy" and meta.inflight > 0
                           and snap.get("progress_age_s", 0.0)
                           > self.stall_after_s)
                if stalled:
                    state = "stalled"
                    meta.health = dict(snap, state="stalled")
                newly_down = state in ("crashed", "stalled") \
                    and meta.emitted_state not in ("crashed", "stalled")
                if newly_down:
                    self._open_breaker(meta)
                    # Everything this replica holds is not coming
                    # back on its own: surface as lost for the
                    # deadline-aware retry path.  (A crashed
                    # ThreadReplica reports its own lost set via
                    # poll(); the src-match guard in _absorb dedupes.)
                    if state == "stalled":
                        rescue.extend(
                            {"uid": u, "status": "lost",
                             "replica": name}
                            for u, e in self._inflight.items()
                            if e["replica"] == name)
                emit = state != meta.emitted_state
                if emit:
                    meta.emitted_state = state
            if emit:
                self._state_rec(name, state, snap)
                if self.log and state in ("crashed", "stalled"):
                    self.log(f"fleet: replica {name} {state} "
                             f"(breaker open)")
        for ev in rescue:
            self._absorb(ev)

    def poll(self) -> int:
        """One router turn: refresh health, harvest replica events,
        requeue/retry, drain the backlog.  Returns the number of
        events absorbed."""
        self._refresh_health()
        if self._slo is not None:
            self._slo_rollup()
        if self.rebalance_kv_ratio is not None:
            self._maybe_rebalance()
        with self._lock:
            handles = [(n, self._replicas[n].handle)
                       for n in self._order]
        events: List[Dict[str, Any]] = []
        for name, handle in handles:
            for ev in handle.poll():
                ev.setdefault("replica", name)
                events.append(ev)
        for ev in events:
            self._absorb(ev)
        # Backlog: one dispatch attempt per uid per poll (a failed
        # attempt re-parks it).
        with self._lock:
            parked = list(self._backlog)
            self._backlog.clear()
        now = time.time()
        for uid in parked:
            expired = False
            with self._lock:
                entry = self._inflight.get(uid)
                if entry is None:
                    continue
                if entry["deadline"] is not None \
                        and now > entry["deadline"]:
                    self._router_done(self._done, self._inflight,
                                      uid, "timeout", None)
                    expired = True
            if not expired:
                self._dispatch(uid, "backlog")
        # Stale-spool sweep: a uid whose handoff was acked by a worker
        # that then died leaves NO claim to redeliver and NO process to
        # report it lost (a crashed ThreadReplica reports its acked
        # set; a kill -9'd proc child cannot) — presumed lost after
        # spool_timeout_s and re-routed through prefill from scratch.
        if self.spool_timeout_s is not None:
            now = time.time()
            with self._lock:
                stale = [u for u, e in self._inflight.items()
                         if e.get("stage") == "spool"
                         and now - e.get("spooled_at", now)
                         > self.spool_timeout_s]
            for uid in stale:
                if self.log:
                    self.log(f"fleet: {uid} stale on the spool "
                             f"(> {self.spool_timeout_s}s) — "
                             "re-routing through prefill")
                self._absorb({"uid": uid, "status": "lost",
                              "replica": None})
        return len(events)

    def done(self) -> bool:
        with self._lock:
            return not self._inflight

    def replica_state(self, name: str) -> Optional[str]:
        """The ROUTER's view of one replica (breaker/stall verdicts
        included — a stalled replica reports "healthy" about itself)."""
        with self._lock:
            meta = self._replicas.get(name)
            return meta.emitted_state if meta is not None else None

    def run(self, timeout_s: float = 120.0,
            poll_interval_s: float = 0.01) -> bool:
        """Poll until every submitted uid is terminal (True) or the
        timeout passes (False — the leftovers count as ``lost``)."""
        t0 = time.time()
        while time.time() - t0 < timeout_s:
            self.poll()
            if self.done():
                return True
            time.sleep(poll_interval_s)
        return self.done()

    # -------------------------------------------------------- summary

    def summary_record(self) -> Dict[str, Any]:
        with self._lock:
            done = dict(self._done)
            lost = len(self._inflight)
            per_replica: Dict[str, Any] = {}
            dispatches: Dict[str, int] = {}
            for name in self._order:
                meta = self._replicas[name]
                per_replica[name] = dict(meta.counts)
                per_replica[name]["dispatches"] = meta.dispatches
                ok_r = meta.counts.get("ok", 0)
                # A handed-off request continues on a decode replica,
                # and a migrated one on a peer — like a drain they
                # leave this replica's availability denominator (the
                # destination owns the outcome).
                owned = sum(v for k, v in meta.counts.items()
                            if k not in ("drained", "lost", "handoff",
                                         "migrated"))
                per_replica[name]["availability"] = round(
                    ok_r / owned, 3) if owned else 1.0
                per_replica[name]["state"] = \
                    meta.health.get("state", "?")
                role = self._roles.get(name, "both")
                if role != "both":
                    per_replica[name]["role"] = role
                dispatches[name] = meta.dispatches
            submitted = self._submitted
            retries = self._retries
            requeued = self._drained_requeued
            dups = self._duplicates
            handoffs = self._handoffs
            redelivered = self._handoff_redelivered
            in_spool = sum(1 for e in self._inflight.values()
                           if e.get("stage") == "spool")
            migrations = self._migrations
            migration_completed = self._migration_completed
            migration_redelivered = self._migration_redelivered
            rebalanced = self._rebalance_migrations
            scale_up = self._scale_up
            scale_down = self._scale_down
            slo_scored = list(self._slo_scored)
            tenant_counts = {t: dict(c) for t, c
                             in self._tenant_counts.items()}
            tenant_scored = {t: list(s) for t, s
                             in self._tenant_scored.items()}
            health_snaps = [dict(self._replicas[n].health)
                            for n in self._order]
        ok = sum(1 for s in done.values() if s == "ok")
        terminal = len(done)
        counts = {s: sum(1 for v in done.values() if v == s)
                  for s in _TERMINAL}
        # Balance skew over DISPATCHABLE replicas only: decode workers
        # are never routed prompts, so counting their structural zeros
        # would read every disagg fleet as imbalanced.
        vals = [v for n, v in dispatches.items()
                if self._roles.get(n, "both") != "decode"]
        mean = sum(vals) / len(vals) if vals else 0.0
        skew = round(max(vals) / mean, 3) if mean else 0.0
        rec: Dict[str, Any] = {
            "record": "fleet_summary",
            "time": time.time(),
            "replicas": len(self._order),
            "requests": submitted,
            "policy": self.policy,
            "duration_s": round(time.perf_counter() - self._t0, 3),
            "completed": counts["ok"],
            "failed": counts["failed"],
            "timed_out": counts["timeout"],
            "shed": counts["shed"],
            "cancelled": counts["cancelled"],
            "rejected": counts["rejected"],
            "drained_requeued": requeued,
            "retries": retries,
            "duplicates": dups,
            "lost": lost,
            "availability": round(ok / terminal, 3) if terminal else 1.0,
            "per_replica": per_replica,
            "routing": {"dispatches": dispatches,
                        "balance_skew": skew},
            "run_id": self.run_id,
        }
        n_prefill = sum(1 for r in self._roles.values()
                        if r == "prefill")
        n_decode = sum(1 for r in self._roles.values() if r == "decode")
        if n_prefill or n_decode:
            # v13 disagg topology fields: only a disaggregated fleet
            # carries them, so homogeneous streams stay byte-stable.
            rec["prefill_replicas"] = n_prefill
            rec["decode_replicas"] = n_decode
            rec["handoffs"] = handoffs
            rec["handoff_redelivered"] = redelivered
            rec["in_spool"] = in_spool
        if migrations:
            # v18 migration conservation ledger: only a fleet that
            # actually live-migrated carries these, so legacy streams
            # stay byte-stable.
            rec["migrations"] = migrations
            rec["migration_completed"] = migration_completed
            rec["migration_redelivered"] = migration_redelivered
            if rebalanced:
                rec["rebalance_migrations"] = rebalanced
            if "in_spool" not in rec:
                rec["in_spool"] = in_spool
        if scale_up or scale_down:
            # v18 autoscale ledger (the autoscale_flap oscillation
            # bound) — absent on fixed-size fleets.
            rec["scale_up_events"] = scale_up
            rec["scale_down_events"] = scale_down
        if self._slo is not None:
            # v14 SLO verdict: computed PURELY from the scored-event
            # list (score_windows chunks it exactly as the emission
            # windows did), so the two summary_record calls in
            # close()'s path agree and match the emitted records.
            mod = self._slo_mod
            wins = mod.score_windows(slo_scored, self.slo_window,
                                     self._slo["availability"])
            breaches = sum(1 for w in wins if w["burn_rate"] > 1.0)
            wi, wb = mod.worst_window(wins)
            rec["slo_verdict"] = "fail" if breaches else "pass"
            rec["slo_windows"] = len(wins)
            rec["slo_breaches"] = breaches
            rec["slo_worst_burn"] = wb
            if wi is not None:
                rec["slo_worst_window"] = wi
        if self._tenants is not None:
            # v17 per-tenant ledger: status counts + availability per
            # tenant, the spec's declared shape (weight/class/budget),
            # admitted tokens folded from the replicas' heartbeat
            # ledgers, and — SLO armed — a per-tenant verdict computed
            # PURELY from the tenant's scored list (same score_windows
            # discipline as the fleet verdict: two summary calls agree
            # bit-for-bit).  This block is the noisy_neighbor
            # assertion surface: fair keeps the victim's verdict
            # "pass" where FIFO demonstrably breaches it.
            admitted: Dict[str, int] = {}
            for h in health_snaps:
                for t, v in (h.get("tenant_admitted") or {}).items():
                    admitted[t] = admitted.get(t, 0) + int(v)
            tnames = list(self._tenants)
            for extra in (tenant_counts, admitted):
                for t in extra:
                    if t not in tnames:
                        tnames.append(t)
            tenants_rec: Dict[str, Any] = {}
            for t in tnames:
                c = tenant_counts.get(t, {})
                ok_t = c.get("ok", 0)
                term_t = sum(c.values())
                ent: Dict[str, Any] = {
                    "counts": c,
                    "availability": round(ok_t / term_t, 3)
                    if term_t else 1.0}
                ts = self._tenants.get(t)
                if ts is not None:
                    ent["weight"] = float(getattr(ts, "weight", 1.0))
                    ent["slo_class"] = getattr(ts, "slo_class", "batch")
                    budget = getattr(ts, "budget", None)
                    if budget is not None:
                        ent["budget"] = int(budget)
                if t in admitted:
                    ent["admitted_tokens"] = admitted[t]
                if self._slo is not None:
                    mod = self._slo_mod
                    wins = mod.score_windows(
                        tenant_scored.get(t, []), self.slo_window,
                        self._slo["availability"])
                    t_breaches = sum(1 for w in wins
                                     if w["burn_rate"] > 1.0)
                    ent["slo_verdict"] = "fail" if t_breaches \
                        else "pass"
                    ent["slo_breaches"] = t_breaches
                tenants_rec[t] = ent
            rec["tenants"] = tenants_rec
        # v17 fleet-level prefix hit rate: raw reuse counters summed
        # over every advertising replica's latest heartbeat — absent
        # entirely on unarmed fleets (byte-stable legacy streams).
        shared_tok = prompt_tok = 0
        prefix_armed = False
        for h in health_snaps:
            if h.get("prefix_prompt_tokens") is not None:
                prefix_armed = True
                shared_tok += int(h.get("prefix_shared_tokens", 0))
                prompt_tok += int(h.get("prefix_prompt_tokens", 0))
        if prefix_armed:
            rec["prefix_hit_rate"] = round(shared_tok / prompt_tok, 4) \
                if prompt_tok else 0.0
        if self.scenario:
            rec["scenario"] = self.scenario
        if self.verdict:
            rec["verdict"] = self.verdict
        return rec

    def close(self) -> Dict[str, Any]:
        """Write the fleet_summary and close the stream; returns the
        summary record."""
        # Last-chance re-snapshot: a short run's final heartbeat (the
        # one carrying nonzero sketches / a settled overhead fraction)
        # often lands AFTER the last poll, so poll state() once more
        # now.  Only the slo_sketch key is folded back into health and
        # only profiler-armed replicas get a closing replica_state —
        # close-time is not the place to act on state transitions, and
        # an unarmed fleet's stream is byte-shaped as before.
        with self._lock:
            handles = [(n, self._replicas[n].handle)
                       for n in self._order]
        for name, handle in handles:
            try:
                snap = handle.state()
            except Exception:
                continue
            if not isinstance(snap, dict):
                continue
            if self._slo is not None and "slo_sketch" in snap:
                with self._lock:
                    meta = self._replicas[name]
                    meta.health = dict(
                        meta.health, slo_sketch=snap["slo_sketch"])
            # v17: the FINAL prefix counters / tenant ledger are what
            # the summary's prefix_hit_rate and admitted_tokens should
            # reflect — a short run's last heartbeat (the one with the
            # settled totals) often lands after the last poll.
            late = {k: snap[k] for k in
                    ("prefix_keys", "prefix_shared_tokens",
                     "prefix_prompt_tokens", "tenant_admitted")
                    if k in snap}
            if late:
                with self._lock:
                    meta = self._replicas[name]
                    meta.health = dict(meta.health, **late)
            if snap.get("host_overhead_frac") is not None:
                # v15: the cumulative fraction is only meaningful once
                # the run is over — state transitions rarely fire late
                # enough to re-emit it, so the closing record is what
                # fleet_report and perf_ledger actually rank on.
                with self._lock:
                    state = self._replicas[name].emitted_state \
                        or "healthy"
                self._state_rec(name, state, snap)
        if self._slo is not None:
            self._slo_rollup(force=True)
            # Trailing partial window: emitted before the summary so
            # the stream's slo_window count matches the summary's
            # windows field (score_windows includes the partial too).
            with self._lock:
                self._slo_close_window()
        rec = self.summary_record()
        self._stream.write(rec)
        self._stream.close()
        return rec
