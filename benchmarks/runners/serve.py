"""Runner for serving cells.  It knows no model: the configuration file
names the builder and the reference, the traffic file every parameter of
the mix, the engine's geometry included.

Load is an open loop at the rate fixed in the traffic file, offered by this
one thread between engine ticks: a request is submitted once its due
instant has passed, and every latency is taken from that due instant.
Arrivals start ``ramp_s`` before the window so that it opens at steady
occupancy; only requests due inside the window are counted.  After the
window the engine is stepped until those have finished (``drain_grace_s``
at most); one that has not is a failed request.  Judged is the median
over the window's requests of every gap between two consecutive tokens of
one request (``time_per_token`` below); the per-request means, the time to
first token, the wait for a slot and how late the generator ran go to the
run's notes (at this load they swing with every burst or stall: PERF.md
section 2).

``correct``: once the window has closed, a sample of the requests it
finished, drawn from the seed with the longest in it, goes through the
plain reference, once over each prompt with its served tokens; the number
compared is the widest gap by which a served (greedy) token's logit lies
below the reference's best at its position.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import harness, loadgen


class Cell:
    """The system under test for one serving cell."""

    def __init__(self, cfg: Dict, trf: Dict, devices):
        self.cfg, self.trf, self.devices = cfg, trf, devices
        mspec = cfg["model"]
        self.model = harness.resolve(mspec["builder"])(**mspec["kwargs"])
        self.ref, self.prefix = harness.load_reference(cfg["reference"])
        self.rcfg = cfg["reference_cfg"]
        weights = getattr(self.ref, self.prefix + "_weights")
        self.weights = jax.jit(lambda k: weights(k, self.rcfg)["params"])
        self.vocab = mspec["kwargs"]["vocab_size"]

    def engine(self, key, break_step: Optional[str] = None,
               control: bool = False):
        """The engine over seeded weights.  ``control`` switches on the
        program's own lower-precision path that the configuration names
        (the control of 'How correct is decided'; never a benchmark run)."""
        from apex_example_tpu.serve import ServeEngine
        e = self.trf["engine"]
        params, extra = self.weights(key), {}
        if control:
            spec = self.cfg["control"]
            params, _ = harness.resolve(spec["quantize"])(params,
                                                          spec["mode"])
            extra = spec["engine_kwargs"]
        eng = ServeEngine(self.model, params,
                          num_slots=e["slots"], max_len=e["max_len"],
                          block_size=e["block_size"],
                          rng=jax.random.fold_in(key, 1), **extra)
        if break_step == "alter_token":
            # the tests' way of breaking the timed path underneath: every
            # token is altered where it is produced
            real, vocab = eng._step_fn, self.vocab

            def altered(*a):
                cache, nxt, finite = real(*a)
                return cache, (nxt + 1) % vocab, finite
            eng._step_fn = altered
        return eng

    def gaps(self, key, ids: np.ndarray) -> np.ndarray:
        """One reference pass over whole sequences ``ids`` [n, L]: at each
        position t, how far the logit of the token that follows
        (``ids[:, t + 1]``) lies below the reference's best.  Worked out on
        the device; only [n, L - 1] floats come back."""
        fn = getattr(self.ref, self.prefix + "_logits")

        def gap(k, x):
            ref = fn(self.weights(k), x, self.rcfg, "highest")[:, :-1]
            nxt = jnp.take_along_axis(ref, x[:, 1:, None], -1)[..., 0]
            return ref.max(-1) - nxt

        return np.asarray(jax.jit(gap)(key, jnp.asarray(ids)))


def _request(item: loadgen.Scheduled):
    from apex_example_tpu.serve import Request
    return Request(prompt=item.prompt, max_new_tokens=item.max_new,
                   temperature=0.0, uid=f"r{item.index}")


def drive(sut: Cell, eng, plan: List[loadgen.Scheduled], seconds: float,
          spans: harness.Spans, compiles, trace_at: float = float("inf"),
          trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Warm the engine's one program, then offer ``plan`` on the wall
    clock.  Returns the stamps; metrics are worked out by the caller."""
    from apex_example_tpu.serve import Request
    trf = sut.trf
    ramp, grace = trf["ramp_s"], trf["drain_grace_s"]
    eng.submit(Request(prompt=[1] * (trf["engine"]["block_size"] + 1),
                       max_new_tokens=2, uid="warm"))
    while not any(c.request.uid == "warm" for c in eng.completions):
        eng.step()
    harness.note("engine warm; arrivals start")
    counted = {f"r{p.index}" for p in plan if p.due_s >= ramp}
    ticks: List[tuple] = []      # (end, duration, live, live_tokens, tokens)
    late: Dict[str, float] = {}
    nxt = 0
    tracing = False
    t_trace = t_trace_end = None
    kv_per_tok = eng.pool.kv_bytes_per_token()
    origin = time.perf_counter()
    w0, w1 = origin + ramp, origin + ramp + seconds
    while True:
        now = time.perf_counter()
        if not compiles.armed and now >= w0:
            compiles.armed = True
        if compiles.armed and now >= w1:
            compiles.armed = False
        if not tracing and trace_dir and w0 + trace_at <= now < w1:
            jax.profiler.start_trace(trace_dir)
            spans.annotate = tracing = True
            t_trace = time.perf_counter()
        if tracing and t_trace_end is None and now >= w1:
            t_trace_end = now
            jax.profiler.stop_trace()
            spans.annotate = False
        with spans.span("bench.submit"):
            while nxt < len(plan) and origin + plan[nxt].due_s <= now:
                req = _request(plan[nxt])
                eng.submit(req)
                late[req.uid] = time.perf_counter() \
                    - (origin + plan[nxt].due_s)
                nxt += 1
        if now >= w1:
            done = {c.request.uid for c in eng.completions}
            if counted <= done or now >= w1 + grace:
                break
        t0 = time.perf_counter()
        with spans.span("bench.engine_step"):
            ran = eng.step()
        t1 = time.perf_counter()
        if ran:
            ticks.append((t1, t1 - t0, len(eng.pool.live),
                          eng.pool.kv_bytes_live() / kv_per_tok,
                          eng.tokens_sampled))
        elif nxt < len(plan):
            time.sleep(max(0.0, min(0.001, origin + plan[nxt].due_s
                                    - time.perf_counter())))
        else:
            time.sleep(0.001)
    compiles.armed = False
    if tracing and t_trace_end is None:
        t_trace_end = time.perf_counter()
        jax.profiler.stop_trace()
        spans.annotate = False
    return {"origin": origin, "w0": w0, "w1": w1, "ticks": ticks,
            "late": late, "counted": counted, "t_end": time.perf_counter(),
            "pool_tokens": eng.pool.num_blocks * trf["engine"]["block_size"],
            "traced_s": (t_trace_end - t_trace) if tracing else None}


def served_gaps(sut: Cell, key, sample) -> Dict[str, float]:
    """Widest gap below the reference's best logit over the served tokens of
    ``sample`` (completions), and the share of them that are not the
    reference's first place."""
    L = sut.trf["engine"]["max_len"]
    ids = np.zeros((len(sample), L), np.int32)
    for r, c in enumerate(sample):
        seq = list(c.request.prompt) + list(c.tokens)
        ids[r, :len(seq)] = seq
    got = sut.gaps(key, ids)
    gaps = np.concatenate([
        got[r, len(c.request.prompt) - 1:
            len(c.request.prompt) - 1 + len(c.tokens)]
        for r, c in enumerate(sample)])
    return {"served_logit_gap": float(gaps.max()),
            "served_off_first_share": float(np.mean(gaps > 0)),
            "served_tokens": int(gaps.size)}


def pick_sample(done_ok, seed: int, n: int):
    """The longest finished request and ``n - 1`` more, drawn by the seed."""
    if not done_ok:
        return []
    order = sorted(done_ok, key=lambda c: (len(c.request.prompt)
                                           + len(c.tokens), c.request.uid))
    longest, rest = order[-1], order[:-1]
    rng = np.random.default_rng(int(seed) + 1)
    take = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(take)]


def request_ticks(starts: np.ndarray, ends: np.ndarray, t_first: float,
                  t_finish: float, n: int, tolerance: float):
    """(i0, i1): the ticks that delivered the first and the last of one
    request's ``n`` tokens.  Tick ``i`` ran over ``[starts[i], ends[i]]`` on
    the harness's clock and the engine stamps a token inside the tick that
    delivers it, so a stamp belongs to the tick that holds it (an end lies a
    little after the stamps taken in its tick: never matched by ``>``), and
    ticks ``i0 + 1 .. i1`` deliver one token each: the request's gaps are
    ``diff(ends[i0:i1 + 1])``.  None where that does not hold (a stamp no
    tick holds; another count of ticks than ``n - 1``: preempted, migrated,
    several tokens a tick) or where those gaps do not sum to ``t_finish -
    t_first`` within ``tolerance``."""
    def holding(t):
        i = int(np.searchsorted(ends, t, side="left"))
        return i if i < len(ends) and starts[i] <= t else None

    i0, i1 = holding(t_first), holding(t_finish)
    if i0 is None or i1 is None or i1 - i0 != n - 1:
        return None
    if abs((ends[i1] - ends[i0]) - (t_finish - t_first)) > tolerance:
        return None
    return i0, i1


def time_per_token(ticks, requests, t_end: float) -> Dict[str, Any]:
    """Both readings of the time per output token, in ms, over ``requests``
    = (due instant, tokens asked for, completion or None where it failed or
    did not finish); ``ticks`` are all of ``drive``'s, ramp and drain
    included, ``t_end`` the drain's end.

    ``gaps`` (judged by its median): every gap between two consecutive
    tokens of one request, each one whole tick-to-tick interval of the
    harness's clock (``request_ticks``).  Nothing is dropped and nothing
    estimated: a request whose ticks do not match its tokens one for one
    gives ``n - 1`` copies of its own mean, a failed one ``asked - 1``
    copies of the drain's end less its due instant.  ``per_request``: each
    request's mean ``(t_finish - t_first_token) / (n - 1)`` on the engine's
    stamps, a failed one once at the drain's end less its due instant (the
    reading judged before PR 30; ``tpot_ms_p95`` still reads it).
    ``matched`` requests gave gaps of their own, ``stalled`` of them held
    one of over twice the median gap, ``stamp_to_end_ms`` is how long after
    the engine's stamp of a last token its tick ended, at most, and ``p50``
    is the judged number: the nearest-rank median of ``gaps``."""
    ends = np.array([t[0] for t in ticks], np.float64)
    starts = ends - np.array([t[1] for t in ticks], np.float64)
    tolerance = float(np.median(ends - starts)) / 2 if len(ticks) else 0.0
    per_request, gaps, own, lag = [], [], [], 0.0
    for due, asked, c in requests:
        if c is None:
            per_request.append((t_end - due) * 1e3)
            gaps.append(np.full(max(asked - 1, 1), per_request[-1]))
            continue
        n = len(c.tokens)
        if n < 2:
            continue
        per_request.append((c.t_finish - c.t_first_token) / (n - 1) * 1e3)
        found = request_ticks(starts, ends, c.t_first_token, c.t_finish, n,
                              tolerance)
        if found is None:
            gaps.append(np.full(n - 1, per_request[-1]))
            continue
        own.append(np.diff(ends[found[0]:found[1] + 1]) * 1e3)
        gaps.append(own[-1])
        lag = max(lag, (ends[found[1]] - c.t_finish) * 1e3)
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    long = 2 * float(np.median(gaps)) if gaps.size else 0.0
    return {"per_request": per_request, "gaps": gaps, "matched": len(own),
            "p50": harness.quantile(gaps.tolist(), 50) if gaps.size else None,
            "stalled": sum(bool((g > long).any()) for g in own),
            "stamp_to_end_ms": lag}


def note_time_per_token(per_token: Dict[str, Any], in_window) -> None:
    """Both medians side by side, and what the window's tick-to-tick
    interval does with the live slots (the rest of a spread: PERF.md)."""
    means = per_token["per_request"]
    harness.note(
        f"time per output token, ms: median of {per_token['gaps'].size} "
        f"gaps {per_token['p50'] or 0.0:.4f} (judged), median of "
        f"{len(means)} requests' means "
        f"{harness.quantile(means or [0.0], 50):.4f}; "
        f"{per_token['matched']} requests' ticks match their tokens, "
        f"{per_token['stalled']} of them held a gap of over twice the "
        f"median; a tick ends {per_token['stamp_to_end_ms']:.3f} after the "
        "engine's stamp at most")
    if len(in_window) < 3:
        return
    every = np.diff([t[0] for t in in_window]) * 1e3
    live = np.array([t[2] for t in in_window[1:]], np.float64)
    slope, alone = np.polyfit(live, every, 1) if np.ptp(live) \
        else (0.0, every.mean())
    harness.note(f"tick to tick in the window, ms: median "
                 f"{np.median(every):.4f}, mean {every.mean():.4f} at "
                 f"{live.mean():.2f} live slots; least squares "
                 f"{alone:.3f} + {slope:.4f} a live slot")


def run(cell, cfg, trf, limits, args, devices, t_process, spans,
        compiles, break_step=None) -> Dict[str, Any]:
    key = harness.seed_key(args.seed)
    sut = Cell(cfg, trf, devices)
    eng = sut.engine(key, break_step)
    harness.note("built the engine")
    plan = loadgen.schedule(trf["mix"], args.seed, trf["ramp_s"],
                            args.seconds, sut.vocab)
    trace_dir = harness.trace_dir() if args.trace else None
    trace_at = max(0.0, args.seconds - trf["trace_seconds"])
    out = drive(sut, eng, plan, args.seconds, spans, compiles,
                trace_at=trace_at, trace_dir=trace_dir)
    harness.note(f"drained {out['t_end'] - out['w1']:.1f} s after the window")
    device = harness.device_record(devices)
    trace = None
    if args.trace:
        from benchmarks import trace as trace_lib
        trace = trace_lib.reduce_dir(trace_dir, out["traced_s"])

    origin, w0, w1 = out["origin"], out["w0"], out["w1"]
    due = {f"r{p.index}": origin + p.due_s for p in plan}
    asked = {f"r{p.index}": p.max_new for p in plan}
    comps = {c.request.uid: c for c in eng.completions}
    ok, ttft, wait, requests, miscount = [], [], [], [], 0
    for uid in sorted(out["counted"]):
        c = comps.get(uid)
        if c is None or c.status != "ok":
            # failed or unfinished: it misses both latencies; the drain's
            # end is the least either can have been
            ttft.append((out["t_end"] - due[uid]) * 1e3)
            requests.append((due[uid], asked[uid], None))
            continue
        ok.append(c)
        requests.append((due[uid], asked[uid], c))
        miscount += int(len(c.tokens) != asked[uid])
        ttft.append((c.t_first_token - due[uid]) * 1e3)
        wait.append((c.t_admitted - due[uid]) * 1e3)
    per_token = time_per_token(out["ticks"], requests, out["t_end"])
    tpot = per_token["per_request"]
    in_window = [t for t in out["ticks"] if w0 <= t[0] <= w1]
    failed = len(out["counted"]) - len(ok)
    late = [out["late"][u] * 1e3 for u in out["counted"] if u in out["late"]]
    q = lambda v, at: harness.quantile(v or [0.0], at)
    harness.note(f"time to first token from the due instant, ms: p50 "
                 f"{q(ttft, 50):.0f}, p95 {q(ttft, 95):.0f}; due to "
                 f"admitted p95 {q(wait, 95):.0f}; submitted late p95 "
                 f"{q(late, 95):.0f}")
    slow = sorted(in_window, key=lambda t: -t[1])[:3]
    harness.note("slowest ticks (ms, at s into the window): " + ", ".join(
        f"{t[1] * 1e3:.0f} at {t[0] - t[1] - w0:.1f}" for t in slow))
    note_time_per_token(per_token, in_window)

    del eng
    t_ref = time.perf_counter()
    check = harness.Check()
    sample = pick_sample(ok, args.seed, trf["check_requests"])
    got = served_gaps(sut, key, sample) if sample else dict(
        {name: float("nan") for name in limits}, served_tokens=0)
    for name, limit in limits.items():
        check.add(name, got[name], limit)
    check.add("token_count_mismatch", float(miscount), 0.0)
    ref_s = time.perf_counter() - t_ref
    harness.note(f"reference took {ref_s:.1f} s")

    return {
        "check": check,
        "attempted": len(out["counted"]),
        "failed": failed + compiles.n,
        "device": device,
        "trace": trace,
        "end_to_end": {
            "tpot_ms_p50": per_token["p50"],
            "setup_s": w0 - t_process},
        "facts": {"ticks": in_window, "tpot_ms": tpot,
                  "token_gaps": int(per_token["gaps"].size),
                  "slots": trf["engine"]["slots"],
                  "pool_tokens": out["pool_tokens"], "reference_s": ref_s,
                  "checked_tokens": got["served_tokens"],
                  "completed_ok": len(ok), "chips": len(devices)},
    }
