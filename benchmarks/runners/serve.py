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
over the window's requests of the time per output token; the time to first
token, the wait for a slot and how late the generator ran go to the run's
notes (at this load they swing with every burst: PERF.md section 2).

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
    ok, ttft, tpot, wait, miscount = [], [], [], [], 0
    for uid in sorted(out["counted"]):
        c = comps.get(uid)
        if c is None or c.status != "ok":
            # failed or unfinished: it misses both latencies; the drain's
            # end is the least either can have been
            ttft.append((out["t_end"] - due[uid]) * 1e3)
            tpot.append(ttft[-1])
            continue
        ok.append(c)
        miscount += int(len(c.tokens) != asked[uid])
        ttft.append((c.t_first_token - due[uid]) * 1e3)
        wait.append((c.t_admitted - due[uid]) * 1e3)
        if len(c.tokens) > 1:
            tpot.append((c.t_finish - c.t_first_token)
                        / (len(c.tokens) - 1) * 1e3)
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
            "tpot_ms_p50": harness.quantile(tpot, 50) if tpot else None,
            "setup_s": w0 - t_process},
        "facts": {"ticks": in_window, "tpot_ms": tpot,
                  "slots": trf["engine"]["slots"],
                  "pool_tokens": out["pool_tokens"], "reference_s": ref_s,
                  "checked_tokens": got["served_tokens"],
                  "completed_ok": len(ok), "chips": len(devices)},
    }
