"""Runner for serving cells whose reference does not fit one call.

``runners/serve.py`` asks the plain reference for the logits of all sampled
requests at once (``[requests, max_len, vocabulary]`` float32); at a large
vocabulary and a long ``max_len`` that is more than a chip holds.  This
runner is that one with one thing changed: the reference pass goes request
by request and in blocks of positions (the configuration's
``reference_block``), each block reduced on the device to the one number a
position is judged by — how far the served token's logit lies below the
reference's best — before the next is computed, with the engine released
first.  The reference module therefore offers ``<prefix>_hidden`` (the
normed last hidden state of whole sequences) and ``<prefix>_head`` (logits
of hidden states) beside ``<prefix>_weights``.  Load, window, drain,
sampling of requests and the judged metrics are ``runners/serve.py``'s own
(``drive``, ``pick_sample``, ``run``, by import; ``served_gaps`` is that
module's with three more statistics of the same gaps).

It also looks after the host's path round a long tick (``settle_host``
below: the idle loop waits without sleeping, the engine is handed over
warm, a slow system-call path is probed, nudged and noted).

Two more things ride along, neither naming a model: a model that counts
what its layers did in a tick (the engine's ``counter_log``: an expert
layer's load) gets the window's counts passed on in ``facts``; and
``python3 benchmarks/runners/serve_blocked.py --workload <cell> --seeds
1,2,3`` is this runner's ``benchmarks/control.py``: each seed's run goes
through ``run`` with the control in the program's place — the reference at
the configuration's ``control.precision`` serves, at every served
position, its own first place given the same prefix — and is judged by the
same comparison with the same limits; it prints the checks and ``correct``.
"""

from __future__ import annotations

import functools
import gc
import os
import sys
import threading
import time
from types import SimpleNamespace
from typing import Any, Dict, Optional, Tuple
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import harness
from benchmarks.runners import serve as base


# The machines the benchmark runs on put the command in a sandbox whose
# system-call path has two modes (PERF.md section 6, PR 27): a trivial call
# takes ~5 us or ~35 us, a hand-off between two threads ~50 us or ~230-360
# us, for the whole sandbox and for minutes at a time.  A tick of the engine
# makes about thirteen such hand-offs on its critical path (eight host-to-
# device puts, the key split, the launch, the wait, two fetches), so the
# slow mode adds 4.7 ms to every tick whatever the program does.  Seen to
# switch it to slow: a loop of ~1 ms sleeps lasting a second or more (the
# idle loop of ``runners/serve.drive`` before the first arrival) and bursts
# of many busy threads (a compilation); to fast: threads being created (a
# profiler session does that too).  So, for a long-tick cell: the idle loop
# waits without sleeping, and the engine is handed over warm with the path
# probed and, where it reads slow, nudged.  What the probe read goes to the
# run's notes either way.
SLOW_CALL_US = 15.0


def system_call_us(n: int = 200) -> float:
    """Mean time of a trivial system call, in microseconds."""
    t0 = time.perf_counter()
    for _ in range(n):
        os.getppid()
    return (time.perf_counter() - t0) / n * 1e6


def settle_host(tries: int = 25) -> Tuple[float, int]:
    """Probe the system-call path; while it reads slow, create and join a
    few threads and probe again.  Returns the last reading and the number
    of nudges.  On a host without the two modes this is one probe."""
    for nudges in range(tries + 1):
        us = system_call_us()
        if us < SLOW_CALL_US or nudges == tries:
            return us, nudges
        ts = [threading.Thread(target=time.sleep, args=(0.002,))
              for _ in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()


def _wait(seconds: float) -> None:
    """``time.sleep`` for the idle loop of ``drive``, without the call."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


class Cell(base.Cell):
    """``runners/serve.Cell`` with the reference pass in blocks."""

    def __init__(self, cfg: Dict, trf: Dict, devices):
        super().__init__(cfg, trf, devices)
        self.counter_log = None
        hidden = getattr(self.ref, self.prefix + "_hidden")
        head = getattr(self.ref, self.prefix + "_head")
        self._hidden = jax.jit(
            lambda p, x, prec: hidden(p, x, self.rcfg, prec),
            static_argnums=2)

        def block(p, h, nxt, served_by):
            """Of one block of positions: the gap of the token judged below
            the float32 reference's best.  Judged is the token that
            follows in the sequence, or (``served_by``) the first place of
            the reference at that lower precision."""
            ref = head(p, h[0], self.rcfg, "highest")
            if served_by:
                nxt = jnp.argmax(head(p, h[1], self.rcfg, served_by), -1)
            got = jnp.take_along_axis(ref, nxt[..., None], -1)[..., 0]
            return ref.max(-1) - got

        self._block = jax.jit(block, static_argnums=3)

    def engine(self, key, break_step: Optional[str] = None,
               control: bool = False):
        if control:
            raise ValueError("this runner's control is the reference at a "
                             "lower precision (gaps(served_by=...)), not "
                             "an engine")
        eng = super().engine(key)
        self.counter_log = eng.counter_log      # tiny arrays, not the engine
        if break_step == "alter_token":
            real, vocab = eng._step_fn, self.vocab

            def altered(*a):
                cache, nxt, *rest = real(*a)
                return (cache, (nxt + 1) % vocab, *rest)
            eng._step_fn = altered
        # hand the engine over warm (its program built or loaded here, not
        # in ``drive``), then see to the host's path
        from apex_example_tpu.serve import Request
        eng.submit(Request(prompt=[1] * (self.trf["engine"]["block_size"]
                                         + 1),
                           max_new_tokens=2, uid="built"))
        while not any(c.request.uid == "built" for c in eng.completions):
            eng.step()
        us, nudges = settle_host()
        harness.note(f"a system call takes {us:.1f} us after {nudges} "
                     f"nudges (slow mode: over {SLOW_CALL_US:.0f})")
        return eng

    def gaps(self, key, ids: np.ndarray,
             served_by: Optional[str] = None) -> np.ndarray:
        """As ``runners/serve.Cell.gaps``, ``[n, L - 1]`` floats, but one
        request at a time and the vocabulary head in blocks of positions."""
        gc.collect()                   # the engine's weights and arena go
        params = self.weights(key)
        n, L = ids.shape
        blk = int(self.cfg.get("reference_block", L))
        out = np.zeros((n, L), np.float32)
        nxt = np.concatenate([ids[:, 1:], np.zeros((n, 1), ids.dtype)], 1)
        for r in range(n):
            x = jnp.asarray(ids[r:r + 1])
            hs = [self._hidden(params, x, "highest")]
            if served_by:
                hs.append(self._hidden(params, x, served_by))
            for b0 in range(0, L, blk):
                out[r, b0:b0 + blk] = np.asarray(self._block(
                    params, [h[0, b0:b0 + blk] for h in hs],
                    jnp.asarray(nxt[r, b0:b0 + blk]), served_by))
        return out[:, :-1]


def served_gaps(sut: Cell, key, sample,
                served_by: Optional[str] = None) -> Dict[str, float]:
    """``runners/serve.served_gaps`` and, beside the widest gap and the
    share off first place, the gaps' mean and 90th and 99th percentiles:
    where a model routes tokens to experts, one near-tie decided the other
    way moves a token's every logit, in a sound run and in a control
    alike, so the widest gap cannot tell them apart and the body of the
    distribution has to (a limits file names the ones it judges).
    ``served_by``: judge the reference's own first places at that lower
    precision instead of the served tokens (the control)."""
    harness.note(f"a system call takes {system_call_us():.1f} us after the "
                 "drain")
    L = sut.trf["engine"]["max_len"]
    ids = np.zeros((len(sample), L), np.int32)
    for r, c in enumerate(sample):
        seq = list(c.request.prompt) + list(c.tokens)
        ids[r, :len(seq)] = seq
    got = sut.gaps(key, ids, served_by)
    gaps = np.concatenate([
        got[r, len(c.request.prompt) - 1:
            len(c.request.prompt) - 1 + len(c.tokens)]
        for r, c in enumerate(sample)])
    return {"served_logit_gap": float(gaps.max()),
            "served_off_first_share": float(np.mean(gaps > 0)),
            "served_logit_gap_mean": float(gaps.mean()),
            "served_logit_gap_p90": float(np.quantile(gaps, 0.9)),
            "served_logit_gap_p99": float(np.quantile(gaps, 0.99)),
            "served_tokens": int(gaps.size)}


def counted(log, ticks) -> Dict[str, Any]:
    """What the model's layers counted in the window's ticks, reduced to
    the numbers the per-layer readers want: per counter ``[layers, E]`` a
    tick, the mean over ticks and layers of the fullest expert's tokens
    over the mean expert's (``max_over_mean``), of the experts that got a
    token (``touched``) and of the tokens routed (``routed``)."""
    if not log or not ticks:
        return {}
    lo, hi = ticks[0][0] - ticks[0][1], ticks[-1][0]
    out: Dict[str, Any] = {}
    names = {k for _, tree in log for k in tree}
    for name in sorted(names):
        a = np.stack([np.asarray(tree[name]) for t, tree in log
                      if lo <= t <= hi and name in tree] or
                     [np.zeros((0, 1, 1))]).astype(np.float64)
        a = a[a.sum(axis=(1, 2)) > 0]
        if not len(a):
            continue
        mean = a.mean(-1)
        out[name] = {
            "ticks": int(len(a)),
            "max_over_mean": float(np.mean(a.max(-1)[mean > 0]
                                           / mean[mean > 0])),
            "touched": float(np.mean((a > 0).sum(-1))),
            "routed": float(np.mean(a.sum(-1)))}
    return out


def run(cell, cfg, trf, limits, args, devices, t_process, spans,
        compiles, break_step=None, served_by: Optional[str] = None
        ) -> Dict[str, Any]:
    """``runners/serve.run`` over this module's ``Cell`` and
    ``served_gaps``.  ``served_by`` (the control; never a benchmark run)
    puts the reference at that lower precision in the program's place
    before the same comparison with the same limits."""
    made = []

    def make(*a):
        made.append(Cell(*a))
        return made[-1]

    clock = SimpleNamespace(perf_counter=time.perf_counter, sleep=_wait)
    with mock.patch.object(base, "Cell", make), \
            mock.patch.object(base, "time", clock), \
            mock.patch.object(base, "served_gaps", functools.partial(
                served_gaps, served_by=served_by)):
        res = base.run(cell, cfg, trf, limits, args, devices, t_process,
                       spans, compiles, break_step=break_step)
    log = made[0].counter_log
    if log:
        fetched = [(t, jax.tree_util.tree_map(np.asarray, tree))
                   for t, tree in log]
        res["facts"]["counted"] = counted(fetched, res["facts"]["ticks"])
    return res


def main(argv=None):
    """The control on the chip: each seed's run judged by ``run``'s own
    comparison with the reference at ``control.precision`` in the
    program's place; prints the checks and ``correct`` (false is sound)."""
    import argparse
    import json
    import time
    from types import SimpleNamespace

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec()
    cell = harness.find_cell(spec, args.workload)
    cfg, trf = harness.cell_files(cell)
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", cell["name"] + ".json"))
    devices = harness.require_chips(cell["chips"], rehearsal=False)
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run(cell, cfg, trf, limits,
                  SimpleNamespace(seed=seed, seconds=args.seconds, trace=0),
                  devices, time.perf_counter(), harness.Spans(),
                  harness.CompileCounter(),
                  served_by=cfg["control"]["precision"])
        res["check"].print(sys.stdout)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control_is": cfg["control"],
                          "correct": res["check"].ok,
                          "failed": res["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
