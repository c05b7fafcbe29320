"""Runner for training cells.  It knows no model: the configuration file
names the builder, the optimizer, the loss and the reference; the traffic
file gives the shapes; the batch kind is a file under ``batches/``.

One object is built in set-up: the compiled step with its state.  It is
driven from the seed through its first three steps by the window's own call
and feed, and that same object goes on into the measured window.  The feed
(a fresh batch made on the device from the seed and the step index) is part
of that one compiled call, so a step in flight holds no batch of its own and
the host may run ``steps_ahead`` (the traffic file's) steps ahead of the
device: a host that stalls for less than that lead starves nothing.  After the
window the plain reference follows the same three steps from the same seed,
and ``correct`` compares each step's loss, the first gradient as the
optimizer got it (worked out from its state after one step) and the
parameters' change after three, by the worst leaf.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import harness

FIRST_STEPS = 3


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> Dict[str, float]:
    """Gap between the program's norms and the reference's, leaf by leaf:
    the gap between the two norms (not the norm of a difference) against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  ``worst`` is the worst
    leaf's, ``p90`` the 90th percentile over the leaves, ``all`` the gap of
    the norm over all leaves together."""
    per_leaf = np.abs(prog - ref) / np.maximum(ref, float(np.median(ref)))
    whole = lambda v: float(np.sqrt(np.sum(np.square(v))))
    return {"worst": float(np.max(per_leaf)),
            "p90": float(np.quantile(per_leaf, 0.9)),
            "all": abs(whole(prog) - whole(ref)) / whole(ref)}


class Cell:
    """The system under test for one training cell, built once."""

    def __init__(self, cfg: Dict, trf: Dict, devices):
        from apex_example_tpu import amp
        from apex_example_tpu.engine import (TrainState, make_sharded_train_step,
                                             make_train_step)
        self.cfg, self.trf, self.devices = cfg, trf, devices
        recipe, mspec = cfg["recipe"], cfg["model"]
        self.policy, scaler = amp.initialize(recipe["opt_level"])
        md = amp.module_dtypes(self.policy)
        kwargs = dict(mspec["kwargs"], **trf.get("model_kwargs", {}))
        kwargs.update({k: getattr(md, v)
                       for k, v in mspec["dtype_kwargs"].items()})
        self.model = harness.resolve(mspec["builder"])(**kwargs)
        self.hp = recipe["optimizer_kwargs"]
        opt_kw = dict(self.hp)
        if "betas" in opt_kw:
            opt_kw["betas"] = tuple(opt_kw["betas"])
        self.opt = harness.resolve(recipe["optimizer"])(**opt_kw)
        step_kw = {"compute_accuracy": False}
        if recipe.get("loss"):
            step_kw["loss_fn"] = harness.resolve(recipe["loss"])
        self.ref, self.prefix = harness.load_reference(cfg["reference"])
        self.ref_opt = harness.load_file_module(
            "benchmarks/reference/optimizers.py")
        self.rcfg = cfg["reference_cfg"]
        weights = getattr(self.ref, self.prefix + "_weights")
        batch = harness.batch_maker(trf["batch"])(trf, cfg)
        moment = recipe["moment_field"]
        first_grad = getattr(self.ref_opt, cfg["reference_optimizer"]
                             + "_first_grad")

        def init(key):
            v = weights(key, self.rcfg)
            return TrainState(step=jnp.zeros((), jnp.int32),
                              params=v["params"],
                              batch_stats=v.get("batch_stats", {}),
                              opt_state=self.opt.init(v["params"]),
                              scaler=scaler)

        def grad_norms(state, key):
            p0 = weights(key, self.rcfg)["params"]
            return _leaf_norms(first_grad(
                getattr(state.opt_state, moment), p0, self.hp))

        def update_norms(state, key):
            p0 = weights(key, self.rcfg)["params"]
            return _leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, state.params, p0))

        if len(devices) > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from apex_example_tpu.parallel.mesh import (DATA_AXIS,
                                                        make_data_mesh)
            mesh = make_data_mesh(devices=devices)
            rep = NamedSharding(mesh, P())
            rows = NamedSharding(mesh, P(DATA_AXIS))
            inner = make_sharded_train_step(mesh, self.model, self.opt,
                                            self.policy, **step_kw)
            feed = lambda key, i: jax.lax.with_sharding_constraint(
                batch(key, i), rows)
            self.init = jax.jit(init, out_shardings=rep)
            self.batch = jax.jit(batch, out_shardings=rows)
        else:
            inner = make_train_step(self.model, self.opt, self.policy,
                                    **step_kw)
            feed = batch
            self.init = jax.jit(init)
            self.batch = jax.jit(batch)
        # the window's call: the step with its feed, one compiled program
        self.step = jax.jit(
            lambda state, key, i: inner(state, feed(key, i)),
            donate_argnums=(0,))
        self.grad_norms = jax.jit(grad_norms)
        self.update_norms = jax.jit(update_norms)
        self.items_per_step = trf["batch_size"] * trf["items_per_row"]

    # ------------------------------------------------ the program's side

    def first_steps(self, key, break_step: Optional[str] = None):
        """Build the state from the seed and drive it through its first
        steps with the window's own call and feed.  Returns the state and
        the program's readings.  ``break_step`` is the tests' way of
        breaking the timed path underneath: ``"unchanged"`` makes the step
        return its state as it came."""
        step = self.step
        if break_step == "unchanged":
            real = self.step

            def step(state, key, i):       # noqa: F811 - the broken path
                keep = jax.tree_util.tree_map(jnp.copy, state)
                _, metrics = real(state, key, i)
                return keep, metrics
        self.window_step = step
        state = self.init(key)
        losses = []
        g = None
        for i in range(FIRST_STEPS):
            state, metrics = step(state, key, i)
            losses.append(metrics["loss"])
            if i == 0:
                g = self.grad_norms(state, key)
        u = self.update_norms(state, key)
        return state, {"loss": np.asarray(jnp.stack(losses), np.float64),
                       "grad_norms": np.asarray(g, np.float64),
                       "update_norms": np.asarray(u, np.float64)}

    # ---------------------------------------------- the reference's side

    def reference(self, key, prec: str = "highest") -> Dict[str, Any]:
        """The plain reference (or, at a lower ``prec``, the control) over
        the same first steps from the same seed, on one device."""
        ref, pre, ro = self.ref, self.prefix, self.ref_opt
        oname = self.cfg["reference_optimizer"]
        loss_sum = getattr(ref, pre + "_loss_sum")
        denom_of = getattr(ref, pre + "_loss_denom")
        rows_of = getattr(ref, pre + "_rows")
        blocks = getattr(ref, pre + "_row_blocks")
        n_rows = self.trf["batch_size"]
        rb = self.trf.get("reference_rows", n_rows) if blocks else n_rows
        opt_step = jax.jit(lambda p, g, s, t: getattr(ro, oname + "_step")(
            p, g, s, t, self.hp))
        given = jax.jit(lambda g, p: _leaf_norms(
            getattr(ro, oname + "_given_grad")(g, p, self.hp)))

        @jax.jit
        def block_grad(params, rows, denom):
            return jax.value_and_grad(
                lambda p: loss_sum(p, rows, self.rcfg, prec) / denom)(params)

        add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
        w0 = jax.jit(lambda k: getattr(ref, pre + "_weights")(
            k, self.rcfg)["params"])
        params = w0(key)
        ostate = getattr(ro, oname + "_init")(params)
        out = {"loss": []}
        for t in range(1, FIRST_STEPS + 1):
            batch = jax.device_put(self.batch(key, t - 1), self.devices[0])
            denom = denom_of(batch)
            loss, grads = 0.0, None
            for lo in range(0, n_rows, rb):
                l, g = block_grad(params, rows_of(batch, lo, lo + rb), denom)
                loss = loss + l
                grads = g if grads is None else add(grads, g)
            out["loss"].append(float(loss))
            if t == 1:
                out["grad_norms"] = np.asarray(given(grads, params),
                                               np.float64)
            params, ostate = opt_step(params, grads, ostate, float(t))
        out["loss"] = np.asarray(out["loss"], np.float64)
        out["update_norms"] = np.asarray(jax.jit(
            lambda p, k: _leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, p, w0(k))))(params, key), np.float64)
        return out


def gaps(side: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """Every number ``correct`` may compare, one side against the
    reference; a cell's limits file names those it does compare."""
    out = {"loss_gap": float(np.max(np.abs(side["loss"] - ref["loss"])
                                    / np.abs(ref["loss"])))}
    for name, key in (("grad_norm_gap", "grad_norms"),
                      ("update_norm_gap", "update_norms")):
        for kind, value in leaf_gaps(side[key], ref[key]).items():
            out[name if kind == "worst" else f"{name}_{kind}"] = value
    return out


def run(cell, cfg, trf, limits, args, devices, t_process, spans,
        compiles, break_step=None) -> Dict[str, Any]:
    key = harness.seed_key(args.seed)
    sut = Cell(cfg, trf, devices)
    harness.note("built the step")
    state, prog = sut.first_steps(key, break_step)
    step = sut.window_step
    jax.block_until_ready(state)
    harness.note("first steps done; the window opens")

    trace_dir = None
    trace_at = float("inf")
    if args.trace:
        trace_at = max(0.0, args.seconds - trf["trace_seconds"])
        trace_dir = harness.trace_dir()
    ahead = trf["steps_ahead"]
    pending: collections.deque = collections.deque()
    losses: List[Any] = []
    done_at: List[float] = []
    t_trace = None
    steady = None
    step_s = 0.0        # the device's time a step, from the steps fetched
    ran_dry = 0         # times the host found the device with nothing queued

    def start_trace():
        nonlocal t_trace, steady
        steady = list(done_at)           # the profiler's start stalls the loop
        jax.profiler.start_trace(trace_dir)
        spans.annotate = True
        t_trace = time.perf_counter()

    compiles.armed = True
    i = FIRST_STEPS
    t_start = time.perf_counter()
    while True:
        now = time.perf_counter() - t_start
        in_flight = sum(not x.is_ready() for x in pending)
        if now + in_flight * step_s >= args.seconds:
            break          # no step that would start after the window's end
        if t_trace is None and now >= trace_at:
            start_trace()
        ran_dry += bool(pending) and in_flight == 0
        with spans.span("bench.train_step"):
            state, metrics = step(state, key, i)
        pending.append(metrics["loss"])
        i += 1
        if len(pending) > ahead:
            with spans.span("bench.fetch"):
                losses.append(jax.block_until_ready(pending.popleft()))
            done_at.append(time.perf_counter())
            if len(done_at) > 1:
                step_s = (done_at[-1] - done_at[0]) / (len(done_at) - 1)
    if args.trace and t_trace is None:
        # the steps in flight carry the device to the window's end
        time.sleep(max(0.0, trace_at - (time.perf_counter() - t_start)))
        start_trace()
    with spans.span("bench.fetch"):
        jax.block_until_ready(state)
    t_end = time.perf_counter()
    compiles.armed = False
    losses.extend(pending)
    trace = None
    if t_trace is not None:
        jax.profiler.stop_trace()
        spans.annotate = False
        from benchmarks import trace as trace_lib
        trace = trace_lib.reduce_dir(trace_dir, t_end - t_trace)
    n_steps = i - FIRST_STEPS
    harness.note(f"window closed after {n_steps} steps, {in_flight} in flight "
                 f"when dispatching stopped; the host found the device dry "
                 f"{ran_dry} times")
    device = harness.device_record(devices)
    finite = bool(np.all(np.isfinite(np.asarray(jnp.stack(losses)))))
    del state

    t_ref = time.perf_counter()
    ref = sut.reference(key)
    check = harness.Check()
    for name, value in gaps(prog, ref).items():
        if name in limits:
            check.add(name, value, limits[name])
    check.add("nonfinite_losses", 0.0 if finite else 1.0, 0.0)
    ref_s = time.perf_counter() - t_ref
    harness.note(f"reference took {ref_s:.1f} s")

    window = t_end - t_start
    rate = sut.items_per_step * n_steps / window / len(devices)
    return {
        "check": check,
        "attempted": n_steps,
        "failed": compiles.n,
        "device": device,
        "trace": trace,
        "end_to_end": {"train_items_per_s": rate,
                       "setup_s": t_start - t_process},
        "facts": {"n_steps": n_steps, "window_s": window,
                  "items_per_step": sut.items_per_step,
                  "step_done_at": done_at if steady is None else steady,
                  "reference_s": ref_s,
                  "flops_per_item": harness.flops_per_item(cfg, trf),
                  "chips": len(devices)},
    }
