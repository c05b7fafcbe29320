#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, taken on the chip at
a cell's own size (never by the benchmark's own runs):

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 \
        [--controls 3] [--seconds 15]

For each seed it prints one JSON line with the numbers ``correct`` compares
for a sound run of the program (``sound``) and, for the first ``--controls``
seeds, the same numbers for the control (``control``): the plain reference
put in the program's place and computed in the configuration's
``control_precision``, the nearest precision below the one it states.  A
limit goes above the sound runs' largest and below the control's smallest.
Training cells need no measured window; a serving cell runs a short one at
the cell's own load.  All seeds share one process and one compilation.
"""

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def worst_leaves(sut, key, prog, ref, n=3):
    """Names of the leaves whose first-gradient norm is farthest off."""
    import jax
    import numpy as np
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 jax.eval_shape(sut.init, key).params)[0]]
    r, g = ref["grad_norms"], prog["grad_norms"]
    gap = np.abs(g - r) / np.maximum(r, np.median(r))
    return [[paths[i], float(gap[i]), float(g[i]), float(r[i])]
            for i in np.argsort(-gap)[:n]] + [["median", float(np.median(r))]]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    from benchmarks import harness
    spec = harness.benchmark_spec()
    cell = harness.find_cell(spec, args.workload)
    cfg, trf = harness.cell_files(cell)
    devices = harness.require_chips(cell["chips"], rehearsal=False)
    harness.enable_cache()
    control = cfg["control"]
    seeds = [int(s) for s in args.seeds.split(",")]
    if cfg["runner"] == "train":
        from benchmarks.runners import train
        sut = train.Cell(cfg, trf, devices)
        for n, seed in enumerate(seeds):
            t0 = time.perf_counter()
            key = harness.seed_key(seed)
            state, prog = sut.first_steps(key)
            del state
            ref = sut.reference(key)
            row = {"workload": cell["name"], "seed": seed,
                   "sound": train.gaps(prog, ref),
                   "worst_leaves": worst_leaves(sut, key, prog, ref)}
            if n < args.controls:
                row["control"] = train.gaps(
                    sut.reference(key, control["precision"]), ref)
                row["control_is"] = control
            row["seconds"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)
    else:
        from benchmarks import loadgen
        from benchmarks.runners import serve
        sut = serve.Cell(cfg, trf, devices)
        def read(key, seed, as_control):
            eng = sut.engine(key, control=as_control)
            plan = loadgen.schedule(trf["mix"], seed, trf["ramp_s"],
                                    args.seconds, sut.vocab)
            out = serve.drive(sut, eng, plan, args.seconds, harness.Spans(),
                              SimpleNamespace(armed=False, n=0))
            ok = [c for c in eng.completions
                  if c.request.uid in out["counted"] and c.status == "ok"]
            del eng
            got = serve.served_gaps(
                sut, key, serve.pick_sample(ok, seed, trf["check_requests"]))
            return dict(got, finished=len(ok))

        for n, seed in enumerate(seeds):
            t0 = time.perf_counter()
            key = harness.seed_key(seed)
            row = {"workload": cell["name"], "seed": seed,
                   "sound": read(key, seed, False)}
            if n < args.controls:
                row["control"] = read(key, seed, True)
                row["control_is"] = control
            row["seconds"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
