#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``; its configuration,
traffic mix and limits are files found by name under ``benchmarks/``; the
configuration's ``runner`` names the module under ``runners/`` that drives
it.  The last line of standard output is one JSON object: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, the
device's busy time and the breakdown.  On anything but the TPU chips the
cell asks for it exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def reports(metric, cell_name, reported):
    """Does ``metric`` of BENCHMARK.json belong to this cell's line?"""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def run_cell(args, rehearsal=False, overrides=None, break_step=None,
             out=sys.stdout, t_process=T_PROCESS):
    """Drive one cell and return the result line (a dict).  ``rehearsal``,
    ``overrides`` (tiny sizes laid over the cell's files) and
    ``break_step`` are for the tests; the command line cannot set them."""
    from benchmarks import harness
    spec = harness.benchmark_spec()
    cell = harness.find_cell(spec, args.workload)
    cfg, trf = harness.cell_files(cell)
    limits = harness.load_json(os.path.join(
        harness.HERE, "limits", cell["name"] + ".json"))
    for target, patch in (overrides or {}).items():
        _merge({"config": cfg, "traffic": trf}[target], patch)
    devices = harness.require_chips(cell["chips"], rehearsal)
    harness.note(f"found {len(devices)} x {devices[0].device_kind}")
    if not rehearsal:
        harness.enable_cache()
    spans, compiles = harness.Spans(), harness.CompileCounter()
    runner = harness.load_file_module(
        os.path.join("benchmarks", "runners", cfg["runner"] + ".py"))
    res = runner.run(cell, cfg, trf, limits, args, devices, t_process, spans,
                     compiles, break_step=break_step)
    res["check"].print(out)

    e2e = {k: v for k, v in res["end_to_end"].items() if v is not None}
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    device = res["device"]
    if args.trace:
        run = SimpleNamespace(
            cell=cell, config=cfg, traffic=trf, end_to_end=e2e,
            facts=res["facts"], trace=res["trace"], spans=spans.by_name,
            peaks=harness.device_peaks(device["kind"]) if not rehearsal
            else harness.device_peaks("TPU v5 lite"))
        values = {}
        for m in spec["per_layer"]:
            if not reports(m, cell["name"], e2e):
                continue
            reader = harness.layer_metric_reader(m["name"])
            value = reader(run) if reader else None
            if value is not None:
                values[m["name"]] = value
        device = dict(device, busy_s=res["trace"]["busy_s"],
                      window_s=res["trace"]["window_s"])
    else:
        values = {m["name"]: e2e[m["name"]] for m in spec["end_to_end"]
                  if m["name"] in e2e and reports(m, cell["name"], e2e)}
    line = {"correct": res["check"].ok, "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": device}
    if args.trace:
        line["breakdown"] = {"device_ops": res["trace"]["device_ops"],
                             "idle_gaps": res["trace"]["idle_gaps"]}
    return line


def _merge(into, patch):
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            _merge(into[k], v)
        else:
            into[k] = v


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(args)
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
