"""What every runner shares: finding a cell's files by name, the seed, host
spans, the clock's percentiles, the compile counter, the device record.

Nothing here knows a model, a traffic mix or a metric by name: a cell is
``BENCHMARK.json``'s ``{name, config, traffic, chips}``, and its two files
are ``configs/<config>.json`` and ``traffic/<traffic>.json`` beside this
module.  A per-layer metric ``m`` is ``layer_metrics/<m>.py`` with
``compute(run)``; a batch kind ``k`` is ``batches/<k>.py`` with ``make``;
a reference is the ``file.py:prefix`` a config names.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ------------------------------------------------------------ the files

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_files(cell: Dict[str, Any]) -> Tuple[Dict, Dict]:
    """(config, traffic) of a ``workloads`` entry, found by name."""
    cfg = load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    trf = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cfg, trf


def find_cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (known: "
                     f"{[c['name'] for c in spec['workloads']]})")


def resolve(ref: str) -> Any:
    """``"package.module:attr"`` (the program's or the benchmark's)."""
    mod, _, attr = ref.partition(":")
    return getattr(importlib.import_module(mod), attr)


def load_file_module(rel_path: str):
    """A module of the benchmark by its path under the repo root
    (``benchmarks/reference/transformer.py``)."""
    path = os.path.join(ROOT, rel_path)
    name = "bench_" + rel_path.replace("/", "_").removesuffix(".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(ref: str):
    """``"benchmarks/reference/x.py:prefix"`` -> (module, prefix)."""
    path, _, prefix = ref.partition(":")
    return load_file_module(path), prefix


def layer_metric_reader(name: str) -> Optional[Callable]:
    """The reader of per-layer metric ``name``: ``layer_metrics/<name>.py``
    or, for a quantity split by the end-to-end metric it moves (``x.train``,
    ``x.serve``), the one reader ``layer_metrics/x.py`` that they share."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join("benchmarks", "layer_metrics", stem + ".py")
        if os.path.exists(os.path.join(ROOT, path)):
            return load_file_module(path).compute
    return None


def flops_per_item(cfg: Dict, trf: Dict) -> float:
    """Analytic FLOPs to train on one item, by the function the
    configuration names (``file.py:function``) over its own ``args`` and
    those it takes from the traffic file (``from_traffic``: argument ->
    key)."""
    spec = cfg["flops"]
    path, _, fn = spec["function"].partition(":")
    kwargs = dict(spec["args"], **{arg: trf[key] for arg, key
                                   in spec["from_traffic"].items()})
    return getattr(load_file_module(path), fn)(**kwargs)


def batch_maker(kind: str) -> Callable:
    return load_file_module(
        os.path.join("benchmarks", "batches", kind + ".py")).make


def device_peaks(device_kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"device_kind {device_kind!r} is not in "
                         f"benchmarks/peaks.json (known: {sorted(table)})")
    return table[device_kind]


# ------------------------------------------------------------- the seed

def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


# ------------------------------------------------------------ the clock

def quantile(values: List[float], q: float) -> float:
    """Nearest-rank percentile ``q`` in (0, 100] of a non-empty list."""
    s = sorted(values)
    rank = max(1, -(-len(s) * q // 100))
    return float(s[int(rank) - 1])


class Spans:
    """Host spans the harness puts round its own calls.  Each is kept as
    (start, duration) on perf_counter and, while the profiler runs, also
    written into its trace as a TraceAnnotation of the same name, so that
    idle gaps on the device can be laid to what the host was doing."""

    def __init__(self):
        self.by_name: Dict[str, List[Tuple[float, float]]] = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax
            ctx = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ctx:
            yield
        self.by_name.setdefault(name, []).append(
            (t0, time.perf_counter() - t0))


class CompileCounter:
    """Counts programs lowered (compiled or fetched from the cache) while armed: inside the measured window
    there must be none, and each one found there is a failed operation."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event == self.EVENT:
            self.n += 1


# ----------------------------------------------------------- the device

def require_chips(chips: int, rehearsal: bool):
    """The devices a cell runs on.  Outside a rehearsal (a switch only the
    tests hold) anything but ``chips`` TPU chips is an error: a CPU timing
    is never printed under a device metric's name."""
    import jax
    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found platform "
                         f"{devs[0].platform!r}; the benchmark measures "
                         "on a TPU only")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def note(msg: str) -> None:
    """A line for whoever reads the run's standard error."""
    import sys
    print(f"[bench {time.perf_counter():.1f}] {msg}", file=sys.stderr,
          flush=True)


def device_record(devices) -> Dict[str, Any]:
    """The device as JAX reports it, on the fullest chip.  This runtime's
    ``memory_stats()`` keeps two regions apart: live arrays (``peak_bytes_
    in_use``: weights, state, a cache, a step's arguments and results) and
    the region it holds reserved for the temporaries of the programs it
    has loaded (``bytes_reserved``; it stands from a program's first run
    on, and in every run recorded it equals ``peak_bytes_reserved`` when
    the window closes).  Both are given as facts of their own
    (``memory_live_peak_bytes``, ``memory_reserved_bytes``);
    ``memory_peak_bytes`` is the chip's memory committed at the peak, live
    arrays plus the standing reservation.  PERF.md section 4 sets it
    beside the compiled programs' ``memory_analysis()``."""
    peak = live = reserved = 0
    for d in devices:
        stats = d.memory_stats() or {}
        note(f"memory_stats {d}: {stats}")
        d_live = int(stats.get("peak_bytes_in_use", 0))
        d_reserved = int(stats.get("bytes_reserved", 0))
        if d_live + d_reserved >= peak:
            peak, live, reserved = d_live + d_reserved, d_live, d_reserved
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
            "memory_live_peak_bytes": live,
            "memory_reserved_bytes": reserved}


def enable_cache() -> str:
    """The program's one rule for the compile cache (``<checkout>/
    .jax_cache`` unless JAX_COMPILATION_CACHE_DIR is set), plus: cache
    every program, however quick it was to compile, so that a second run
    in the checkout compiles nothing."""
    import jax
    from apex_example_tpu.utils.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class Check:
    """The numbers compared with the reference, each beside its limit."""

    def __init__(self):
        self.rows: List[Dict[str, Any]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        ok = bool(value == value and value <= limit)   # NaN fails
        self.rows.append({"name": name, "value": float(value),
                          "limit": float(limit), "ok": ok})

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self, out) -> None:
        for r in self.rows:
            print(f"check {r['name']}: {r['value']:.6g} (limit "
                  f"{r['limit']:.6g}) {'ok' if r['ok'] else 'FAIL'}",
                  file=out)


def trace_dir() -> str:
    """Where a traced run keeps the profiler's files: one fixed directory
    in the checkout, emptied first so that only this run's trace is read."""
    import shutil
    path = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
