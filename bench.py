#!/usr/bin/env python
"""Benchmark harness for the BASELINE.md acceptance matrix.

Default (no args) = the headline metric: ResNet-50 ImageNet-shaped
images/sec per chip under amp-O2 bf16 (BASELINE.md; target 4000 img/s/chip
on v5e).  Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

``--config`` selects the other acceptance-matrix rows (BASELINE.md:17-30):
  c1        ResNet-18 / CIFAR-shaped fp32 O0, single device   (img/s/chip)
  c2        ResNet-50 / ImageNet-shaped amp-O2 bf16 (default) (img/s/chip)
  c3        ResNet-50 DDP + SyncBatchNorm over all local devices
            (img/s/chip; on one chip this measures the sharded-step
            path; semantics are covered by the 8-CPU-device tests)
  c4        BERT-base MLM + FusedLAMB amp-O2                  (tokens/s/chip)
  c5        Transformer-XL + FusedLayerNorm + grad clip       (tokens/s/chip)
  hostpipe  c2 step fed by the native C++ double-buffered prefetcher
            instead of on-device synthesis (quantifies the host pipeline;
            stderr carries the on-device comparison)

Data is generated on-device once and reused across steps (c1-c5) so the
number isolates device throughput (the reference isolates the same way
with its CUDA-stream prefetcher, SURVEY.md §3.5).

Runs on a TPU only: ``main`` exits non-zero, printing no JSON line, on any
other backend or on a ``device_kind`` missing from the peaks table
(utils/flops.py DEVICE_PEAKS) — a CPU timing is not a device metric.

``vs_baseline`` is reported against the only normative target (4000
img/s/chip, ResNet-50 O2) for c2/c3; other rows have no published baseline
(BASELINE.md:3) and report ``vs_baseline: null``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from apex_example_tpu.obs import (FlightRecorder, JsonlSink, StallWatchdog,
                                  rank_print, span)
from apex_example_tpu.obs import costmodel as obs_costmodel
from apex_example_tpu.obs import metrics as obs_metrics
from apex_example_tpu.utils.compile_cache import enable_compile_cache
from apex_example_tpu.utils.flops import (DevicePeaks, device_peaks,
                                          model_train_flops_per_token,
                                          mfu_pct,
                                          resnet_train_flops_per_image)

BASELINE_IMG_PER_SEC_PER_CHIP = 4000.0

# Optional JSONL sink (--metrics-jsonl): every _emit line also lands as a
# schema-valid "bench" record (obs/schema.py) for the tools/ thin clients.
_SINK: JsonlSink | None = None
# Optional stall watchdog (--stall-timeout): each emitted measurement is
# its heartbeat — a bench config that hangs mid-measurement leaves a
# 'stall' record with thread stacks instead of silence.
_WATCHDOG: StallWatchdog | None = None
_EMITS = 0
# The peaks-table row of the device main() found; mfu_pct divides by it.
_PEAKS: DevicePeaks | None = None


def _emit(metric: str, value: float, unit: str, vs_baseline,
          flops_per_item: float = None):
    """One JSON line.  ``flops_per_item`` (analytic model FLOPs per image/
    token, utils/flops.py) adds ``mfu_pct`` — the fraction of the device's
    bf16 peak (``_PEAKS``, from the table) this throughput represents.  MFU
    counts MODEL FLOPs by convention: rematerialization recompute does not
    inflate it."""
    rec = {
        "metric": metric,
        "value": round(value, 1),
        "unit": unit,
        "vs_baseline": (round(vs_baseline, 4)
                        if vs_baseline is not None else None),
    }
    if flops_per_item is not None:
        rec["mfu_pct"] = round(
            mfu_pct(value, flops_per_item, _PEAKS.bf16_flops), 2)
    rank_print(json.dumps(rec))
    if _SINK is not None:
        sunk = {"record": "bench", "time": obs_metrics.now(), **rec}
        if sunk["vs_baseline"] is None:
            del sunk["vs_baseline"]     # schema: omitted, never null
        _SINK.write(sunk)
    if _WATCHDOG is not None:
        global _EMITS
        _EMITS += 1
        _WATCHDOG.notify_step(_EMITS)


def chain_rate(step, state, batch, steps: int, items_per_step: int,
               fetch) -> float:
    """Two-point measurement: each chain ends in a scalar value fetch, and
    differencing two chain lengths cancels the fetch and launch cost so the
    rate reflects device throughput.

    NOTE: consumes ``state`` (steps donate their input state); callers must
    not reuse the pytree they passed in.
    """
    steps = max(steps, 2)           # two chains must differ in length
    def run_chain(n, state):
        with span("bench_chain") as sp:
            for _ in range(n):
                state, metrics = step(state, batch)
            fetch(metrics)
        return sp.dur_s, state

    n1 = max(steps // 5, 1)
    if n1 >= steps:
        n1 = steps - 1
    t1, state = run_chain(n1, state)
    t2, state = run_chain(steps, state)
    return (steps - n1) * items_per_step / max(t2 - t1, 1e-9)


def _image_setup(policy, scaler, *, arch: str, batch_size: int,
                 image_size: int, num_classes: int,
                 syncbn: bool = False, remat: str = "none"):
    from apex_example_tpu.data import image_batch
    from apex_example_tpu.engine import create_train_state
    from apex_example_tpu.models import ARCHS
    from apex_example_tpu.optim import FusedSGD

    model = ARCHS[arch](
        num_classes=num_classes, dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype, bn_dtype=policy.bn_dtype,
        bn_axis_name="data" if syncbn else None, remat=remat)
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    batch = image_batch(jnp.asarray(0), batch_size=batch_size,
                        image_size=image_size, channels=3,
                        num_classes=num_classes, seed=0)
    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               batch[0][:1], policy, scaler)
    return model, opt, batch, state


def bench_image_single(args, *, arch: str, opt_level: str, image_size: int,
                       num_classes: int, metric: str, vs_target: bool):
    from apex_example_tpu import amp
    from apex_example_tpu.engine import make_train_step

    policy, scaler = amp.initialize(opt_level)
    model, opt, batch, state = _image_setup(
        policy, scaler, arch=arch, batch_size=args.batch_size,
        image_size=image_size, num_classes=num_classes,
        remat=getattr(args, "remat", "none"))
    batch = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, jax.devices()[0]), batch)
    step = obs_costmodel.instrument(f"bench_{args.config}_step",
                       jax.jit(make_train_step(model, opt, policy),
                               donate_argnums=(0,)))

    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, batch)
    float(metrics["loss"])

    rate = chain_rate(step, state, batch, args.steps, args.batch_size,
                      lambda m: float(m["loss"]))
    _emit(metric, rate, "images/sec/chip",
          rate / BASELINE_IMG_PER_SEC_PER_CHIP if vs_target else None,
          flops_per_item=resnet_train_flops_per_image(
              arch, image_size, num_classes))


def bench_c3(args):
    """ResNet-50 DDP + SyncBN over every local device (BASELINE.md row 3)."""
    from apex_example_tpu import amp
    from apex_example_tpu.engine import make_sharded_train_step
    from apex_example_tpu.parallel.mesh import make_data_mesh

    devices = jax.devices()
    n = len(devices)
    mesh = make_data_mesh(devices=devices)
    policy, scaler = amp.initialize("O2")
    global_bs = args.batch_size * n
    model, opt, batch, state = _image_setup(
        policy, scaler, arch="resnet50", batch_size=global_bs,
        image_size=args.image_size, num_classes=1000, syncbn=True)
    step = obs_costmodel.instrument("bench_c3_step",
                       make_sharded_train_step(mesh, model, opt, policy))

    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, batch)
    float(metrics["loss"])

    rate = chain_rate(step, state, batch, args.steps, global_bs,
                      lambda m: float(m["loss"]))
    _emit(f"resnet50_ddp_syncbn_{n}dev_ampO2_images_per_sec_per_chip",
          rate / n, "images/sec/chip",
          rate / n / BASELINE_IMG_PER_SEC_PER_CHIP,
          flops_per_item=resnet_train_flops_per_image(
              "resnet50", args.image_size, 1000))


def bench_c4(args):
    """BERT-base MLM + FusedLAMB under amp-O2 (BASELINE.md row 4)."""
    from apex_example_tpu import amp
    from apex_example_tpu.data import mlm_batch
    from apex_example_tpu.engine import create_train_state, make_train_step
    from apex_example_tpu.models.bert import bert_base
    from apex_example_tpu.optim import FusedLAMB
    from apex_example_tpu.workloads import mlm_loss

    policy, scaler = amp.initialize("O2")
    md = amp.module_dtypes(policy)
    # flag set => force the kernel; absent => "auto" (kernel at seq >= the
    # measured ~2k crossover, XLA path below — models/bert.py)
    model = bert_base(dtype=md.compute, param_dtype=md.param,
                      ln_dtype=md.ln_io, softmax_dtype=md.softmax,
                      fused_attention=args.fused_attention or "auto")
    opt = FusedLAMB(lr=1e-3, weight_decay=0.01)
    bs, seq = args.batch_size, args.seq_len
    V = model.vocab_size
    ids, labels, w = mlm_batch(jnp.asarray(0), batch_size=bs, seq_len=seq,
                               vocab_size=V, mask_token_id=V - 1, seed=0)
    batch = (ids, (labels, w))
    state = create_train_state(jax.random.PRNGKey(0), model, opt, ids[:1],
                               policy, scaler, train_kwargs={})
    step = obs_costmodel.instrument("bench_c4_step",
                       jax.jit(make_train_step(model, opt, policy,
                                               loss_fn=mlm_loss,
                                               compute_accuracy=False),
                               donate_argnums=(0,)))

    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, batch)
    float(metrics["loss"])

    rate = chain_rate(step, state, batch, args.steps, bs * seq,
                      lambda m: float(m["loss"]))
    _emit("bert_base_mlm_fusedlamb_ampO2_tokens_per_sec_per_chip",
          rate, "tokens/sec/chip", None,
          flops_per_item=model_train_flops_per_token(model, seq))


def bench_gpt(args):
    """GPT-base causal LM + FusedAdam under amp-O2 (beyond-reference model
    family, models/gpt.py; same measurement contract as c4 — tokens/sec/
    chip, the "auto" flash crossover engages at --seq-len >= 2048)."""
    from apex_example_tpu import amp
    from apex_example_tpu.data import lm_batch
    from apex_example_tpu.engine import create_train_state, make_train_step
    from apex_example_tpu.models.gpt import gpt_base
    from apex_example_tpu.optim import FusedAdam
    from apex_example_tpu.workloads import lm_loss

    policy, scaler = amp.initialize("O2")
    md = amp.module_dtypes(policy)
    kw = {}
    if args.seq_len > 1024:
        kw["max_position"] = args.seq_len
    model = gpt_base(dtype=md.compute, param_dtype=md.param,
                     ln_dtype=md.ln_io, softmax_dtype=md.softmax,
                     fused_attention=args.fused_attention or "auto", **kw)
    opt = FusedAdam(lr=1e-4, weight_decay=0.01)
    bs, seq = args.batch_size, args.seq_len
    toks = lm_batch(jnp.asarray(0), batch_size=bs, seq_len=seq,
                    vocab_size=model.vocab_size, seed=0)
    batch = (toks[:, :-1], toks[:, 1:])
    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               batch[0][:1], policy, scaler,
                               train_kwargs={})
    step = obs_costmodel.instrument("bench_gpt_step",
                       jax.jit(make_train_step(model, opt, policy,
                                               loss_fn=lm_loss,
                                               compute_accuracy=False),
                               donate_argnums=(0,)))

    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, batch)
    float(metrics["loss"])

    rate = chain_rate(step, state, batch, args.steps, bs * seq,
                      lambda m: float(m["loss"]))
    _emit("gpt_base_causal_lm_fusedadam_ampO2_tokens_per_sec_per_chip",
          rate, "tokens/sec/chip", None,
          flops_per_item=model_train_flops_per_token(model, seq))


def bench_c5(args):
    """Transformer-XL + FusedLayerNorm + grad clip (BASELINE.md row 5)."""
    from apex_example_tpu import amp
    from apex_example_tpu.data import lm_batch
    from apex_example_tpu.engine import create_train_state
    from apex_example_tpu.models.transformer_xl import transformer_xl_base
    from apex_example_tpu.optim import FusedAdam
    from apex_example_tpu.workloads import make_txl_train_step

    policy, scaler = amp.initialize("O2")
    md = amp.module_dtypes(policy)
    model = transformer_xl_base(dtype=md.compute, param_dtype=md.param,
                                ln_dtype=md.ln_io, softmax_dtype=md.softmax)
    opt = FusedAdam(lr=2.5e-4)
    bs, seq = args.batch_size, args.seq_len
    V = model.vocab_size
    toks = lm_batch(jnp.asarray(0), batch_size=bs, seq_len=seq + 1,
                    vocab_size=V, seed=0)
    batch = (toks[:, :-1], toks[:, 1:])
    state = create_train_state(jax.random.PRNGKey(0), model, opt,
                               batch[0][:1], policy, scaler,
                               train_kwargs={})
    mems = model.init_mems(bs)
    raw = obs_costmodel.instrument("bench_c5_step",
                      jax.jit(make_txl_train_step(model, opt, policy),
                              donate_argnums=(0, 1)))
    # adapt (state, mems) into the chain_rate (state, batch) shape
    def step(carry, batch):
        state, mems = carry
        state, mems, metrics = raw(state, mems, batch)
        return (state, mems), metrics

    carry = (state, mems)
    for _ in range(max(args.warmup, 1)):
        carry, metrics = step(carry, batch)
    float(metrics["loss"])

    rate = chain_rate(step, carry, batch, args.steps, bs * seq,
                      lambda m: float(m["loss"]))
    _emit("transformer_xl_fusedln_clip_tokens_per_sec_per_chip",
          rate, "tokens/sec/chip", None,
          flops_per_item=model_train_flops_per_token(model, seq))


def bench_hostpipe(args):
    """C2 step fed by the native host prefetcher vs on-device synthesis.

    Quantifies the C++ double-buffered pipeline (csrc/apex_tpu_host.cpp):
    the JSON line is the host-fed rate; stderr carries the on-device rate
    so the comparison lands in one run.
    """
    from apex_example_tpu import amp
    from apex_example_tpu.engine import make_train_step
    from apex_example_tpu.host_runtime import NativePrefetcher, available
    if not available():
        raise SystemExit("hostpipe: native runtime not buildable")

    policy, scaler = amp.initialize("O2")
    model, opt, batch, state = _image_setup(
        policy, scaler, arch="resnet50", batch_size=args.batch_size,
        image_size=args.image_size, num_classes=1000)
    step = obs_costmodel.instrument("bench_hostpipe_step",
                       jax.jit(make_train_step(model, opt, policy),
                               donate_argnums=(0,)))

    dev_batch = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, jax.devices()[0]), batch)
    for _ in range(max(args.warmup, 1)):
        state, metrics = step(state, dev_batch)
    float(metrics["loss"])

    on_device = chain_rate(step, state, dev_batch, args.steps,
                           args.batch_size, lambda m: float(m["loss"]))

    pf = NativePrefetcher(batch=args.batch_size,
                          image_size=args.image_size,
                          num_classes=1000, seed=0)
    it = iter(pf)

    def host_step(state, _):
        img, lab = next(it)
        b = (jnp.asarray(img), jnp.asarray(lab))
        return step(state, b)

    # chain_rate consumed the donated state above (including the scaler
    # arrays) — build a fresh state from a fresh scaler for this phase.
    policy, scaler = amp.initialize("O2")
    _, _, _, state = _image_setup(
        policy, scaler, arch="resnet50", batch_size=args.batch_size,
        image_size=args.image_size, num_classes=1000)
    for _ in range(2):
        state, metrics = host_step(state, None)
    float(metrics["loss"])
    host_rate = chain_rate(host_step, state, None, args.steps,
                           args.batch_size, lambda m: float(m["loss"]))
    rank_print(f"hostpipe: on-device {on_device:.1f} img/s, "
          f"host-fed {host_rate:.1f} img/s "
          f"({host_rate / on_device:.2%})", file=sys.stderr)
    _emit("resnet50_ampO2_hostpipe_images_per_sec_per_chip", host_rate,
          "images/sec/chip", host_rate / BASELINE_IMG_PER_SEC_PER_CHIP,
          flops_per_item=resnet_train_flops_per_image(
              "resnet50", args.image_size, 1000))


def _require_tpu() -> DevicePeaks:
    """One stderr header naming what JAX found, then the peaks-table row
    for it — or a non-zero exit: without a TPU that is in the table there
    is nothing this harness may print as a device number."""
    dev = jax.devices()[0]
    print(f"bench: jax {jax.__version__}  platform {dev.platform}  "
          f"device_kind {dev.device_kind!r}  count {jax.device_count()}",
          file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a TPU; JAX found platform "
                         f"{dev.platform!r} (no CPU fallback)")
    try:
        return device_peaks(dev.device_kind)
    except KeyError as e:
        raise SystemExit(e.args[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="c2",
                    choices=["c1", "c2", "c3", "c4", "c5", "gpt",
                             "hostpipe"])
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--fused-attention", action="store_true",
                    help="c4: flash-attention kernel (ops/attention.py)")
    ap.add_argument("--remat", default="none",
                    choices=["none", "conv", "block"],
                    help="c1/c2 rematerialization variant (PERF.md HBM "
                         "traffic experiments)")
    ap.add_argument("--metrics-jsonl", default="", metavar="PATH",
                    help="also write each measurement as a schema-valid "
                         "'bench' JSONL record (obs/schema.py; "
                         "tools/metrics_lint.py validates)")
    ap.add_argument("--cost-model", action="store_true",
                    help="with --metrics-jsonl: AOT-compile the "
                         "measurement step and emit schema-v6 "
                         "compile_event + cost_model records (XLA flops/"
                         "HBM bytes + roofline verdict — the analytic "
                         "twin of the measured MFU; obs/costmodel.py)")
    ap.add_argument("--flight-recorder", action="store_true",
                    help="with --metrics-jsonl: emit a 'crash_dump' "
                         "record on crash/SIGTERM (obs/flight.py)")
    ap.add_argument("--stall-timeout", type=float, default=0.0,
                    metavar="S",
                    help="with --metrics-jsonl: emit a 'stall' record "
                         "with thread stacks if no measurement lands for "
                         "S seconds (0 disables; covers compile time)")
    args = ap.parse_args()
    global _SINK, _WATCHDOG, _PEAKS
    enable_compile_cache()
    _PEAKS = _require_tpu()
    recorder = None
    if (args.flight_recorder or args.stall_timeout > 0
            or args.cost_model) and not args.metrics_jsonl:
        raise SystemExit("--flight-recorder/--stall-timeout/--cost-model "
                         "write to the telemetry sink; add "
                         "--metrics-jsonl PATH")
    # Clear any instance a previous in-process run leaked before the
    # measurement bodies instrument their steps (train.make_telemetry
    # hygiene).
    obs_costmodel.set_default(None)
    if args.metrics_jsonl:
        _SINK = JsonlSink(args.metrics_jsonl)
        if args.flight_recorder:
            recorder = FlightRecorder(sink=_SINK, config=vars(args))
            recorder.install()
        if args.stall_timeout > 0:
            _WATCHDOG = StallWatchdog(_SINK,
                                      deadline_s=args.stall_timeout)
            _WATCHDOG.start()
        if args.cost_model:
            obs_costmodel.set_default(obs_costmodel.CostModel(
                sink=_SINK, peak_flops=_PEAKS.bf16_flops,
                hbm_gbps=_PEAKS.hbm_bytes_per_s / 1e9))

    defaults = {          # (batch_size, image_size, seq_len)
        "c1": (256, 32, None), "c2": (256, 224, None),
        "c3": (256, 224, None), "c4": (64, None, 128),
        "c5": (32, None, 192), "gpt": (64, None, 128),
        "hostpipe": (256, 224, None),
    }
    db, di, ds = defaults[args.config]
    if args.batch_size is None:
        args.batch_size = db
    if args.image_size is None:
        args.image_size = di
    if args.seq_len is None:
        args.seq_len = ds

    try:
        if args.config == "c1":
            bench_image_single(
                args, arch="resnet18", opt_level="O0",
                image_size=args.image_size, num_classes=10,
                metric="resnet18_cifar_fp32_images_per_sec_per_chip",
                vs_target=False)
        elif args.config == "c2":
            bench_image_single(
                args, arch="resnet50", opt_level="O2",
                image_size=args.image_size, num_classes=1000,
                metric="resnet50_imagenet_ampO2_bf16_train_images_per_sec"
                       "_per_chip",
                vs_target=True)
        elif args.config == "c3":
            bench_c3(args)
        elif args.config == "c4":
            bench_c4(args)
        elif args.config == "c5":
            bench_c5(args)
        elif args.config == "gpt":
            bench_gpt(args)
        elif args.config == "hostpipe":
            bench_hostpipe(args)
    finally:
        # Crash-aware teardown (sys.exc_info is live inside a finally):
        # an unwinding exception leaves a crash_dump, not a silent stream.
        if _WATCHDOG is not None:
            _WATCHDOG.close()
        exc = sys.exc_info()
        if recorder is not None:
            if exc[0] is not None and not issubclass(exc[0], SystemExit):
                recorder.crash_dump(f"exception:{exc[0].__name__}",
                                    exc_info=exc)
            recorder.close()
        obs_costmodel.set_default(None)
        if _SINK is not None:
            _SINK.close()


if __name__ == "__main__":
    main()
