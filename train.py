#!/usr/bin/env python
"""train.py — the reference-parity training entrypoint, TPU-native.

CLI surface preserved from the reference harness (SURVEY.md §3.5/§6: argparse
flags --arch --opt-level --loss-scale --sync_bn --delay-allreduce ... as in
apex's examples/imagenet/main_amp.py pattern), so invocations carry over.
Flags that configure CUDA-specific machinery (--local_rank process binding,
--workers, channels-last) are accepted and recorded but are no-ops on TPU —
one process drives all local devices and the mesh replaces process groups.

Examples
--------
C1 (ResNet-18 / CIFAR-shaped, fp32, single device):
    python train.py --arch resnet18 --dataset cifar10 --opt-level O0 \
        --epochs 2 --batch-size 256

C2/C3 (ResNet-50 / ImageNet-shaped, amp O2 bf16, DDP over all devices):
    python train.py --arch resnet50 --dataset imagenet --opt-level O2 \
        --sync_bn --batch-size 256 --opt sgd
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from apex_example_tpu import amp
from apex_example_tpu import obs
from apex_example_tpu.data import CIFAR10, IMAGENET, image_batch, lm_batch, \
    mlm_batch
from apex_example_tpu.engine import (
    create_train_state, make_eval_step, make_sharded_train_step,
    make_train_step)
from apex_example_tpu.models import ARCHS
from apex_example_tpu.models.bert import bert_base, bert_tiny
from apex_example_tpu.models.transformer_xl import (transformer_xl_base,
                                                    transformer_xl_tiny)
from apex_example_tpu.optim import (DistributedFusedAdam, FusedAdagrad,
                                    FusedAdam, FusedLAMB, FusedNovoGrad,
                                    FusedSGD, build_schedule,
                                    make_zero_train_step)
from apex_example_tpu.parallel import (DDPConfig, LARC, is_main_process,
                                       make_data_mesh,
                                       maybe_initialize_distributed)
from apex_example_tpu.obs import (TelemetryEmitter, TensorBoardAdapter,
                                  make_profiler_window, rank_print, span)
from apex_example_tpu.resilience import (EX_TEMPFAIL, FaultPlan,
                                         PreemptionHandler)
from apex_example_tpu.utils import AverageMeter, Throughput
from apex_example_tpu.utils.checkpoint import (CheckpointManager,
                                               restore_under_mesh)
from apex_example_tpu.utils.compile_cache import enable_compile_cache
from apex_example_tpu.workloads import (lm_loss,
                                        make_sharded_txl_train_step,
                                        make_txl_train_step, mlm_loss)

LM_ARCHS = ["bert_base", "bert_tiny", "gpt_base", "gpt_tiny",
            "transformer_xl", "transformer_xl_tiny"]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="TPU-native apex-parity trainer")
    p.add_argument("--arch", "-a", default="resnet18",
                   choices=sorted(ARCHS) + LM_ARCHS)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--fused-attention", action="store_true",
                   help="blockwise flash-attention kernel for BERT archs "
                        "(ops/attention.py; fp32-softmax opt levels only)")
    p.add_argument("--vocab-size", type=int, default=30522)
    p.add_argument("--max-grad-norm", type=float, default=0.25,
                   help="global-norm grad clip (transformer_xl)")
    p.add_argument("--dataset", default="cifar10",
                   choices=["cifar10", "imagenet"])
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--steps-per-epoch", type=int, default=100)
    p.add_argument("--batch-size", "-b", type=int, default=256,
                   help="global batch size (split across devices)")
    p.add_argument("--lr", type=float, default=0.1)
    # LR schedule (reference harness: step-decay adjust_learning_rate with
    # warmup; BERT/LAMB uses warmup+poly — SURVEY.md §3.5, §7)
    p.add_argument("--lr-schedule", default="const",
                   choices=["const", "step", "cosine", "poly"])
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--lr-decay-epochs", default="",
                   help='comma epochs for step decay, e.g. "30,60,90" '
                        "(default: 1/3 and 2/3 of the run)")
    p.add_argument("--lr-gamma", type=float, default=0.1)
    p.add_argument("--lr-min", type=float, default=0.0)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", "--wd", type=float, default=1e-4)
    p.add_argument("--opt", default="sgd",
                   choices=["sgd", "adam", "lamb", "novograd", "adagrad"])
    p.add_argument("--larc", action="store_true",
                   help="wrap the optimizer in LARC layer-wise adaptive "
                        "rate control (parallel/larc.py; apex.parallel.LARC)")
    p.add_argument("--larc-trust", type=float, default=0.02,
                   help="LARC trust coefficient")
    # amp surface (apex parity)
    p.add_argument("--opt-level", default="O0",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None,
                   help='None, a number, or "dynamic"')
    p.add_argument("--keep-batchnorm-fp32", default=None, type=lambda s:
                   None if s in (None, "None") else s.lower() == "true")
    # DDP surface (apex parity)
    p.add_argument("--sync_bn", action="store_true",
                   help="use cross-replica SyncBatchNorm")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO-1 optimizer-state sharding over the data "
                        "axis (DistributedFusedAdam; forces --opt adam, "
                        "image workloads, >1 device, static loss scale)")
    p.add_argument("--delay-allreduce", action="store_true", default=True)
    p.add_argument("--gradient-predivide-factor", type=float, default=1.0)
    p.add_argument("--quantized-allreduce", default="off",
                   choices=["off", "int8"],
                   help="DDP gradient exchange precision (ISSUE 13; "
                        "EQuARX, PAPERS.md): int8 reduces each "
                        "--quant-chunk-element chunk under one "
                        "pmax-shared max-abs scale (error bound "
                        "world*scale/2 per element, see "
                        "parallel/distributed.py); off is bit-identical "
                        "to the unquantized path")
    p.add_argument("--quant-chunk", type=int, default=1024,
                   help="chunk size (elements) for --quantized-allreduce "
                        "scales")
    p.add_argument("--num-devices", type=int, default=None,
                   help="devices to use (default: all)")
    # Megatron-style model parallelism (apex.transformer parity, GSPMD form)
    p.add_argument("--tensor-parallel", type=int, default=1, metavar="TP",
                   help="shard attention heads / MLP features / vocab over "
                        "a 'model' mesh axis of this size (BERT archs); "
                        "remaining devices form the data axis")
    p.add_argument("--sequence-parallel", action="store_true",
                   help="with --tensor-parallel: keep activations outside "
                        "the TP blocks sequence-sharded (Megatron-SP)")
    p.add_argument("--pipeline-parallel", type=int, default=1, metavar="PP",
                   help="split BERT/GPT's encoder layers into this many "
                        "stages "
                        "driven by the SPMD ring schedule "
                        "(transformer/bert_pipeline.py); remaining devices "
                        "form the data axis")
    p.add_argument("--microbatches", type=int, default=4,
                   help="ring slots per data shard under "
                        "--pipeline-parallel")
    p.add_argument("--pipeline-schedule", default="ring",
                   choices=["ring", "1f1b", "interleaved"],
                   help="pipeline program: SPMD ring (autodiff backward; "
                        "composes with TP), true 1F1B (bounded in-flight "
                        "activations), or interleaved virtual stages "
                        "(apex's three schedule entry points)")
    p.add_argument("--virtual-stages", type=int, default=None,
                   help="chunks per device for --pipeline-schedule "
                        "interleaved (default 2; rejected with other "
                        "schedules rather than silently ignored)")
    p.add_argument("--context-parallel", type=int, default=1, metavar="CP",
                   help="shard BERT's sequence over a 'context' mesh axis "
                        "of this size (ppermute KV-ring attention — the "
                        "long-context training path); remaining devices "
                        "form the data axis")
    p.add_argument("--cp-mode", default="ring",
                   choices=["ring", "zigzag", "ulysses"],
                   help="attention program under --context-parallel: "
                        "'ring' (ppermute KV ring), 'zigzag' (load-"
                        "balanced CAUSAL ring, gpt archs — each device "
                        "holds chunks (i, 2n-1-i) so every ring step does "
                        "identical live work), 'ulysses' (all-to-all head "
                        "sharding: full sequence per device, H/N heads "
                        "per device; needs heads divisible by CP)")
    p.add_argument("--moe-experts", type=int, default=0, metavar="E",
                   help="switch-MoE BERT/GPT FFNs with E experts, E/n per "
                        "device over the 'data' axis of size n (expert "
                        "parallelism via all_to_all dispatch; requires "
                        "E to be a multiple of the data-axis size)")
    p.add_argument("--moe-aux-weight", type=float, default=1e-2,
                   help="weight of the Switch load-balancing aux loss in "
                        "the --moe-experts objective")
    p.add_argument("--moe-top-k", type=int, default=1, choices=[1, 2],
                   help="router fan-out under --moe-experts: 1 = Switch "
                        "top-1, 2 = GShard-style top-2 (renormalized "
                        "gates; second choices dropped first under "
                        "capacity pressure)")
    p.add_argument("--moe-capacity-factor", type=float, default=1.25,
                   help="per-expert token capacity multiplier under "
                        "--moe-experts (overflow tokens ride the residual "
                        "only)")
    # harness
    p.add_argument("--resume", default="", help="checkpoint dir to resume")
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--async-checkpoint", action="store_true",
                   help="don't block training on checkpoint IO (orbax "
                        "background write; joined before the next save)")
    p.add_argument("--save-every-steps", type=int, default=0, metavar="N",
                   help="also checkpoint every N optimizer steps (requires "
                        "--checkpoint-dir; epoch boundaries still save) — "
                        "bounds how stale the preemption grace path's "
                        "'last checkpoint' can be on long epochs")
    p.add_argument("--remat", default="none",
                   choices=["none", "conv", "block"],
                   help="rematerialization for image archs: 'conv' saves "
                        "only conv outputs (BN/ReLU recomputed in backward)"
                        ", 'block' saves only block inputs")
    p.add_argument("--host-pipeline", action="store_true",
                   help="feed batches from the native C++ prefetcher "
                        "(csrc/; the reference's fast_collate analog) "
                        "instead of on-device synthesis")
    p.add_argument("--print-freq", type=int, default=10)
    # observability (obs/ subsystem; README "Observability")
    p.add_argument("--metrics-jsonl", default="", metavar="PATH",
                   help="emit one schema-valid telemetry record per step "
                        "(loss, scale, grad norm, step time, items/sec, "
                        "overflow count) plus run header/summary to this "
                        "JSONL file; rank 0 writes by default "
                        "(tools/metrics_lint.py validates)")
    p.add_argument("--metrics-all-ranks", action="store_true",
                   help="with --metrics-jsonl: every process writes its "
                        "own per-host file (PATH.rank<K> for K > 0)")
    p.add_argument("--profile-window", default="", metavar="N:M",
                   help="capture a jax profiler trace for exactly run-"
                        "relative steps N..M (1-based, inclusive) instead "
                        "of --prof's whole-run dump")
    p.add_argument("--cost-model", action="store_true",
                   help="with --metrics-jsonl: compile the step/eval "
                        "functions through the AOT path and emit one "
                        "schema-v6 'compile_event' (compile wall time, "
                        "lowering hash) + 'cost_model' (XLA flops/HBM "
                        "bytes/memory + roofline verdict) record per "
                        "compilation (obs/costmodel.py; zero extra "
                        "compiles — tools/cost_report.py reports)")
    p.add_argument("--trace", action="store_true",
                   help="with --metrics-jsonl: emit schema-v9 "
                        "trace_event records for every host span "
                        "(data / step / checkpoint) — a per-step "
                        "timeline exportable to Perfetto via "
                        "tools/trace_export.py; histograms and stdout "
                        "unchanged (README 'Request tracing')")
    p.add_argument("--tick-profile", action="store_true",
                   help="with --metrics-jsonl: arm the hot-path step "
                        "profiler (obs/tickprof.py, ISSUE 17) — every "
                        "image-loop training step decomposes into "
                        "data_wait / dispatch / device (an explicit "
                        "block-until-ready boundary separating enqueue "
                        "cost from device execution) / telemetry / "
                        "checkpoint, folded into online quantile "
                        "sketches; every Nth step emits a schema-v15 "
                        "tick_profile record and the run closes with an "
                        "overhead_summary (host_gap_ms, per-phase "
                        "percentiles, host_overhead_frac — what "
                        "tools/perf_ledger.py regression-gates).  The "
                        "boundary sync trades host/device overlap for "
                        "attribution, so keep it off for BENCH numbers; "
                        "LM loops are not decomposed (README 'Hot-path "
                        "profiling')")
    p.add_argument("--tick-profile-every", type=int, default=16,
                   metavar="N",
                   help="emit a tick_profile record every N steps "
                        "(default 16; the cumulative overhead_summary "
                        "always folds EVERY step)")
    # diagnostics stratum (obs/flight.py, obs/watchdog.py, obs/numerics.py;
    # README "Diagnostics") — all write to the --metrics-jsonl sink
    p.add_argument("--flight-recorder", action="store_true",
                   help="with --metrics-jsonl: keep a ring of the last K "
                        "step records and, on crash/SIGTERM/SIGINT, emit "
                        "a 'crash_dump' record plus an aborted run "
                        "summary to the JSONL sink (obs/flight.py)")
    p.add_argument("--flight-recorder-keep", type=int, default=64,
                   metavar="K",
                   help="step records the flight recorder's ring retains")
    p.add_argument("--stall-timeout", type=float, default=0.0, metavar="S",
                   help="with --metrics-jsonl: if no step completes for S "
                        "seconds, dump all-thread stacks and emit a "
                        "'stall' record (0 disables; the deadline covers "
                        "the first step's compile — size it accordingly)")
    p.add_argument("--stall-trace", action="store_true",
                   help="with --stall-timeout: on the first stall, arm a "
                        "one-shot profiler trace (stall start to first "
                        "recovered step) in the --profile-window trace dir")
    p.add_argument("--numerics-check", default="off",
                   choices=["off", "overflow", "always"],
                   help="overflow provenance fused into the engine's "
                        "finite-check pass: per-module non-finite counts "
                        "+ grad norms, emitted as 'overflow_event' "
                        "records naming the offending module(s) "
                        "('overflow': only on overflow steps; 'always': "
                        "every step; requires --metrics-jsonl)")
    # resilience stratum (apex_example_tpu/resilience/; README
    # "Resilience") — preemption grace, supervised auto-resume, fault
    # drills.  tools/supervise.py is the restart supervisor.
    p.add_argument("--preempt-grace", action="store_true",
                   help="catch SIGTERM/SIGUSR1 and exit gracefully at the "
                        "next step boundary: join pending checkpoint IO, "
                        "save a final checkpoint (with --checkpoint-dir), "
                        "emit a 'preemption' record (with --metrics-jsonl) "
                        "and exit 75/EX_TEMPFAIL so a supervisor "
                        "(tools/supervise.py) restarts the run instead of "
                        "declaring it broken")
    p.add_argument("--inject-fault", default="", metavar="KIND@STEP",
                   help="deterministic fault drill at a 1-based global "
                        "step: crash | sigterm | hang | nan "
                        "(resilience/faults.py); a resumed run already "
                        "past STEP never re-fires")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eval", action="store_true")
    p.add_argument("--eval-batches", type=int, default=10,
                   help="validation batches per eval pass")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="microbatches accumulated per optimizer step")
    p.add_argument("--tensorboard", default="",
                   help="write scalars to this tensorboard logdir")
    p.add_argument("--prof", action="store_true",
                   help="capture a jax profiler trace of a few steps")
    p.add_argument("--prof-server", type=int, default=0, metavar="PORT",
                   help="start jax.profiler.start_server(PORT) for live "
                        "xprof/tensorboard capture (SURVEY.md §6 tracing)")
    # accepted no-ops (CUDA-specific in the reference)
    p.add_argument("--local_rank", type=int, default=0)
    p.add_argument("--workers", type=int, default=4)
    p.add_argument("--deterministic", action="store_true")
    return p.parse_args(argv)


def select_devices(args):
    devices = jax.devices()[:args.num_devices] if args.num_devices \
        else jax.devices()
    if args.batch_size % len(devices):
        raise SystemExit(f"--batch-size {args.batch_size} not divisible by "
                         f"{len(devices)} devices")
    return devices


def build_lr(args):
    """Float or traced schedule f(step), fed to the fused optimizers'
    callable-lr path."""
    total = args.epochs * args.steps_per_epoch
    boundaries = [int(e) * args.steps_per_epoch
                  for e in args.lr_decay_epochs.split(",") if e]
    return build_schedule(args.lr_schedule, args.lr, total,
                          warmup_steps=args.warmup_steps,
                          boundaries=boundaries, gamma=args.lr_gamma,
                          min_lr=args.lr_min)


def make_writer(args):
    """Optional tensorboard writer (SURVEY.md §6 metrics row: stdout meters
    are the contract; tensorboardX sits behind a flag), rank-0 only."""
    if not args.tensorboard or not is_main_process():
        return None
    from tensorboardX import SummaryWriter
    return SummaryWriter(args.tensorboard)


def make_telemetry(args):
    """Flag-gated obs wiring shared by the image and LM loops: the per-step
    telemetry emitter (--metrics-jsonl), the profiler window
    (--profile-window), and the diagnostics stratum (--flight-recorder /
    --stall-timeout / --numerics-check) riding the emitter as observers.
    Also binds the span registry so host spans ("data"/"step") aggregate
    into the run_summary."""
    emitter = recorder = watchdog = None
    # Clear any cost-model/tracer instance a previous in-process run
    # leaked (e.g. it died between telemetry setup and its finally):
    # this run's instrument() sites run after us, so a stale default
    # must not write records into the old run's stream.
    obs.costmodel.set_default(None)
    obs.trace.set_default(None)
    if args.metrics_jsonl:
        registry = obs.MetricsRegistry()
        obs.set_default_registry(registry)
        sink = obs.JsonlSink(args.metrics_jsonl,
                             all_ranks=args.metrics_all_ranks)
        emitter = TelemetryEmitter(sink, registry=registry)
        emitter.run_header(config=vars(args), argv=sys.argv[1:],
                           arch=args.arch)
        if args.cost_model:
            # Installed as the process default so the loops' single
            # instrument() call sites stay no-ops when the flag is off;
            # close_telemetry clears it (a programmatic caller must not
            # inherit the instance).
            obs.costmodel.set_default(obs.CostModel(
                sink=sink, registry=registry, run_id=emitter.run_id))
        if getattr(args, "trace", False):
            # Same process-default shape: the span layer (obs/spans.py)
            # consults it, so every data/step/checkpoint span lands as
            # a schema-v9 trace_event alongside its histogram; a
            # supervised restart joins the parent timeline via
            # APEX_TRACE_ID (obs/trace.py).
            obs.trace.set_default(obs.Tracer(sink, run_id=emitter.run_id))
        if args.flight_recorder:
            recorder = obs.FlightRecorder(emitter, config=vars(args),
                                          keep=args.flight_recorder_keep)
            recorder.install()
            emitter.add_observer(recorder.on_record)
        if args.stall_timeout > 0:
            from apex_example_tpu.obs import DEFAULT_TRACE_DIR
            watchdog = obs.StallWatchdog(
                sink, deadline_s=args.stall_timeout, run_id=emitter.run_id,
                trace_dir=DEFAULT_TRACE_DIR if args.stall_trace else None)
            watchdog.start()
            emitter.add_observer(watchdog.on_record)
        if args.numerics_check != "off":
            monitor = obs.NumericsMonitor(sink, mode=args.numerics_check,
                                          run_id=emitter.run_id)
            emitter.add_observer(monitor.on_record)
    return emitter, make_profiler_window(args.profile_window or None), \
        recorder, watchdog


def make_tickprof(args, emitter):
    """--tick-profile wiring (ISSUE 17): the hot-path step profiler,
    sharing the emitter's sink and run id.  The image loop feeds it one
    data_wait/dispatch/device/telemetry/checkpoint decomposition per
    step; arming it costs one block_until_ready per step at the
    enqueue/device boundary — attribution in exchange for host/device
    overlap (README 'Hot-path profiling')."""
    if not getattr(args, "tick_profile", False):
        return None
    if emitter is None:
        raise SystemExit("--tick-profile requires --metrics-jsonl (the "
                         "tick_profile/overhead_summary records ride "
                         "the metrics stream)")
    if args.tick_profile_every < 1:
        raise SystemExit(f"--tick-profile-every must be >= 1, got "
                         f"{args.tick_profile_every}")
    from apex_example_tpu.obs.tickprof import TickProfiler
    return TickProfiler(kind="train",
                        sample_every=args.tick_profile_every,
                        emit=emitter.sink.write, run_id=emitter.run_id)


def close_telemetry(emitter, profwin, recorder=None, watchdog=None):
    """Counterpart of make_telemetry for the finally blocks: stop an open
    trace window, disarm the watchdog, flush the run_summary, unbind the
    span registry (a programmatic caller must not inherit it).  Called
    while an exception is unwinding (sys.exc_info is live inside a
    finally), it routes through the flight recorder instead: crash_dump +
    aborted summary, not a clean close."""
    if profwin is not None:
        profwin.close()
    if watchdog is not None:
        watchdog.close()
    exc = sys.exc_info()
    if recorder is not None and exc[0] is not None \
            and not issubclass(exc[0], SystemExit):
        recorder.crash_dump(f"exception:{exc[0].__name__}", exc_info=exc)
    if recorder is not None:
        recorder.close()
    if emitter is not None:
        emitter.close()
    obs.set_default_registry(None)
    obs.costmodel.set_default(None)
    obs.trace.set_default(None)


def make_resilience(args, recorder):
    """--preempt-grace handler + --inject-fault plan for a train loop.
    Installed AFTER make_telemetry so the grace handler can take SIGTERM
    ownership over from the flight recorder (release_signal handover —
    a preempted run saves and exits 75 instead of crash-dumping 143);
    the recorder keeps excepthook/atexit/faulthandler for real crashes."""
    preempt = fault = None
    if args.preempt_grace:
        preempt = PreemptionHandler(recorder=recorder)
        preempt.install()
    if args.inject_fault:
        fault = FaultPlan.parse(args.inject_fault)
    return preempt, fault


def host_loop_state(args, global_step):
    """The host-state checkpoint sidecar (utils/checkpoint.py): loop
    position + host PRNG state — everything resume needs that lives
    outside the TrainState.  The synthetic data streams are index-driven
    (data/__init__.py: batch_fn(global_step)), so ``data_index`` IS the
    stream position; persisting it (with the python PRNG, for host-side
    augmentation) makes mid-epoch resume continue the exact stream
    instead of restarting the epoch."""
    import random
    rng_version, rng_state, rng_gauss = random.getstate()
    return {
        "step": int(global_step),
        "data_index": int(global_step),
        "steps_per_epoch": int(args.steps_per_epoch),
        "epoch": int(global_step) // args.steps_per_epoch,
        "step_in_epoch": int(global_step) % args.steps_per_epoch,
        "seed": int(args.seed),
        "python_random": [rng_version, list(rng_state), rng_gauss],
    }


def restore_loop_position(args, rmgr, global_step):
    """(start_epoch, start_step_in_epoch) for a resumed run, restoring
    the host PRNG from the sidecar when one exists.  Falls back to
    deriving position from the restored step alone (pre-sidecar
    checkpoints stay resumable — at epoch granularity both forms agree;
    mid-epoch they also agree as long as --steps-per-epoch is
    unchanged)."""
    hs = rmgr.load_host_state(global_step)
    start_epoch = global_step // args.steps_per_epoch
    start_i = global_step % args.steps_per_epoch
    if hs:
        if hs.get("step") == global_step \
                and hs.get("steps_per_epoch") == args.steps_per_epoch:
            start_epoch = int(hs.get("epoch", start_epoch))
            start_i = int(hs.get("step_in_epoch", start_i))
        rng = hs.get("python_random")
        if rng:
            import random
            random.setstate((rng[0], tuple(rng[1]), rng[2]))
    return start_epoch, start_i


def graceful_preempt_exit(args, mgr, state, preempt, emitter, global_step,
                          last_saved=None):
    """The preemption grace sequence (resilience/preemption.py docstring;
    runs at a step boundary, NOT in signal context): join any pending
    async orbax write, save a final checkpoint + host-state sidecar,
    emit the schema-v4 ``preemption`` record, and hand back EX_TEMPFAIL
    (75) so the supervisor restarts rather than buries the run.  The
    caller's finally still runs close_telemetry — with no exception
    unwinding, so the stream closes with a normal (un-aborted)
    run_summary after the preemption record."""
    if args.prof:
        # The returns below skip the loops' post-try stop_trace — an
        # unstopped trace is never finalized on disk.
        jax.profiler.stop_trace()
        rank_print("profile written to /tmp/apex_tpu_trace")
    ckstep = None
    if mgr is not None:
        if is_main_process():
            mgr.wait_until_finished()
            if last_saved != int(state.step):
                mgr.save(state, wait=True,
                         host_state=host_loop_state(args, global_step))
            else:
                # This exact step is already on disk (a --save-every-steps
                # boundary); just refresh its sidecar.
                mgr.save_host_state(int(state.step),
                                    host_loop_state(args, global_step))
        # ckstep/saved describe the RUN, not this rank: rank 0 owns the
        # write (state is replicated), so every rank's preemption record
        # reports the same run-level outcome — fleet_report must not see
        # contradictory saved flags for one run.
        ckstep = int(state.step)
        rank_print(f"preempted by {preempt.signal_name}: saved checkpoint "
                   f"at step {ckstep}; exiting {EX_TEMPFAIL} (resumable)")
    else:
        rank_print(f"preempted by {preempt.signal_name}: no "
                   f"--checkpoint-dir, nothing saved; exiting "
                   f"{EX_TEMPFAIL}")
    if emitter is not None:
        emitter.preemption(preempt.signal_name, step=int(global_step),
                           checkpoint_step=ckstep,
                           saved=ckstep is not None)
    return EX_TEMPFAIL


def build_optimizer(args):
    lr = build_lr(args)
    # Under LARC, weight decay moves INTO the trust ratio (apex zeroes the
    # group's wd and folds it into the LARC denominator; wd applied by the
    # inner optimizer after the scaling would be a different update).
    wd = 0.0 if args.larc else args.weight_decay
    if args.opt == "sgd":
        opt = FusedSGD(lr=lr, momentum=args.momentum, weight_decay=wd)
    elif args.opt == "adam":
        opt = FusedAdam(lr=lr, weight_decay=wd)
    elif args.opt == "novograd":
        opt = FusedNovoGrad(lr=lr, weight_decay=wd)
    elif args.opt == "adagrad":
        opt = FusedAdagrad(lr=lr, weight_decay=wd)
    else:
        opt = FusedLAMB(lr=lr, weight_decay=wd)
    if args.larc:
        # apex recipe shape: LARC wraps the inner optimizer and scales each
        # leaf's update by the trust ratio ||p||/||g|| (parallel/larc.py).
        # Clip mode needs the outer lr; under an LR schedule the BASE lr
        # bounds the ratio (apex clamps against the per-step group lr).
        opt = LARC(opt.as_optax(), trust_coefficient=args.larc_trust,
                   lr=args.lr, weight_decay=args.weight_decay)
    return opt


def pick_devices(args):
    """Device list without main()'s batch-divisibility check (the TP/PP
    paths divide the batch by their data-axis size instead)."""
    return jax.devices()[:args.num_devices] if args.num_devices \
        else jax.devices()


def build_zero_optimizer(args, n_dev, gspmd=False,
                         global_mean_grads=False):
    """Optimizer for the --zero paths.

    shard_map path (tp == 1): DistributedFusedAdam, the explicit flat-buffer
    reduce-scatter/all-gather program.  GSPMD path (--tensor-parallel): plain
    FusedAdam — there the ZeRO-1 contract lives entirely in the opt-state
    shardings (engine.gspmd_state_shardings zero_axis), not in the optimizer.
    """
    if args.larc:
        raise SystemExit("--larc does not compose with --zero (the sharded "
                         "optimizer owns its update)")
    if n_dev < 2:
        raise SystemExit("--zero needs >1 device on the data axis (state "
                         "shards over it)")
    if args.opt != "adam":
        raise SystemExit("--zero is wired for --opt adam "
                         "(DistributedFusedAdam)")
    if args.grad_accum != 1:
        raise SystemExit("--zero does not support --grad-accum")
    if args.gradient_predivide_factor != 1.0:
        raise SystemExit("--zero does not support "
                         "--gradient-predivide-factor (the reduction "
                         "lives inside the sharded optimizer)")
    if gspmd:
        return FusedAdam(lr=build_lr(args), weight_decay=args.weight_decay)
    return DistributedFusedAdam(lr=build_lr(args),
                                weight_decay=args.weight_decay,
                                world=n_dev,
                                # the CP losses are psum-normalized
                                # GLOBALLY, so their implicitly psum-ed
                                # grads are already the true global mean
                                # (optim/distributed.py ctor docstring)
                                grads_global_mean=global_mean_grads)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    if args.grad_accum > 1 and args.batch_size % args.grad_accum:
        # Uniform rejection for every path (the microbatch split would
        # otherwise surface as a reshape TypeError deep inside tracing).
        raise SystemExit(f"--batch-size {args.batch_size} not divisible by "
                         f"--grad-accum {args.grad_accum}")
    # Multi-host rendezvous (no-op single-host): must precede first device
    # use.  Launch contract in parallel/launch.py — JAX_COORDINATOR_ADDRESS
    # or the reference's MASTER_ADDR/PORT + WORLD_SIZE/RANK (hosts).
    proc_id, n_procs = maybe_initialize_distributed()
    # Reference behavior: only rank 0 writes to stdout.  rank_print (the
    # old global-print monkeypatch's replacement, obs/logging.py) keeps
    # rank 0 byte-identical to print() and routes worker lines to the
    # package logger at DEBUG instead of deleting them.
    if args.prof and args.profile_window:
        raise SystemExit("--prof traces the whole run; pick it or "
                         "--profile-window N:M, not both")
    if (args.flight_recorder or args.stall_timeout > 0
            or args.numerics_check != "off") and not args.metrics_jsonl:
        raise SystemExit("--flight-recorder/--stall-timeout/"
                         "--numerics-check write to the telemetry sink; "
                         "add --metrics-jsonl PATH")
    if args.cost_model and not args.metrics_jsonl:
        raise SystemExit("--cost-model emits compile_event/cost_model "
                         "records to the telemetry sink; add "
                         "--metrics-jsonl PATH")
    if args.trace and not args.metrics_jsonl:
        raise SystemExit("--trace emits trace_event records to the "
                         "telemetry sink; add --metrics-jsonl PATH")
    if args.stall_trace and args.stall_timeout <= 0:
        raise SystemExit("--stall-trace arms on a stall; it needs "
                         "--stall-timeout S")
    if args.save_every_steps < 0:
        raise SystemExit(f"--save-every-steps {args.save_every_steps} "
                         "must be >= 0")
    if args.save_every_steps and not args.checkpoint_dir:
        raise SystemExit("--save-every-steps writes through "
                         "--checkpoint-dir; add it")
    if args.inject_fault:
        # Early CLI gate only (uniform SystemExit before devices/model
        # build); make_resilience re-parses to build each loop's plan.
        try:
            FaultPlan.parse(args.inject_fault)
        except ValueError as e:
            raise SystemExit(str(e))
    if args.numerics_check != "off" and (
            args.zero or args.pipeline_parallel > 1
            or args.context_parallel > 1 or args.moe_experts
            or args.arch.startswith("transformer_xl")):
        raise SystemExit("--numerics-check rides the shared engine step's "
                         "finite-check pass (engine.make_train_step); the "
                         "--zero/--pipeline-parallel/--context-parallel/"
                         "--moe-experts and transformer_xl steps own their "
                         "own grad pipelines and are not wired yet")
    if args.profile_window:
        from apex_example_tpu.obs import parse_window
        try:
            parse_window(args.profile_window)
        except ValueError as e:
            raise SystemExit(str(e))
    if args.prof_server:
        # Per-process port offset: single-host multi-process launches (the
        # localhost rendezvous tests/test_launch.py exercises) would
        # otherwise all bind the same port.
        port = args.prof_server + jax.process_index()
        jax.profiler.start_server(port)
        rank_print(f"profiler server on :{port}")
    policy, scaler = amp.initialize(
        args.opt_level, loss_scale=args.loss_scale,
        keep_batchnorm_fp32=args.keep_batchnorm_fp32)
    if args.fused_attention and not args.arch.startswith(("bert", "gpt")):
        # Uniform rejection (not a silent no-op): the kernel is wired into
        # the BERT/GPT attention module only — see lm_main for the
        # transformer_xl rationale.
        raise SystemExit("--fused-attention is wired for the BERT/GPT "
                         "archs only")
    if args.fused_attention and args.opt_level == "O3":
        # The kernel's softmax is always fp32; O3's contract is half softmax
        # and the module gate would silently fall back to the naive path.
        raise SystemExit("--fused-attention requires fp32 softmax "
                         "(opt levels O0-O2); O3 runs softmax half")
    if args.arch in LM_ARCHS:
        return lm_main(args, policy, scaler)

    if args.tensor_parallel > 1:
        raise SystemExit("--tensor-parallel is wired for the transformer "
                         "archs (bert_*, transformer_xl*); image models "
                         "scale by DP/--zero")
    if args.pipeline_parallel > 1:
        raise SystemExit("--pipeline-parallel is wired for the BERT archs; "
                         "image models scale by DP/--zero")
    if args.context_parallel > 1:
        raise SystemExit("--context-parallel is wired for the BERT archs "
                         "(sequence sharding; images have no sequence)")
    if args.moe_experts:
        raise SystemExit("--moe-experts is wired for the BERT archs "
                         "(switch-MoE replaces the transformer FFN)")
    if args.cp_mode != "ring":
        raise SystemExit(f"--cp-mode {args.cp_mode} only applies with "
                         "--context-parallel on the LM archs")

    spec = CIFAR10 if args.dataset == "cifar10" else IMAGENET
    devices = select_devices(args)
    n_dev = len(devices)

    # Per-op-class dtypes from the policy + white/blacklist tables (O1's
    # call-site classification; O0/O2/O3 collapse to the uniform table).
    md = amp.module_dtypes(policy)
    model = ARCHS[args.arch](
        num_classes=spec["num_classes"],
        dtype=md.compute,
        param_dtype=md.param,
        bn_dtype=md.bn_stats,
        bn_io_dtype=md.bn_io,
        bn_axis_name="data" if (args.sync_bn and n_dev > 1) else None,
        remat=args.remat)

    optimizer = build_zero_optimizer(args, n_dev) if args.zero \
        else build_optimizer(args)
    if args.host_pipeline:
        from apex_example_tpu import host_runtime
        if not host_runtime.available():
            raise SystemExit("--host-pipeline: native runtime not buildable")
    else:
        batch_fn = lambda i: image_batch(
            jnp.asarray(i, jnp.int32), batch_size=args.batch_size,
            image_size=spec["image_size"], channels=spec["channels"],
            num_classes=spec["num_classes"], seed=args.seed)

    sample = jnp.zeros((1, spec["image_size"], spec["image_size"],
                        spec["channels"]), jnp.float32)
    state = create_train_state(jax.random.PRNGKey(args.seed), model,
                               optimizer, sample, policy, scaler)

    ddp = DDPConfig(
        delay_allreduce=args.delay_allreduce,
        gradient_predivide_factor=args.gradient_predivide_factor,
        quantized_allreduce=args.quantized_allreduce == "int8",
        quant_chunk=args.quant_chunk)

    if n_dev > 1:
        mesh = make_data_mesh(devices=devices)
        if args.zero:
            step_fn = make_zero_train_step(mesh, model, optimizer, policy)
            rank_print(f"ZeRO-1 DDP over {n_dev} devices: {mesh}")
        else:
            step_fn = make_sharded_train_step(
                mesh, model, optimizer, policy, ddp=ddp,
                grad_accum=args.grad_accum,
                numerics=args.numerics_check != "off")
            rank_print(f"DDP over {n_dev} devices: {mesh}")
    else:
        step_fn = jax.jit(make_train_step(
            model, optimizer, policy, grad_accum=args.grad_accum,
            numerics=args.numerics_check != "off"),
            donate_argnums=(0,))
    eval_fn = jax.jit(make_eval_step(model))

    mgr = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir \
        else None
    writer = make_writer(args)
    tb = TensorBoardAdapter(writer)
    emitter, profwin, recorder, watchdog = make_telemetry(args)
    tickprof = make_tickprof(args, emitter)
    preempt, fault = make_resilience(args, recorder)
    # --cost-model: re-route the step through the AOT path so its one
    # compilation is harvested (compile_event + cost_model records); a
    # no-op identity without the flag (obs/costmodel.instrument).
    step_fn = obs.costmodel.instrument("train_step", step_fn)
    eval_fn = obs.costmodel.instrument("eval_step", eval_fn)
    start_epoch = start_i = 0
    if args.resume:
        rmgr = CheckpointManager(args.resume)
        if n_dev > 1:
            state = restore_under_mesh(
                rmgr, state, mesh, optimizer if args.zero else None)
        else:
            state = rmgr.restore(state)
        start_epoch, start_i = restore_loop_position(args, rmgr,
                                                     int(state.step))
        rank_print(f"resumed from step {int(state.step)} (epoch {start_epoch})")

    if args.prof:
        jax.profiler.start_trace("/tmp/apex_tpu_trace")

    global_step = int(state.step)
    prefetcher = None
    if args.host_pipeline:
        # Created AFTER resume so the native stream continues at the exact
        # batch index training stopped at (start_index); the eval stream
        # lives at a far-offset index range, disjoint from training — the
        # same contract as the on-device batch_fn(10_000 + epoch) path.
        mk = lambda start: host_runtime.NativePrefetcher(
            batch=args.batch_size, image_size=spec["image_size"],
            num_classes=spec["num_classes"], channels=spec["channels"],
            seed=args.seed, start_index=start)
        prefetcher = mk(global_step)

        def batch_fn(i):
            images, labels = next(prefetcher)
            return jnp.asarray(images), jnp.asarray(labels)

        def eval_batch_fn(i):
            # Deterministic in the batch index alone (a fresh stream per
            # call), so fresh and resumed runs evaluate identical batches —
            # the same contract as the on-device batch_fn(10_000 + epoch).
            pf = mk(10_000_000 + i)
            try:
                images, labels = next(pf)
            finally:
                pf.close()
            return jnp.asarray(images), jnp.asarray(labels)
    else:
        eval_batch_fn = batch_fn

    run_step = 0                    # run-relative step index (1-based in
    last_saved = None               # the loop; drives the profiler window)
    try:
        for epoch in range(start_epoch, args.epochs):
            losses, top1s = AverageMeter("loss"), AverageMeter("top1")
            thr = Throughput(warmup_steps=2)
            # Mid-epoch resume (host-state sidecar): the first resumed
            # epoch continues at its saved position instead of rerunning
            # the whole epoch — data indices stay continuous either way
            # (batch_fn is index-driven), this keeps the STEP COUNT exact.
            for i in range(start_i if epoch == start_epoch else 0,
                           args.steps_per_epoch):
                run_step += 1
                t_tick_start = time.perf_counter() \
                    if tickprof is not None else 0.0
                if profwin is not None:
                    profwin.on_step_start(run_step)
                with span("data"):
                    batch = batch_fn(global_step)
                if fault is not None:
                    batch = fault.maybe_poison(global_step + 1, batch)
                t_data_end = time.perf_counter() \
                    if tickprof is not None else 0.0
                t0 = time.perf_counter()
                with span("step"):
                    state, metrics = step_fn(state, batch)
                    global_step += 1
                    if tickprof is not None:
                        # The dispatch/device boundary (ISSUE 17): the
                        # jitted call has returned, its outputs may
                        # still be computing — block HERE so enqueue
                        # cost and device time separate.  Value-
                        # preserving: on_step's metric fetch was about
                        # to block on the same values anyway.
                        t_enqueue_end = time.perf_counter()
                        jax.block_until_ready((state, metrics))
                        t_device_end = time.perf_counter()
                    if emitter is not None:
                        # Inside the span: the blocking metric fetch is
                        # part of what "step" means when telemetry is on
                        # (obs.spans.PHASES).
                        emitter.on_step(global_step=global_step,
                                        epoch=epoch, metrics=metrics,
                                        items=args.batch_size, t_start=t0)
                thr.step(args.batch_size)
                if profwin is not None:
                    profwin.on_step_end(run_step, blocker=metrics)
                if (i + 1) % args.print_freq == 0 \
                        or i + 1 == args.steps_per_epoch:
                    losses.update(float(metrics["loss"]))
                    top1s.update(float(metrics["top1"]))
                    rank_print(f"epoch {epoch} step "
                          f"{i + 1}/{args.steps_per_epoch} "
                          f"{losses} {top1s} "
                          f"{thr.rate:.1f} img/s "
                          f"scale {float(metrics['scale']):.0f}")
                    tb.scalars({"train/loss": losses.val,
                                "train/top1": top1s.val,
                                "train/img_per_sec": thr.rate},
                               global_step)
                t_tel_end = time.perf_counter() \
                    if tickprof is not None else 0.0
                if args.save_every_steps and mgr is not None \
                        and is_main_process() \
                        and global_step % args.save_every_steps == 0:
                    with span("checkpoint"):
                        mgr.save(state, wait=not args.async_checkpoint,
                                 host_state=host_loop_state(args,
                                                            global_step))
                    last_saved = global_step
                    rank_print(f"saved checkpoint at step {global_step}")
                if tickprof is not None:
                    # Contiguous boundaries: the five phases telescope
                    # to the measured wall (perf_ledger's 1% gate).
                    # checkpoint covers the save-every-steps window and
                    # is 0.0 on steps that skip it.
                    t_tick_end = time.perf_counter()
                    tickprof.observe_tick(
                        t_tick_start,
                        (t_tick_end - t_tick_start) * 1e3,
                        data_wait=(t_data_end - t_tick_start) * 1e3,
                        dispatch=(t_enqueue_end - t_data_end) * 1e3,
                        device=(t_device_end - t_enqueue_end) * 1e3,
                        telemetry=(t_tel_end - t_device_end) * 1e3,
                        checkpoint=(t_tick_end - t_tel_end) * 1e3)
                if fault is not None:
                    # After the step's telemetry AND any interval save
                    # landed: forensics hold the last good step, and a
                    # crash@N drill with --save-every-steps N resumes
                    # PAST the fault instead of crash-looping.
                    fault.maybe_fire(global_step)
                if preempt is not None and preempt.preempted:
                    break               # grace sequence below the loops
            if preempt is not None and preempt.preempted:
                break
            if args.eval:
                # Full validation loop (reference harness shape: N batches,
                # top-1/top-5 meters, SURVEY.md §3.5) on a held-out index
                # range disjoint from training.
                el, e1, e5 = (AverageMeter("loss"), AverageMeter("top1"),
                              AverageMeter("top5"))
                for j in range(args.eval_batches):
                    em = eval_fn(state, eval_batch_fn(
                        10_000 + epoch * args.eval_batches + j))
                    el.update(float(em["loss"]))
                    e1.update(float(em["top1"]))
                    e5.update(float(em["top5"]))
                rank_print(f"epoch {epoch} EVAL loss {el.avg:.4f} "
                      f"top1 {e1.avg:.2f} top5 {e5.avg:.2f} "
                      f"({args.eval_batches} batches)")
                tb.scalars({"eval/loss": el.avg, "eval/top1": e1.avg,
                            "eval/top5": e5.avg}, global_step)
            if mgr is not None and is_main_process() \
                    and last_saved != int(state.step):
                # Reference: rank 0 writes the checkpoint (SURVEY.md §4.5);
                # state is replicated so one host's copy is the full state.
                # (last_saved guard: a --save-every-steps boundary landing
                # on the epoch end already wrote this exact step.)
                with span("checkpoint"):
                    mgr.save(state, wait=not args.async_checkpoint,
                             host_state=host_loop_state(args, global_step))
                last_saved = int(state.step)
                rank_print(f"saved checkpoint at step {int(state.step)}")
            if preempt is not None and preempt.preempted:
                # Re-poll AFTER eval + the epoch-end save: a SIGTERM that
                # lands during either must not cost one more training
                # step of the scheduler's kill-escalation window.
                break
        if preempt is not None and preempt.preempted:
            return graceful_preempt_exit(args, mgr, state, preempt,
                                         emitter, global_step,
                                         last_saved=last_saved)
        if tickprof is not None and tickprof.ticks:
            # Clean-exit close: the cumulative overhead fold lands
            # before close_telemetry's run_summary, so report tools
            # find it ahead of the stream tail.
            emitter.sink.write(tickprof.summary_record())
    finally:
        if preempt is not None:
            preempt.close()
        close_telemetry(emitter, profwin, recorder, watchdog)
        if prefetcher is not None:
            prefetcher.close()
        tb.close()
        if mgr is not None:
            mgr.wait_until_finished()

    if args.prof:
        jax.profiler.stop_trace()
        rank_print("profile written to /tmp/apex_tpu_trace")
    return 0


def lm_main(args, policy, scaler):
    """C4 (BERT-base MLM + FusedLAMB) and C5 (Transformer-XL) workloads."""
    try:
        return _lm_main_impl(args, policy, scaler)
    finally:
        if (args.tensor_parallel > 1 or args.pipeline_parallel > 1
                or args.context_parallel > 1):
            # Undo the TP/PP/CP paths' process-global kernel-dispatch
            # override and mesh registration even when SETUP raises (bad
            # --resume dir, indivisible batch, ...): a programmatic caller
            # must not inherit them.
            from apex_example_tpu.ops import _config as ops_config
            from apex_example_tpu.transformer import parallel_state
            ops_config.set_force_xla(False)
            parallel_state.set_mesh(None)


def _lm_main_impl(args, policy, scaler):
    tp = args.tensor_parallel
    pp = args.pipeline_parallel
    cp = args.context_parallel
    is_bert = args.arch.startswith("bert")
    is_gpt = args.arch.startswith("gpt")
    if args.moe_experts:
        if not (is_bert or is_gpt):
            raise SystemExit("--moe-experts is wired for the BERT/GPT "
                             "archs (switch-MoE replaces the "
                             "transformer FFN)")
        if args.sequence_parallel or args.zero:
            raise SystemExit("--moe-experts does not compose with "
                             "--sequence-parallel or --zero yet; "
                             "--tensor-parallel, --context-parallel and "
                             "--pipeline-parallel compose")
        if pp > 1:
            # EP x PP (round 5): experts inside the ring schedule's stage
            # cells, aux loss riding the schedule carry.  Bounds:
            if args.pipeline_schedule != "ring":
                raise SystemExit("--moe-experts composes with "
                                 "--pipeline-schedule ring only (the 1F1B "
                                 "value program has no aux-loss channel)")
            if tp > 1 or cp > 1:
                raise SystemExit("--moe-experts --pipeline-parallel "
                                 "composes pairwise only (no MoE x PP x "
                                 "TP/CP triple yet)")
            if args.eval:
                raise SystemExit("--eval under --moe-experts "
                                 "--pipeline-parallel is not wired (the "
                                 "dense unpacked eval would route with a "
                                 "different global capacity)")
            ep_pp = len(pick_devices(args)) // pp
            if ep_pp < 1:
                raise SystemExit(f"--pipeline-parallel {pp} exceeds the "
                                 f"{len(pick_devices(args))} devices")
            if args.moe_experts % ep_pp:
                raise SystemExit(f"--moe-experts {args.moe_experts} must "
                                 f"be a multiple of the data-axis size "
                                 f"{ep_pp} (= devices / "
                                 f"--pipeline-parallel)")
        # EP x CP, EP x TP and the EP x CP x TP triple all compose: the
        # expert all_to_all (manual 'data'), the KV ring (manual
        # 'context') and the GSPMD TP collectives (automatic 'model') are
        # independent; see workloads._moe_cp_axis_names.
        if args.opt in ("lamb", "novograd") or args.larc:
            raise SystemExit("--opt lamb/novograd and --larc compute "
                             "per-tensor statistics that collapse on the "
                             "EP-sharded [E, ...] expert stacks; use adam/"
                             "sgd/adagrad with --moe-experts")
    if cp > 1:
        if not (is_bert or is_gpt):
            raise SystemExit("--context-parallel is wired for the BERT/GPT "
                             "archs (transformer_xl's long-context story "
                             "is its segment recurrence)")
        if args.zero and tp > 1:
            raise SystemExit("--zero --context-parallel --tensor-parallel "
                             "(the ZeRO x CP x TP triple) is not wired "
                             "yet; drop one")
        # (--zero + --pipeline-parallel is rejected by the pp block below)
        # --zero + --context-parallel composes (round 5): the flat
        # (mu, nu) buffers shard over 'data' inside the CP shard_map
        # (workloads._cp_state_spec); params stay replicated over both
        # axes, so the sharded update is context-invariant.
        # CP x PP composes (round 5): the KV ring rides inside the
        # schedule's stage cells on a third manual axis — and the
        # CP x PP x TP TRIPLE composes too (manual pipe/data/context,
        # automatic 'model', branch-free cells; parity-tested).  All
        # three --cp-mode layouts ride the schedules (zigzag is gpt-only
        # per the check below; the factory's zigzag_shard pre-pass +
        # schedule-embed position ids handle the reorder).
        if args.sequence_parallel:
            raise SystemExit("--sequence-parallel shards activations along "
                             "the sequence dim --context-parallel already "
                             "owns; CP composes with plain "
                             "--tensor-parallel")
        if args.fused_attention:
            raise SystemExit("--context-parallel composes the flash kernel "
                             "inside its KV ring already; drop "
                             "--fused-attention")
        if amp.module_dtypes(policy).softmax != jnp.float32:
            raise SystemExit("--context-parallel computes fp32 softmax in "
                             "its KV ring; O3's half-softmax contract does "
                             "not compose (opt levels O0-O2 only)")
        if args.seq_len % cp:
            raise SystemExit(f"--seq-len {args.seq_len} not divisible by "
                             f"--context-parallel {cp}")
        if args.cp_mode == "zigzag":
            if not is_gpt:
                raise SystemExit("--cp-mode zigzag balances the CAUSAL "
                                 "mask's ring work (gpt archs); BERT "
                                 "attention is bidirectional — every "
                                 "device already does uniform work on the "
                                 "plain ring")
            if args.seq_len % (2 * cp):
                raise SystemExit(f"--cp-mode zigzag needs --seq-len "
                                 f"({args.seq_len}) divisible by 2x"
                                 f"--context-parallel ({2 * cp})")
        if args.cp_mode == "ulysses":
            arch_heads = {"bert_base": 12, "bert_tiny": 4,
                          "gpt_base": 12, "gpt_tiny": 4}[args.arch]
            if arch_heads % (cp * tp):
                raise SystemExit(
                    f"--cp-mode ulysses splits the {arch_heads} attention "
                    f"heads over --context-parallel {cp}"
                    + (f" x --tensor-parallel {tp}" if tp > 1 else "")
                    + " — not divisible")
    elif args.cp_mode != "ring":
        raise SystemExit(f"--cp-mode {args.cp_mode} only applies with "
                         "--context-parallel > 1")
    if pp > 1:
        if not (is_bert or is_gpt):
            raise SystemExit("--pipeline-parallel is wired for the "
                             "BERT/GPT archs (transformer_xl's recurrence "
                             "carry spans all layers every segment)")
        # --zero composes with --pipeline-parallel (round 5): the stage-
        # local flat optimizer buffers shard over 'data' WITHIN the pipe
        # sharding — PipelineZeroAdam, wired in the pp branch below.
        if args.zero and (tp > 1 or cp > 1 or args.moe_experts):
            raise SystemExit("--zero --pipeline-parallel composes "
                             "pairwise only (no ZeRO x PP x TP/CP/MoE "
                             "triple yet)")
        if args.larc:
            raise SystemExit("--larc does not compose with "
                             "--pipeline-parallel (the LARC wrapper computes "
                             "per-leaf trust ratios, which collapse on "
                             "stacked per-layer params; --opt lamb has a "
                             "PP form that keeps per-layer ratios)")
        if args.opt == "novograd":
            raise SystemExit("--opt novograd does not compose with "
                             "--pipeline-parallel (its per-tensor second "
                             "moment collapses on stacked per-layer params)")
        # --tensor-parallel composes with ALL THREE schedules (round 5):
        # the 1F1B/interleaved cells run branch-free under TP
        # (schedules.pipeline_1f1b uniform_collectives — one collective
        # order on every device; the cond form deadlocks).
        if args.virtual_stages is not None \
                and args.pipeline_schedule != "interleaved":
            raise SystemExit("--virtual-stages only applies to "
                             "--pipeline-schedule interleaved")
        if args.pipeline_schedule == "interleaved":
            if args.virtual_stages is not None and args.virtual_stages < 2:
                raise SystemExit("--pipeline-schedule interleaved needs "
                                 "--virtual-stages >= 2")
            if args.microbatches % pp:
                raise SystemExit(f"--pipeline-schedule interleaved needs "
                                 f"--microbatches ({args.microbatches}) "
                                 f"divisible by --pipeline-parallel ({pp})")
        if args.grad_accum != 1:
            raise SystemExit("--pipeline-parallel owns microbatching "
                             "(--microbatches); drop --grad-accum")
    if args.zero:
        if not (is_bert or is_gpt):
            raise SystemExit("--zero is wired for the image and BERT/GPT "
                             "workloads (transformer_xl's step owns its "
                             "own grad-clip path)")
        # tp > 1 composes: ZeRO-1 under GSPMD shards optimizer state over
        # 'data' while params keep their 'model'-axis TP specs (both are
        # partitioner-visible mesh axes — engine.gspmd_state_shardings).
    if tp > 1:
        # (pure TP and the TP×PP composition alike)
        if args.sequence_parallel and not (is_bert or is_gpt):
            raise SystemExit("--sequence-parallel is wired for the BERT/GPT "
                             "archs (transformer_xl's recurrence carry is "
                             "batch-sharded, not sequence-sharded)")
        if args.fused_attention:
            raise SystemExit("--tensor-parallel runs the SPMD-partitionable "
                             "einsum attention; drop --fused-attention")
    if tp > 1 or pp > 1 or cp > 1:
        # One shared shard-arithmetic check for every model-parallel
        # composition: the data axis absorbs what pp*tp*cp leaves over
        # (mesh.initialize_model_parallel's contract).
        devices = pick_devices(args)
        denom = pp * tp * cp
        if len(devices) % denom:
            raise SystemExit(f"pp {pp} x tp {tp} x cp {cp} = {denom} does "
                             f"not divide {len(devices)} devices")
        data = max(1, len(devices) // denom)
        if args.batch_size % data:
            raise SystemExit(f"--batch-size {args.batch_size} not divisible "
                             f"by the data-axis size {data}")
        if pp > 1 and (args.batch_size // data) % args.microbatches:
            raise SystemExit(f"per-shard batch {args.batch_size // data} "
                             f"not divisible by --microbatches "
                             f"{args.microbatches}")
        if cp > 1 and (args.batch_size // data) % args.grad_accum:
            raise SystemExit(f"per-shard batch {args.batch_size // data} "
                             f"not divisible by --grad-accum "
                             f"{args.grad_accum}")
        n_dev = len(devices)
    else:
        devices = select_devices(args)
        n_dev = len(devices)
    from apex_example_tpu.models.gpt import gpt_base, gpt_tiny
    builder = {"bert_base": bert_base, "bert_tiny": bert_tiny,
               "gpt_base": gpt_base, "gpt_tiny": gpt_tiny,
               "transformer_xl": transformer_xl_base,
               "transformer_xl_tiny": transformer_xl_tiny}[args.arch]
    md = amp.module_dtypes(policy)
    mkw = dict(dtype=md.compute, param_dtype=md.param, ln_dtype=md.ln_io,
               softmax_dtype=md.softmax)
    if args.arch in ("bert_base", "gpt_base", "transformer_xl"):
        mkw["vocab_size"] = args.vocab_size
    if is_bert or is_gpt:
        # (transformer_xl is rejected in main(): its relative-position
        # logits are q·r terms, not an additive bias — blockwise attention
        # for it needs the rel-shift inside the kernel; its long-context
        # story is the segment recurrence itself, SURVEY.md §6.)
        # flag set => force the kernel; absent => the measured-crossover
        # "auto" default (kernel at seq >= 2048; models/bert.py)
        mkw["fused_attention"] = args.fused_attention or "auto"
        # Long sequences need a position table that covers them — the
        # nn.Embed gather otherwise silently CLAMPS out-of-range position
        # ids to the last row (no error, garbage embeddings).
        arch_maxpos = {"bert_base": 512, "bert_tiny": 128,
                       "gpt_base": 1024, "gpt_tiny": 128}[args.arch]
        if args.seq_len > arch_maxpos:
            mkw["max_position"] = args.seq_len
        if tp > 1:
            mkw["tensor_parallel"] = True
            mkw["sequence_parallel"] = args.sequence_parallel
        if args.moe_experts:
            from apex_example_tpu.parallel.mesh import DATA_AXIS
            mkw["moe_experts"] = args.moe_experts
            mkw["moe_capacity_factor"] = args.moe_capacity_factor
            mkw["moe_top_k"] = args.moe_top_k
            # bind the MoE collectives to the axis the EP step maps over
            mkw["moe_axis_name"] = DATA_AXIS
    elif tp > 1:
        mkw["tensor_parallel"] = True
    model = builder(**mkw)
    # Under TP/CP/PP the data axis only gets n_dev/(tp*cp*pp) devices —
    # that is the axis ZeRO shards over, so it is the size the >=2 check
    # applies to (and DistributedFusedAdam's static world).
    optimizer = build_zero_optimizer(args, n_dev // (tp * cp * pp),
                                     gspmd=tp > 1,
                                     global_mean_grads=cp > 1 or pp > 1) \
        if args.zero else build_optimizer(args)

    V = model.vocab_size
    if is_bert:
        def batch_fn(i):
            ids, labels, w = mlm_batch(
                jnp.asarray(i, jnp.int32), batch_size=args.batch_size,
                seq_len=args.seq_len, vocab_size=V, mask_token_id=V - 1,
                seed=args.seed)
            return ids, (labels, w)
    else:
        def batch_fn(i):
            toks = lm_batch(jnp.asarray(i, jnp.int32),
                            batch_size=args.batch_size,
                            seq_len=args.seq_len, vocab_size=V,
                            seed=args.seed)
            return toks[:, :-1], toks[:, 1:]

    # Index-driven generators serve the held-out eval range directly; the
    # host-pipeline block below swaps in a one-shot-stream form.
    eval_batch_fn = batch_fn

    sample = batch_fn(0)[0]
    if pp > 1:
        # Pipeline parallelism: encoder layers stacked and sharded over the
        # 'pipe' mesh axis, driven by the SPMD ring schedule
        # (transformer/bert_pipeline.py); remaining devices data-parallel.
        # With --tensor-parallel the layer leaves ALSO shard over 'model'
        # and the shard_map stays manual over (pipe, data) only, so the
        # GSPMD TP layers run inside each ring tick (the reference's
        # parallel_state exists precisely to run TP+PP+DP jointly,
        # SURVEY.md:149-151).
        from apex_example_tpu.engine import TrainState
        from apex_example_tpu.ops import _config as ops_config
        from apex_example_tpu.transformer import parallel_state
        from apex_example_tpu.transformer.bert_pipeline import (
            PipelineFusedLAMB, bert_pp_state_shardings,
            make_bert_pp_train_step, pack_params, pack_params_1f1b)
        pp_sched = args.pipeline_schedule
        pp_chunks = (args.virtual_stages or 2) \
            if pp_sched == "interleaved" else 1
        if args.opt == "lamb":
            # C4's optimizer rides the pipeline with per-LAYER trust ratios
            # and a pipe-global clip norm (bare FusedLAMB would collapse
            # both on the stacked per-stage params).  The 1F1B arranged
            # pack carries 3 leading per-layer index dims ([S, V, per]).
            optimizer = PipelineFusedLAMB(
                optimizer, stacked_dims=1 if pp_sched == "ring" else 3)
        if args.zero:
            # ZeRO x PP: stage-local flat (m, v) buffers sharded over
            # 'data' within the pipe sharding.
            from apex_example_tpu.transformer.bert_pipeline import (
                PipelineZeroAdam)
            optimizer = PipelineZeroAdam(optimizer, stages=pp)
        if tp > 1:
            # Pallas custom calls are opaque to the SPMD partitioner; the
            # model axis stays automatic inside the PP shard_map, so pin
            # the XLA reference ops (restored by lm_main's outer finally).
            ops_config.set_force_xla(True)
        mesh = parallel_state.initialize_model_parallel(
            tensor_parallel=tp, pipeline_parallel=pp, context_parallel=cp,
            devices=devices)
        # CP x PP: the schedule's stage cells run the KV ring on the
        # 'context' axis; the step's model twin carries the CP flags
        # (init uses the dense twin — identical param tree).
        model_pp = builder(**mkw, context_parallel=True,
                           cp_mode=args.cp_mode) if cp > 1 else model
        if model.num_layers % (pp * pp_chunks):
            raise SystemExit(f"--pipeline-parallel {pp} x --virtual-stages "
                             f"{pp_chunks} does not divide "
                             f"{model.num_layers} encoder layers")
        # jit the init: under a traced program the TP layers' batch-axis
        # constraints tolerate the size-1 init sample (GSPMD pads); the
        # eager path would reject 1 % data != 0.
        dense_state = jax.jit(
            lambda r: create_train_state(r, model, optimizer, sample[:1],
                                         policy, scaler)
        )(jax.random.PRNGKey(args.seed))
        if pp_sched == "ring":
            packed = pack_params(dense_state.params, model.num_layers)
        else:
            packed = pack_params_1f1b(dense_state.params, model.num_layers,
                                      pp, pp_chunks)
        state = TrainState(step=dense_state.step, params=packed,
                           batch_stats={},
                           opt_state=optimizer.init(packed),
                           scaler=dense_state.scaler)
        state = jax.device_put(
            state, bert_pp_state_shardings(mesh, state, optimizer,
                                           model=model))
        step_fn = make_bert_pp_train_step(mesh, model_pp, optimizer, policy,
                                          microbatches=args.microbatches,
                                          schedule=pp_sched,
                                          num_chunks=pp_chunks,
                                          moe_aux_weight=args.moe_aux_weight)
        mems = None
        rank_print(f"PP over {pp} stages ({pp_sched}"
              + (f", V={pp_chunks}" if pp_chunks > 1 else "")
              + f"), TP over {tp}, CP over {cp}, DP over "
              f"{n_dev // (pp * tp * cp)}, "
              f"{args.microbatches} microbatches/shard: {mesh}")
    elif tp > 1 and cp == 1 and not args.moe_experts:
        # GSPMD tensor parallelism: one (pipe, data, context, model) mesh,
        # params carrying the TP layers' partitioning metadata, the plain
        # single-device step jitted with those shardings — collectives are
        # compiler-inserted at the layers' constraint points (engine.
        # make_gspmd_train_step).  Pallas custom calls are opaque to the
        # SPMD partitioner, so the TP path pins the XLA reference ops.
        from apex_example_tpu.engine import (create_gspmd_train_state,
                                             make_gspmd_train_step)
        from apex_example_tpu.ops import _config as ops_config
        from apex_example_tpu.transformer import parallel_state
        from apex_example_tpu.workloads import make_gspmd_txl_train_step
        # Restored by lm_main's outer finally: retracing happens inside the
        # run loop, so the flag must live for the whole run.
        ops_config.set_force_xla(True)
        mesh = parallel_state.initialize_model_parallel(
            tensor_parallel=tp, devices=devices)
        from apex_example_tpu.parallel.mesh import DATA_AXIS as _DATA
        state, shardings = create_gspmd_train_state(
            jax.random.PRNGKey(args.seed), mesh, model, optimizer,
            sample[:1], policy, scaler,
            zero_axis=_DATA if args.zero else None)
        if is_bert or is_gpt:
            step_fn = make_gspmd_train_step(
                mesh, model, optimizer, policy, shardings,
                loss_fn=mlm_loss if is_bert else lm_loss,
                compute_accuracy=False, grad_accum=args.grad_accum,
                numerics=args.numerics_check != "off")
            mems = None
        else:
            step_fn = make_gspmd_txl_train_step(
                mesh, model, optimizer, policy, shardings,
                max_grad_norm=args.max_grad_norm,
                grad_accum=args.grad_accum)
            mems = model.init_mems(args.batch_size)
        rank_print(f"TP over {tp} devices, DP over {n_dev // tp}"
              + (", ZeRO-1 opt-state over data" if args.zero else "")
              + f": {mesh}")
    elif cp > 1:
        # Ring context parallelism: init via the twin WITHOUT
        # context_parallel (identical param tree; the CP module's
        # collectives only trace inside shard_map), step from the CP twin
        # (workloads.make_bert_cp_train_step).  With --tensor-parallel the
        # shard_map stays manual over (data, context) only and the GSPMD
        # TP layers run inside the KV ring (model axis automatic; the same
        # partially-manual composition as TP×PP) — long context AND wide
        # models jointly.
        from apex_example_tpu.ops import _config as ops_config
        from apex_example_tpu.transformer import parallel_state
        from apex_example_tpu.workloads import (make_bert_cp_train_step,
                                                make_gpt_cp_train_step)
        if tp > 1:
            ops_config.set_force_xla(True)
        mesh = parallel_state.initialize_model_parallel(
            tensor_parallel=tp, context_parallel=cp, devices=devices)
        model_cp = builder(**mkw, context_parallel=True,
                           cp_mode=args.cp_mode)
        cp_shardings = None
        if args.moe_experts:
            # EP x CP (the long-context MoE stack): experts over 'data',
            # KV ring over 'context' — two manual axes, two independent
            # collectives in one step (workloads.make_bert_moe_train_step
            # context_parallel=True).  Init runs the dense twin (full
            # [E, ...] stacks); device_put shards experts one-per-
            # data-device, everything else replicated over both axes.
            from apex_example_tpu.workloads import (
                bert_moe_state_shardings, make_bert_moe_train_step)
            ep = n_dev // (cp * tp)
            if args.moe_experts % ep:
                raise SystemExit(f"--moe-experts {args.moe_experts} must "
                                 f"be a multiple of the data-axis size "
                                 f"{ep} (= devices / cp / tp)")
            moe_shardings = None
            if tp > 1:
                # EP x CP x TP: GSPMD placement for the TP leaves, expert
                # stacks overridden to P('data') (the same overlay the
                # MoE x TP path uses).
                from apex_example_tpu.engine import create_gspmd_train_state
                state, gsh = create_gspmd_train_state(
                    jax.random.PRNGKey(args.seed), mesh, model, optimizer,
                    sample[:1], policy, scaler)
                moe_shardings = bert_moe_state_shardings(
                    mesh, state, optimizer, base_shardings=gsh)
                state = jax.device_put(state, moe_shardings)
            else:
                state = create_train_state(jax.random.PRNGKey(args.seed),
                                           model, optimizer, sample[:1],
                                           policy, scaler)
                state = jax.device_put(
                    state, bert_moe_state_shardings(mesh, state, optimizer))
            step_fn = make_bert_moe_train_step(
                mesh, model_cp, optimizer, policy, state_template=state,
                aux_weight=args.moe_aux_weight,
                grad_accum=args.grad_accum,
                objective="mlm" if is_bert else "lm",
                context_parallel=True, mode=args.cp_mode,
                state_shardings=moe_shardings)
        elif tp > 1:
            from apex_example_tpu.engine import create_gspmd_train_state
            state, cp_shardings = create_gspmd_train_state(
                jax.random.PRNGKey(args.seed), mesh, model, optimizer,
                sample[:1], policy, scaler)
        else:
            state = create_train_state(jax.random.PRNGKey(args.seed), model,
                                       optimizer, sample[:1], policy, scaler)
        if args.moe_experts:
            pass                                   # step_fn built above
        elif is_gpt:
            step_fn = make_gpt_cp_train_step(mesh, model_cp, optimizer,
                                             policy,
                                             grad_accum=args.grad_accum,
                                             state_shardings=cp_shardings,
                                             mode=args.cp_mode)
        else:
            step_fn = make_bert_cp_train_step(mesh, model_cp, optimizer,
                                              policy,
                                              grad_accum=args.grad_accum,
                                              state_shardings=cp_shardings)
        mems = None
        rank_print(f"CP over {cp} sequence shards (local seq "
              f"{args.seq_len // cp}), TP over {tp}, DP over "
              f"{n_dev // (cp * tp)}"
              + (f", MoE over {args.moe_experts} experts"
                 if args.moe_experts else "")
              + f": {mesh}")
    elif args.moe_experts:
        # Expert parallelism: one switch expert per device over the 'data'
        # axis (workloads.make_bert_moe_train_step).  Init runs the dense-
        # reference MoE path (no mesh axis bound), yielding the full
        # [E, ...] stacks; device_put shards them one-expert-per-device.
        # With --tensor-parallel the shard_map goes manual over 'data'
        # only: the GSPMD TP attention/embeddings/head run on the
        # automatic 'model' axis around the expert block (the same
        # partially-manual composition as CP x TP).
        from apex_example_tpu.workloads import (bert_moe_state_shardings,
                                                make_bert_moe_train_step)
        ep = n_dev // tp
        if args.moe_experts % ep:
            raise SystemExit(f"--moe-experts {args.moe_experts} must be a "
                             f"multiple of the data-axis size {ep} "
                             f"(each device owns moe_experts/{ep} experts)")
        if args.batch_size % ep:
            raise SystemExit(f"--batch-size {args.batch_size} not "
                             f"divisible by the data-axis size {ep}")
        if (args.batch_size // ep) % args.grad_accum:
            raise SystemExit(f"per-shard batch {args.batch_size // ep} "
                             f"not divisible by --grad-accum "
                             f"{args.grad_accum}")
        if tp > 1:
            from apex_example_tpu.engine import create_gspmd_train_state
            from apex_example_tpu.ops import _config as ops_config
            from apex_example_tpu.transformer import parallel_state
            ops_config.set_force_xla(True)
            mesh = parallel_state.initialize_model_parallel(
                tensor_parallel=tp, devices=devices)
            state, gsh = create_gspmd_train_state(
                jax.random.PRNGKey(args.seed), mesh, model, optimizer,
                sample[:1], policy, scaler)
            shardings = bert_moe_state_shardings(mesh, state, optimizer,
                                                 base_shardings=gsh)
            state = jax.device_put(state, shardings)
        else:
            mesh = make_data_mesh(devices=devices)
            shardings = None
            state = create_train_state(jax.random.PRNGKey(args.seed),
                                       model, optimizer, sample[:1],
                                       policy, scaler)
            state = jax.device_put(
                state, bert_moe_state_shardings(mesh, state, optimizer))
        step_fn = make_bert_moe_train_step(
            mesh, model, optimizer, policy, state_template=state,
            aux_weight=args.moe_aux_weight, grad_accum=args.grad_accum,
            objective="mlm" if is_bert else "lm",
            state_shardings=shardings)
        mems = None
        rank_print(f"MoE over {args.moe_experts} experts "
              f"({args.moe_experts // ep}/device, capacity factor "
              f"{args.moe_capacity_factor}), TP over {tp}, DP over {ep}: "
              f"{mesh}")
    else:
        state = create_train_state(
            jax.random.PRNGKey(args.seed), model, optimizer, sample[:1],
            policy, scaler,
            train_kwargs={} if not (is_bert or is_gpt) else None)
        mems = None if (is_bert or is_gpt) \
            else model.init_mems(args.batch_size)

    if tp > 1 or pp > 1 or cp > 1 or args.moe_experts:
        pass                                   # step_fn built above
    elif is_bert or is_gpt:
        loss_fn = mlm_loss if is_bert else lm_loss
        if args.zero:
            mesh = make_data_mesh(devices=devices)
            step_fn = make_zero_train_step(mesh, model, optimizer, policy,
                                           loss_fn=loss_fn,
                                           compute_accuracy=False)
            rank_print(f"ZeRO-1 DDP over {n_dev} devices: {mesh}")
        elif n_dev > 1:
            mesh = make_data_mesh(devices=devices)
            step_fn = make_sharded_train_step(
                mesh, model, optimizer, policy, loss_fn=loss_fn,
                compute_accuracy=False, grad_accum=args.grad_accum,
                numerics=args.numerics_check != "off")
        else:
            step_fn = jax.jit(make_train_step(
                model, optimizer, policy, loss_fn=loss_fn,
                compute_accuracy=False, grad_accum=args.grad_accum,
                numerics=args.numerics_check != "off"),
                donate_argnums=(0,))
    else:
        # grad accumulation slices the BATCH axis (independent streams), so
        # each stream's recurrence carry stays exact — see
        # workloads.make_txl_train_step.
        if n_dev > 1:
            mesh = make_data_mesh(devices=devices)
            step_fn = make_sharded_txl_train_step(
                mesh, model, optimizer, policy,
                max_grad_norm=args.max_grad_norm,
                grad_accum=args.grad_accum)
        else:
            step_fn = jax.jit(make_txl_train_step(
                model, optimizer, policy, max_grad_norm=args.max_grad_norm,
                grad_accum=args.grad_accum),
                donate_argnums=(0, 1))

    eval_fn = None
    if args.eval:
        from apex_example_tpu.workloads import (make_bert_eval_step,
                                                make_gpt_eval_step,
                                                make_txl_eval_step)
        if is_bert or is_gpt:
            if pp > 1:
                # PP (and CP x PP) eval: unpack the packed/stacked params
                # into the dense layout and run the dense eval step — the
                # trees are content-identical by construction.  (Under
                # CP x PP this evaluates the full sequence densely; the
                # schedule's own KV ring is a training program.)
                from apex_example_tpu.transformer.bert_pipeline import (
                    unpack_params, unpack_params_1f1b)
                core = make_gpt_eval_step(model) if is_gpt \
                    else make_bert_eval_step(model)
                if pp_sched == "ring":
                    unp = lambda p: unpack_params(p, model.num_layers)
                else:
                    unp = lambda p: unpack_params_1f1b(
                        p, model.num_layers, pp, pp_chunks)
                eval_fn = jax.jit(lambda p, b: core(unp(p), b))
            elif cp > 1 and args.moe_experts:
                # EP x CP eval: same KV ring + per-column expert dispatch
                # as training.
                from apex_example_tpu.workloads import (
                    make_bert_moe_eval_step)
                eval_fn = make_bert_moe_eval_step(
                    mesh, model_cp, state.params,
                    objective="mlm" if is_bert else "lm",
                    context_parallel=True, mode=args.cp_mode)
            elif cp > 1:
                # Sequence-sharded eval under the same KV ring as training
                # — held-out loss AT the training context length (a dense
                # eval forward would materialize the (L, L) scores CP
                # exists to shard).
                from apex_example_tpu.workloads import (
                    make_bert_cp_eval_step, make_gpt_cp_eval_step)
                eval_fn = make_gpt_cp_eval_step(
                    mesh, model_cp, mode=args.cp_mode) if is_gpt \
                    else make_bert_cp_eval_step(mesh, model_cp)
            elif args.moe_experts:
                # Same mesh + all_to_all dispatch as training: a dense
                # eval would need the expert stacks gathered onto one
                # device and would route with a different (global)
                # capacity.
                from apex_example_tpu.workloads import make_bert_moe_eval_step
                eval_fn = make_bert_moe_eval_step(
                    mesh, model, state.params,
                    objective="mlm" if is_bert else "lm")
            else:
                eval_fn = jax.jit((make_gpt_eval_step if is_gpt
                                   else make_bert_eval_step)(model))
        else:
            eval_fn = jax.jit(make_txl_eval_step(model))

    mgr = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir \
        else None
    writer = make_writer(args)
    tb = TensorBoardAdapter(writer)
    emitter, profwin, recorder, watchdog = make_telemetry(args)
    if getattr(args, "tick_profile", False):
        # The LM builders end in jitted callables with workload-specific
        # shapes (DDP shard_map, GSPMD TP, PP microbatching); the
        # decomposition is wired into the image loop only.
        rank_print("WARNING: --tick-profile instruments the image loop "
                   "only; LM steps are not decomposed")
    preempt, fault = make_resilience(args, recorder)
    # --cost-model hookup: see the image loop.  One call site covers
    # every LM step builder above (single-device, DDP shard_map, GSPMD
    # TP/ZeRO, CP, MoE, PP, TXL) — they all end in a jitted callable.
    step_fn = obs.costmodel.instrument("train_step", step_fn)
    eval_fn = obs.costmodel.instrument("eval_step", eval_fn)
    start_epoch = start_i = 0
    if args.resume:
        # TXL mems are transient per-segment activations and restart cold on
        # resume (matches the reference harness, which does not persist
        # them); the host-state sidecar carries the loop position + host
        # PRNG, and the index-driven token streams continue at
        # batch_fn(global_step) — so BERT/GPT resume is exact mid-epoch.
        rmgr = CheckpointManager(args.resume)
        if tp == 1 and pp == 1 and not args.moe_experts and n_dev > 1:
            # (tp/pp > 1 and MoE templates are already mesh-placed above;
            # DP and CP templates are not — CP state is replicated, so the
            # replicated template is the right restore target for it too.)
            state = restore_under_mesh(
                rmgr, state, mesh, optimizer if args.zero else None)
        else:
            state = rmgr.restore(state)
        start_epoch, start_i = restore_loop_position(args, rmgr,
                                                     int(state.step))
        rank_print(f"resumed from step {int(state.step)} (epoch {start_epoch})")

    if args.prof:
        jax.profiler.start_trace("/tmp/apex_tpu_trace")

    global_step = int(state.step)
    prefetcher = None
    if args.host_pipeline:
        # Native C++ token stream (the image path's LM counterpart):
        # created AFTER resume so start_index continues the exact stream.
        from apex_example_tpu import host_runtime
        if not host_runtime.available():
            raise SystemExit("--host-pipeline: native runtime not buildable")
        prefetcher = host_runtime.NativeLMPrefetcher(
            batch=args.batch_size, seq_len=args.seq_len, vocab_size=V,
            mlm=is_bert, mask_token_id=V - 1 if is_bert else -1,
            seed=args.seed, start_index=global_step)

        if is_bert:
            def batch_fn(i):
                ids, labels, w = next(prefetcher)
                return jnp.asarray(ids), (jnp.asarray(labels),
                                          jnp.asarray(w))
        else:
            def batch_fn(i):
                ids, labels, _ = next(prefetcher)
                return jnp.asarray(ids), jnp.asarray(labels)

        def eval_batch_fn(i):
            # One-shot stream at the held-out index (deterministic in i
            # alone, like the image path's eval prefetcher).
            pf = host_runtime.NativeLMPrefetcher(
                batch=args.batch_size, seq_len=args.seq_len, vocab_size=V,
                mlm=is_bert, mask_token_id=V - 1 if is_bert else -1,
                seed=args.seed, start_index=i)
            try:
                ids, labels, w = next(pf)
            finally:
                pf.close()
            if is_bert:
                return jnp.asarray(ids), (jnp.asarray(labels),
                                          jnp.asarray(w))
            return jnp.asarray(ids), jnp.asarray(labels)
    run_step = 0
    last_saved = None
    try:
        for epoch in range(start_epoch, args.epochs):
            losses = AverageMeter("loss")
            thr = Throughput(warmup_steps=2)
            # Mid-epoch resume: see the image loop.
            for i in range(start_i if epoch == start_epoch else 0,
                           args.steps_per_epoch):
                run_step += 1
                if profwin is not None:
                    profwin.on_step_start(run_step)
                with span("data"):
                    batch = batch_fn(global_step)
                if fault is not None:
                    batch = fault.maybe_poison(global_step + 1, batch)
                t0 = time.perf_counter()
                with span("step"):
                    if is_bert or is_gpt:
                        state, metrics = step_fn(state, batch)
                    else:
                        state, mems, metrics = step_fn(state, mems, batch)
                    global_step += 1
                    if emitter is not None:
                        # Inside the span: see the image loop.
                        emitter.on_step(
                            global_step=global_step, epoch=epoch,
                            metrics=metrics,
                            items=args.batch_size * args.seq_len,
                            t_start=t0)
                thr.step(args.batch_size * args.seq_len)
                if profwin is not None:
                    profwin.on_step_end(run_step, blocker=metrics)
                if (i + 1) % args.print_freq == 0 \
                        or i + 1 == args.steps_per_epoch:
                    losses.update(float(metrics["loss"]))
                    extra = (f"ppl {float(metrics['ppl']):.1f} " if "ppl" in
                             metrics else "")
                    rank_print(f"epoch {epoch} step {i + 1}/"
                          f"{args.steps_per_epoch} "
                          f"{losses} {extra}{thr.rate:.0f} tok/s "
                          f"scale {float(metrics['scale']):.0f}")
                    tb.scalars({"train/loss": losses.val,
                                "train/tok_per_sec": thr.rate},
                               global_step)
                if args.save_every_steps and mgr is not None \
                        and is_main_process() \
                        and global_step % args.save_every_steps == 0:
                    with span("checkpoint"):
                        mgr.save(state, wait=not args.async_checkpoint,
                                 host_state=host_loop_state(args,
                                                            global_step))
                    last_saved = global_step
                    rank_print(f"saved checkpoint at step {global_step}")
                if fault is not None:
                    # See the image loop: after telemetry + interval save.
                    fault.maybe_fire(global_step)
                if preempt is not None and preempt.preempted:
                    break
            if preempt is not None and preempt.preempted:
                break
            if eval_fn is not None:
                # Held-out token streams at a disjoint index range (the
                # image path's contract); TXL threads fresh eval mems.
                # TXL ppl = exp(mean loss) over all eval batches (the
                # corpus-level metric; a mean of per-batch exps would be
                # Jensen-biased toward outlier batches).
                import math
                el = AverageMeter("loss")
                e2 = AverageMeter("masked_acc")
                emems = None if (is_bert or is_gpt) \
                    else model.init_mems(args.batch_size)
                for j in range(args.eval_batches):
                    b = eval_batch_fn(
                        10_000_000 + epoch * args.eval_batches + j)
                    if is_bert:
                        em = eval_fn(state.params, b)
                        e2.update(float(em["masked_acc"]))
                    elif is_gpt:
                        em = eval_fn(state.params, b)
                    else:
                        emems, em = eval_fn(state.params, emems, b)
                    el.update(float(em["loss"]))
                metric = ("masked_acc", e2.avg) if is_bert \
                    else ("ppl", math.exp(el.avg))
                rank_print(f"epoch {epoch} EVAL loss {el.avg:.4f} "
                      f"{metric[0]} {metric[1]:.2f} "
                      f"({args.eval_batches} batches)")
                tb.scalars({"eval/loss": el.avg,
                            f"eval/{metric[0]}": metric[1]}, global_step)
            if mgr is not None and is_main_process() \
                    and last_saved != int(state.step):
                with span("checkpoint"):
                    mgr.save(state, wait=not args.async_checkpoint,
                             host_state=host_loop_state(args, global_step))
                last_saved = int(state.step)
                rank_print(f"saved checkpoint at step {int(state.step)}")
            if preempt is not None and preempt.preempted:
                break                # re-poll after eval: see image loop
        if preempt is not None and preempt.preempted:
            return graceful_preempt_exit(args, mgr, state, preempt,
                                         emitter, global_step,
                                         last_saved=last_saved)
    finally:
        # Join pending async checkpoint writes even when unwinding on an
        # exception — an announced save must exist on disk (main() gives
        # its image path the same protection).
        if preempt is not None:
            preempt.close()
        close_telemetry(emitter, profwin, recorder, watchdog)
        if prefetcher is not None:
            prefetcher.close()
        tb.close()
        if mgr is not None:
            mgr.wait_until_finished()
    if args.prof:
        jax.profiler.stop_trace()
        rank_print("profile written to /tmp/apex_tpu_trace")
    return 0


if __name__ == "__main__":
    sys.exit(main())
