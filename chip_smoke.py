#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # all phases; last stdout line is JSON

Drives the main path once through the entry points a user calls —
``train.main(argv)`` and ``serve.run_serve(args)`` — at the full width and
depth of models the repo supports, random weights from a seed, and checks
what comes out by the repo's own means.  It asserts no speed.

One process per chip: this parent never imports JAX.  It re-invokes itself
once per phase (``--phase NAME``), sequentially, so each child owns the
chip alone and releases it on exit; a phase that fails or hangs is killed
and fails the run.  On success the LAST stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
the device as JAX reports it.  On any failure — no accelerator, a
directory without the repo, a failed assertion, a timeout — the exit code
is non-zero and no result line is printed.

Phases (``chip_smoke_out/`` holds each phase's log, stream and checkpoint):

  device     platform is "tpu", device_kind is in the peaks table
             (utils/flops.py), Pallas interpret mode is off; prints versions
  kernels    every Pallas op the CLI paths reach, jitted at model widths:
             compiled text holds a tpu_custom_call (Mosaic ran), and the
             result agrees with the XLA reference path on the same device
  resnet50   c2: ResNet-50 ImageNet-shaped amp-O2 SGD, batch 256
  bert_base  c4: BERT-base MLM LAMB amp-O2, batch 64 x seq 128
  gpt_base   GPT-base Adam amp-O2 with the flash kernel, batch 8 x seq
             1024, writes a checkpoint
  serve      serve.py on gpt_base restored from that checkpoint: 16
             requests through chunked prefill + paged decode
  txl        c5: Transformer-XL (clip_grad_norm kernels)
  granite_hybrid
             models/granite_hybrid.py through ServeEngine at the tiny and
             the published widths (granite-4.0-h-micro whole): a request
             completes, every Mamba layer's slot moved, the per-slot state
             leaves keep their device buffers (updated in place)
  ddp4 tp4 serve4
             with >= 4 chips: DDP+SyncBN ResNet-50, dp2 x tp2 BERT-base,
             --mesh 2,2 gpt_base serve; skipped, saying so, on fewer

Train phases assert: every loss finite, last loss below first (the synthetic
data is learnable), run_header.platform == "tpu", metrics_lint
--require-summary passes.  Serve asserts on COUNTS, not the exit code
(serve.py exits 0 with failed requests by policy): every request ok, no
request_failed record, one serve_decode_step compile (cost_report
--fail-on-recompile), the stream lints and ends in its serve_summary.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")
CKPT = os.path.join(OUT, "ck_gpt")

# The contract: exit within 1200 s on one chip, compilation included.  The
# parent kills the running child and fails at this mark, so it always
# returns (and stops what it started) before an outer limit does.  The
# >= 4-chip phases get the same allowance again.
BUDGET_S = 1150.0

# phase -> the phase whose output it reads (a failed need fails the phase)
ONE_CHIP = {"device": None, "kernels": "device", "resnet50": "device",
            "bert_base": "device", "gpt_base": "device", "serve": "gpt_base",
            "txl": "device", "granite_hybrid": "device"}
FOUR_CHIP = {"ddp4": "resnet50", "tp4": "bert_base", "serve4": "gpt_base"}

_TAIL = ["--epochs", "1", "--print-freq", "1"]
TRAIN_ARGV = {
    # lr 0.01, not the CLI's 0.1: a fresh ResNet-50 at 0.1 climbs for its
    # first eight steps (loss 7.46 -> 7.83 on the chip) before it falls; at
    # 0.01 it falls from the start (7.46 -> 7.18).
    "resnet50": ["--arch", "resnet50", "--dataset", "imagenet",
                 "--opt-level", "O2", "--opt", "sgd", "--lr", "0.01",
                 "--batch-size", "256", "--steps-per-epoch", "8",
                 "--num-devices", "1"],
    # 40 steps: LAMB at lr 1e-3 moves the MLM loss 0.004 in six steps
    # (noise) but 0.15 in forty (10.84 -> 10.68 on the chip); a step costs
    # 63 ms there, the compile 70 s.
    "bert_base": ["--arch", "bert_base", "--opt", "lamb", "--lr", "1e-3",
                  "--opt-level", "O2", "--batch-size", "64", "--seq-len",
                  "128", "--steps-per-epoch", "40", "--num-devices", "1"],
    # seq 1024, not more: train.py widens max_position above the arch's
    # 1024 and serve.py could not then restore the checkpoint.
    "gpt_base": ["--arch", "gpt_base", "--opt", "adam", "--lr", "1e-4",
                 "--opt-level", "O2", "--fused-attention", "--batch-size",
                 "8", "--seq-len", "1024", "--steps-per-epoch", "8",
                 "--num-devices", "1", "--checkpoint-dir", CKPT],
    "txl": ["--arch", "transformer_xl", "--opt", "adam", "--lr", "2.5e-4",
            "--opt-level", "O2", "--batch-size", "32", "--seq-len", "192",
            "--steps-per-epoch", "8", "--num-devices", "1"],
    "ddp4": ["--arch", "resnet50", "--dataset", "imagenet", "--opt-level",
             "O2", "--opt", "sgd", "--lr", "0.01", "--sync_bn",
             "--batch-size", "256", "--steps-per-epoch", "4",
             "--num-devices", "4"],
    "tp4": ["--arch", "bert_base", "--opt", "lamb", "--lr", "1e-3",
            "--opt-level", "O2", "--batch-size", "64", "--seq-len", "128",
            "--tensor-parallel", "2", "--steps-per-epoch", "40",
            "--num-devices", "4"],
}
SERVE_REQUESTS = 16
SERVE_ARGV = ["--arch", "gpt_base", "--checkpoint-dir", CKPT, "--slots", "8",
              "--max-len", "1024", "--block-size", "16", "--requests",
              str(SERVE_REQUESTS), "--prompt-len", "64:512", "--max-new",
              "32:128", "--cost-model"]

# Same seed, same global batch, same initial weights: the first step's loss
# on four chips must match one chip's up to bf16 rounding and reduction
# order.  Relative bound; the measured difference is printed (on a v5e:
# 1.3e-4 for DDP+SyncBN ResNet-50, 1.6e-5 for dp2 x tp2 BERT-base).
MULTICHIP_LOSS_RTOL = 1e-3
# "Nothing may sit on device 0 alone": every device's peak after a
# multi-chip phase must exceed this (the smallest model's weights alone do).
MIN_PEAK_BYTES = 64 << 20


def _stream(name: str) -> str:
    return os.path.join(OUT, f"{name}.jsonl")


def _fail(msg: str):
    raise SystemExit(f"chip_smoke FAIL: {msg}")


# ------------------------------------------------------------------ children

def _require_tpu():
    """The device as JAX reports it, or exit naming why it is not a TPU
    (JAX falls back to the CPU behind one log line when libtpu finds no
    chip; every entry point then 'runs' — this is where that stops)."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"JAX found no accelerator: jax.devices()[0].platform is "
              f"{dev.platform!r}, need 'tpu'")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def phase_device():
    from importlib import metadata

    import jax
    import jaxlib

    device = _require_tpu()
    from apex_example_tpu.ops import _config as ops_config
    from apex_example_tpu.utils.flops import device_peaks
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu}")
    print(f"device_kind {device['kind']!r}  count {device['count']}")
    try:
        peaks = device_peaks(device["kind"])
    except KeyError as e:
        _fail(e.args[0])
    print(f"peaks: {peaks}")
    if ops_config.INTERPRET is not False:
        _fail("ops._config.INTERPRET is on: kernels would not reach Mosaic")
    with open(os.path.join(OUT, "device.json"), "w") as fh:
        json.dump(device, fh)


def _kernel_cases():
    """[(name, fn, args)] — each Pallas op the CLI paths reach, at the
    widths the smoke's models use (hidden 768, vocab 30522, heads 12 x 64)."""
    import jax
    import jax.numpy as jnp

    from apex_example_tpu import ops
    from apex_example_tpu.ops import attention, grouped_matmul, ssd
    from apex_example_tpu.ops.fused_optim import adagrad_update_leaf

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 256))

    def rnd(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def with_vjp(op):
        """fwd outputs + every cotangent, from one traced program."""
        def f(dy, *a):
            y, vjp = jax.vjp(op, *a)
            return (y,) + vjp(dy)
        return f

    cases = []
    # LayerNorm / RMSNorm: BERT's [64*128, 768] rows in both io dtypes,
    # and the serve step's [8 slots, 16 lanes, 768].
    for dt in (jnp.bfloat16, jnp.float32):
        x, g, b = rnd((8192, 768), dt), rnd((768,)), rnd((768,))
        tag = jnp.dtype(dt).name
        cases.append((f"layer_norm fwd+bwd [8192,768] {tag}",
                      with_vjp(lambda x, g, b: ops.layer_norm(x, g, b, 1e-12)),
                      (rnd((8192, 768), dt), x, g, b)))
        cases.append((f"rms_norm fwd+bwd [8192,768] {tag}",
                      with_vjp(lambda x, g: ops.rms_norm(x, g, 1e-6)),
                      (rnd((8192, 768), dt), x, g)))
    cases.append(("layer_norm fwd [8,16,768] float32 (serve step)",
                  lambda x, g, b: ops.layer_norm(x, g, b, 1e-12),
                  (rnd((8, 16, 768)), rnd((768,)), rnd((768,)))))

    # Flash attention, bf16 as amp-O2 feeds it: the gpt_base phase's shape,
    # BERT's key-padding-bias form, and the seq-2048 auto-crossover shape.
    for (b_, s, causal, biased) in ((8, 1024, True, False),
                                    (16, 512, False, True),
                                    (4, 2048, True, False)):
        q, k, v, do = (rnd((b_, s, 12, 64), jnp.bfloat16) for _ in range(4))
        bias = jnp.where(rnd((b_, s)) > 1.0, -1e9, 0.0) if biased else None
        cases.append((
            f"flash_attention fwd+bwd B{b_} S{s} H12 D64 bf16"
            f"{' causal' if causal else ''}{' bias' if biased else ''}",
            with_vjp(lambda q, k, v, bias=bias, causal=causal:
                     ops.flash_attention(q, k, v, bias=bias, causal=causal)),
            (do, q, k, v)))

    # Paged latent attention as serve.py --arch xing4_29b_a4b_cut reaches
    # it: 32 heads x 16 lanes against a 640-wide bf16 arena in blocks of
    # 16 whose first 512 columns are the values; slots in prefill, in
    # decode (block edges on and off), part-way and dead; the slots' blocks
    # in shuffled places, -1 behind them.  (What each form read, the second
    # output, differs by design and is not compared.)
    S, NB, BS, MB = 8, 512, 16, 64
    fill = jnp.asarray([0, 333, 512, 1008, 15, 16, 700, 90], jnp.int32)
    n_new = jnp.asarray([16, 1, 0, 16, 1, 1, 5, 16], jnp.int32)
    blocks = jnp.where(n_new > 0, -(-(fill + n_new) // BS), 0)
    first = jnp.cumsum(blocks) - blocks
    place = jax.random.permutation(next(keys), NB)
    col = jnp.arange(MB)[None, :]
    table = jnp.where(col < blocks[:, None],
                      place[(first[:, None] + col) % NB], -1).astype(jnp.int32)
    qf = (0.5 * rnd((S, 16, 32, 640))).astype(jnp.bfloat16)
    cases.append((
        "paged_latent_attention S8 C16 H32 W640 bf16, blocks of 16",
        lambda qf, arena, table, fill, n_new: attention.paged_latent_attention(
            qf, arena, table, fill, n_new, scale=0.1, kr=512)[0],
        (qf, rnd((NB, BS, 640), jnp.bfloat16), table, fill, n_new)))

    # Paged per-head attention at 64-wide heads, two a lane tile, at the
    # two older decoders' served shapes: serve.py --arch granite_4_0_h_micro
    # (64 slots, 32 query heads over 8 x 64 bfloat16, 64 columns of 16) and
    # --arch gpt_base (12 x 64 float32, 32 columns); slots in prefill, in
    # decode, part-way and dead at seeded fills, their blocks in shuffled
    # places, -1 behind them.  The float32 case is judged by the bfloat16
    # bound: the XLA form's products are one bfloat16 pass on the TPU.
    for tag, Hq, Hk, dt, cols in (("granite4h", 32, 8, jnp.bfloat16, 64),
                                  ("gpt1", 12, 12, jnp.float32, 32)):
        lanes = jnp.tile(n_new, 8)
        fills = jax.random.randint(next(keys), (64,), 0, (cols - 1) * BS)
        held = jnp.where(lanes > 0, -(-(fills + lanes) // BS), 0)
        col = jnp.arange(cols)[None, :]
        places = jax.random.permutation(next(keys), 64 * cols).reshape(
            64, cols)
        cases.append((
            f"paged_gqa_attention S64 C16 H{Hq}/{Hk} D64 "
            f"{jnp.dtype(dt).name} ({tag}), {cols} blocks of 16",
            lambda q, k, v, table, fill, n_new: attention.paged_gqa_attention(
                q, k, v, table, fill, n_new, scale=0.125)[0].astype(
                    jnp.bfloat16),
            (rnd((64, 16, Hq, 64), dt), rnd((64 * cols, BS, Hk * 64), dt),
             rnd((64 * cols, BS, Hk * 64), dt),
             jnp.where(col < held[:, None], places, -1).astype(jnp.int32),
             fills.astype(jnp.int32), lanes)))

    # The dropless experts' grouped products at the served widths (3584 x
    # 1024, bf16; 16 of the 64 experts): empty groups, groups across a row
    # tile of 128, rows past the last group.  Those rows hold anything in
    # the kernel's result and zeros in XLA's, so they are blanked here as
    # the expert layer's combine selects them away.
    sizes = jnp.asarray([5, 0, 120, 7, 0, 0, 130, 1, 9, 3, 0, 40, 2, 6, 0, 11],
                        jnp.int32)
    past = (jnp.arange(512) >= jnp.sum(sizes))[:, None]

    def experts(xs, w_gate, w_up, w_down, sizes):
        h, _ = grouped_matmul.grouped_swiglu(xs, w_gate, w_up, sizes)
        h = jnp.where(past, 0, h)
        ys, _ = grouped_matmul.grouped_matmul(h, w_down, sizes)
        return h, jnp.where(past, 0, ys)

    cases.append((
        "grouped_swiglu + grouped_matmul M512 G16 3584x1024 bf16",
        experts,
        (rnd((512, 3584), jnp.bfloat16),
         (rnd((16, 3584, 1024)) / 60).astype(jnp.bfloat16),
         (rnd((16, 3584, 1024)) / 60).astype(jnp.bfloat16),
         (rnd((16, 1024, 3584)) / 32).astype(jnp.bfloat16), sizes)))

    # The Mamba-2 chunk as serve.py --arch granite_4_0_h_micro reaches it
    # (64 heads of 64, 128 state columns, 16 lanes; 8 slots): slots in
    # prefill, decoding, part-way and dead, one at its request's start.  y
    # of a slot with no live lane is zeros from the kernel and its resting
    # state's read-out from XLA; nothing reads it, so it is blanked here.
    n_new = jnp.asarray([16, 1, 0, 9, 1, 0, 1, 16], jnp.int32)
    live = jnp.arange(16)[None, :] < n_new[:, None]

    def scan(state, x, dt, a_log, B, C, D):
        y, new = ssd.ssd_scan(state, x, dt, a_log, B, C, D, live, chunk=256,
                              reset=jnp.arange(8) == 3)
        return jnp.where(n_new[:, None, None, None] > 0, y, 0), new

    cases.append((
        "ssd_scan S8 L16 H64 P64 N128 f32",
        scan,
        (rnd((8, 64, 64, 128)), rnd((8, 16, 64, 64)),
         jax.nn.softplus(rnd((8, 16, 64)) - 2.0),
         jnp.log(jnp.linspace(1.0, 16.0, 64)), rnd((8, 16, 128)),
         rnd((8, 16, 128)), rnd((64,)))))

    # Optimizer leaves, smallest BN vector to the embedding table.
    hp = dict(beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01,
              bias_c1=10.0, bias_c2=1000.0)
    for shape in ((64,), (768,), (3072, 768), (30522, 768)):
        p, g, m = rnd(shape), rnd(shape), rnd(shape)
        v = jnp.square(rnd(shape))
        cases.append((f"adam_update_leaf {shape}",
                      lambda p, g, m, v: ops.adam_update_leaf(
                          p, g, m, v, lr=1e-3, **hp), (p, g, m, v)))
        cases.append((f"lamb_stage1_leaf {shape}",
                      lambda p, g, m, v: ops.lamb_stage1_leaf(
                          p, g, m, v, grad_scale=0.5, **hp), (p, g, m, v)))
        cases.append((f"lamb_stage2_leaf {shape}",
                      lambda p, u: ops.lamb_stage2_leaf(p, u, 0.37), (p, g)))
    for shape in ((64,), (7, 7, 3, 64), (2048, 1000)):
        p, g, buf = rnd(shape), rnd(shape), rnd(shape)
        cases.append((f"sgd_update_leaf {shape}",
                      lambda p, g, buf: ops.sgd_update_leaf(
                          p, g, buf, lr=0.1, momentum=0.9, weight_decay=1e-4),
                      (p, g, buf)))
    p, g, m = rnd((3072, 768)), rnd((3072, 768)), rnd((3072, 768))
    cases.append(("novograd_update_leaf (3072, 768)",
                  lambda p, g, m: ops.novograd_update_leaf(
                      p, g, m, inv_denom=0.5, lr_c1=1e-3, beta1=0.95,
                      weight_decay=0.01, grad_avg_coeff=0.05), (p, g, m)))
    cases.append(("adagrad_update_leaf (3072, 768)",
                  lambda p, g, h: adagrad_update_leaf(
                      p, g, h, lr=1e-2, eps=1e-10, weight_decay=0.01),
                  (p, g, jnp.square(m))))

    # Multi-tensor list ops over a mixed tree: a 64-vector, a bf16 leaf of
    # 24 lane rows (an 8-row block that is not the whole array), a matrix.
    tree = {"bn": rnd((64,)), "half": rnd((3072,), jnp.bfloat16),
            "w": rnd((3072, 768))}
    other = jax.tree_util.tree_map(lambda t: rnd(t.shape, t.dtype), tree)
    cases.append(("multi_tensor_scale {64, bf16 3072, 3072x768}",
                  lambda t: ops.multi_tensor_scale(t, 0.5), (tree,)))
    cases.append(("multi_tensor_axpby",
                  lambda x, y: ops.multi_tensor_axpby(2.0, x, -0.5, y),
                  (tree, other)))
    cases.append(("multi_tensor_l2norm per_tensor",
                  lambda t: ops.multi_tensor_l2norm(t, per_tensor=True),
                  (tree,)))
    cases.append(("clip_grad_norm",
                  lambda t: ops.clip_grad_norm(t, 0.25), (tree,)))
    return cases


def _compile_case(fn, args, *, reference: bool):
    """(compiled, its tpu_custom_call count): the kernel path, or the XLA
    reference path the same op takes under ``force_xla``."""
    import jax

    from apex_example_tpu.ops import _config as ops_config
    if reference:
        # A fresh function object: FORCE_XLA is read at trace time and is
        # not part of jit's cache key.
        with ops_config.force_xla():
            compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    else:
        compiled = jax.jit(fn).lower(*args).compile()
    # XLA's own kernels for lax.ragged_dot are Mosaic custom calls too
    # (%ragged-dot-*): the reference form of the grouped products holds
    # them, and they are not this package's
    return compiled, sum("tpu_custom_call" in line
                         and "ragged-dot" not in line
                         for line in compiled.as_text().splitlines())


def phase_kernels():
    import jax
    import jax.numpy as jnp

    _require_tpu()
    from apex_example_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    # The unit tests' bounds (tests/test_ops.py, test_attention.py), taken
    # against the reference's largest magnitude: 1e-4 for fp32 results,
    # 2e-2 for bf16.
    tol = {"float32": 1e-4, "bfloat16": 2e-2}
    failed = []
    for name, fn, args in _kernel_cases():
        t0 = time.monotonic()
        try:
            kern, n_calls = _compile_case(fn, args, reference=False)
            out = jax.block_until_ready(kern(*args))
            ref_c, ref_calls = _compile_case(fn, args, reference=True)
            ref = jax.block_until_ready(ref_c(*args))
        except Exception:       # report every refused kernel, not the first
            traceback.print_exc()
            print(f"FAIL {name}: did not compile or run", flush=True)
            failed.append(name)
            continue
        worst = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(ref), strict=True):
            bound = tol.get(jnp.dtype(a.dtype).name, 1e-4)
            # Reduced on the device: one scalar comes back per leaf.
            a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
            err = float(jnp.max(jnp.abs(a32 - b32))
                        / jnp.maximum(1.0, jnp.max(jnp.abs(b32))))
            if not math.isfinite(err):
                err = math.inf
            worst = max(worst, err / bound)
        ok = n_calls > 0 and ref_calls == 0 and worst <= 1.0
        print(f"{'ok  ' if ok else 'FAIL'} {name}: {n_calls} tpu_custom_call"
              f" (reference {ref_calls}), error {worst:.3g} of bound, "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        if not ok:
            failed.append(name)
    if failed:
        _fail(f"{len(failed)} kernel case(s): {failed}")


def _lint(path: str, *flags: str):
    from tools import metrics_lint
    if metrics_lint.main([path, *flags]) != 0:
        _fail(f"metrics_lint {' '.join(flags)} rejected {path}")


def _records(name: str):
    """The phase's stream, after checking it was written on a TPU."""
    from apex_example_tpu.obs.metrics import read_jsonl
    records = read_jsonl(_stream(name))
    header = records[0]
    if header.get("record") != "run_header" \
            or header.get("platform") != "tpu":
        _fail(f"{_stream(name)}: run_header.platform is "
              f"{header.get('platform')!r}, need 'tpu'")
    return records


def _step_losses(name: str):
    return [r["loss"] for r in _records(name) if r.get("record") == "step"]


def _check_all_devices_used():
    import jax
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    print(f"peak_bytes_in_use per device: {peaks}")
    if min(peaks) < MIN_PEAK_BYTES:
        _fail(f"a device stayed (nearly) empty: peaks {peaks}")


def phase_train(name: str):
    device = _require_tpu()
    import train

    argv = TRAIN_ARGV[name] + _TAIL + ["--metrics-jsonl", _stream(name)]
    if name == "gpt_base":
        shutil.rmtree(CKPT, ignore_errors=True)
    if name in FOUR_CHIP and device["count"] != 4:
        _fail(f"{name} needs jax.device_count() == 4, found "
              f"{device['count']}")
    print(f"train.main({argv})", flush=True)
    rc = train.main(argv)
    if rc != 0:
        _fail(f"train.main returned {rc}")

    losses = _step_losses(name)
    steps = int(argv[argv.index("--steps-per-epoch") + 1])
    print(f"losses: {losses}")
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        _fail(f"need {steps} finite losses, got {losses}")
    if not losses[-1] < losses[0]:
        _fail(f"last loss {losses[-1]} is not below the first {losses[0]}")
    _lint(_stream(name), "--require-summary")

    if name in FOUR_CHIP:
        _check_all_devices_used()
        one = _step_losses(FOUR_CHIP[name])[0]
        rel = abs(losses[0] - one) / abs(one)
        print(f"step-1 loss: {losses[0]} on 4 chips vs {one} on 1 "
              f"(relative difference {rel:.3g}, bound {MULTICHIP_LOSS_RTOL})")
        if rel > MULTICHIP_LOSS_RTOL:
            _fail("step-1 loss on four chips disagrees with one chip")


def phase_serve(name: str):
    _require_tpu()
    import serve
    from tools import cost_report

    argv = SERVE_ARGV + ["--metrics-jsonl", _stream(name)]
    if name == "serve4":
        argv += ["--mesh", "2,2"]
    print(f"serve.run_serve({argv})", flush=True)
    completions, summary, rc = serve.run_serve(
        serve.build_parser().parse_args(argv))
    print(f"rc {rc}  completed {summary['completed']}  "
          f"failed {summary['failed']}  output_tokens "
          f"{summary['output_tokens']}")

    records = _records(name)
    ok = sum(1 for c in completions if c.status == "ok")
    if ok != SERVE_REQUESTS or summary["completed"] != SERVE_REQUESTS:
        _fail(f"{ok}/{SERVE_REQUESTS} requests ok "
              f"(summary completed={summary['completed']})")
    bad = [r for r in records if r.get("record") == "request_failed"]
    if bad:
        _fail(f"{len(bad)} request_failed record(s): {bad[:2]}")
    if any(len(c.tokens) == 0 for c in completions):
        _fail("a completed request carries no tokens")
    if cost_report.main([_stream(name), "--fail-on-recompile"]) != 0:
        _fail("cost_report --fail-on-recompile: a step compiled twice")
    # A serve stream closes with serve_summary (run_summary is the
    # trainer's record), so that is the summary demanded here.
    if records[-1].get("record") != "serve_summary":
        _fail(f"{_stream(name)} does not end in a serve_summary")
    _lint(_stream(name))
    if name == "serve4":
        _check_all_devices_used()


def phase_granite_hybrid():
    """serve.py --arch granite_*'s model through ServeEngine at the tiny
    and at the published widths (granite-4.0-h-micro whole: 6.4 GB of
    bfloat16 weights, 64 slots of per-slot state): a prompt of two chunks
    and a part, then decode.  Asserted: the request completes (the engine's
    guard saw finite logits every tick), every Mamba layer's slot moved,
    and the per-slot state leaf is the same device buffer after the ticks
    (the cache is donated: updated in place, never copied)."""
    _require_tpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_example_tpu.models import granite_hybrid as gh
    from apex_example_tpu.ops import paged_cache
    from apex_example_tpu.serve import Request, ServeEngine

    def seeded(model):
        init = jax.jit(lambda k: model.init(
            k, jnp.zeros((1, 8), jnp.int32))["params"])
        return init(jax.random.PRNGKey(0))

    for name, model, slots, max_len in (
            ("granite_hybrid_tiny", gh.granite_hybrid_tiny(), 4, 64),
            ("granite_4_0_h_micro", gh.granite_4_0_h_micro(), 64, 1024)):
        t0 = time.monotonic()
        params = seeded(model)
        eng = ServeEngine(model, params, num_slots=slots, max_len=max_len,
                          block_size=16)
        states = paged_cache.slot_leaves(eng.pool.cache)
        where = [leaf.unsafe_buffer_pointer() for _, leaf in states]
        eng.submit(Request(prompt=list(range(1, 40)), max_new_tokens=6,
                           uid="a"))
        eng.queue.close()
        done = eng.run(max_steps=64)
        if [c.status for c in done] != ["ok"] or len(done[0].tokens) != 6:
            _fail(f"{name}: the request did not complete: {done}")
        after = [leaf.unsafe_buffer_pointer()
                 for _, leaf in paged_cache.slot_leaves(eng.pool.cache)]
        if after != where:
            _fail(f"{name}: a per-slot leaf moved to another buffer "
                  "(the tick copied it)")
        moved = np.stack([np.asarray(t["ssm_slots_advanced"])
                          for _, t in eng.counter_log])
        if not (moved[:, :, 0] == 1).all() or moved[:, :, 1:].any():
            _fail(f"{name}: ssm_slots_advanced is not slot 0 alone")
        state = np.asarray(
            paged_cache.slot_leaves(eng.pool.cache)[-1][1][0], np.float32)
        if not np.isfinite(state).all() or not np.abs(state).max() > 0:
            _fail(f"{name}: the slot's state is not finite and non-zero")
        stats = jax.devices()[0].memory_stats() or {}
        print(f"{name}: 6 tokens {list(done[0].tokens)} in "
              f"{eng.compute_steps} ticks, {len(states)} per-slot leaves in "
              f"place ({eng.pool.state_bytes_reserved()} bytes), peak "
              f"{stats.get('peak_bytes_in_use')} bytes, "
              f"{time.monotonic() - t0:.0f} s", flush=True)
        del eng, params


def run_phase(name: str):
    if name == "device":
        phase_device()
    elif name == "kernels":
        phase_kernels()
    elif name == "granite_hybrid":
        phase_granite_hybrid()
    elif name in TRAIN_ARGV:
        phase_train(name)
    else:
        phase_serve(name)


# -------------------------------------------------------------------- parent

def _run_child(name: str, deadline: float):
    """(ok, seconds): one phase in its own process, output to its log,
    killed at the deadline."""
    log = os.path.join(OUT, f"{name}.log")
    t0 = time.monotonic()
    with open(log, "w") as fh:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", name],
            cwd=HERE, stdout=fh, stderr=subprocess.STDOUT)
        try:
            rc = proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            if proc.poll() is None:       # timeout, SIGTERM, ctrl-C
                proc.kill()
                proc.wait()
    dt = time.monotonic() - t0
    with open(log, errors="replace") as fh:
        lines = fh.read().splitlines()
    shown = lines if rc != 0 else lines[-12:]
    print(f"----- {name}: " + ("TIMEOUT" if rc is None else f"rc {rc}")
          + f" in {dt:.0f}s ({log})")
    print("\n".join("  " + ln for ln in shown[-200:]), flush=True)
    return rc == 0, dt


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=[*ONE_CHIP, *FOUR_CHIP],
                    help="run one phase in this process (what the parent "
                         "invokes); without it, run them all")
    args = ap.parse_args()
    if args.phase:
        os.makedirs(OUT, exist_ok=True)
        run_phase(args.phase)
        return 0

    # SIGTERM unwinds like ctrl-C, so _run_child's finally stops the child.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    t0 = time.monotonic()
    deadline = t0 + BUDGET_S
    device, passed, failed, walls = None, [], [], {}
    for name, need in {**ONE_CHIP, **FOUR_CHIP}.items():
        if name in FOUR_CHIP and device["count"] < 4:
            print(f"----- {name}: SKIPPED, needs 4 chips, found "
                  f"{device['count']}")
            continue
        if name == next(iter(FOUR_CHIP)):
            deadline = time.monotonic() + BUDGET_S
        if need in failed:
            print(f"----- {name}: FAILED, needs phase {need}")
            failed.append(name)
            continue
        ok, walls[name] = _run_child(name, deadline)
        (passed if ok else failed).append(name)
        if name == "device":
            if not ok:
                break                     # nothing else can mean anything
            with open(os.path.join(OUT, "device.json")) as fh:
                device = json.load(fh)
    print(f"chip_smoke: {time.monotonic() - t0:.0f}s total; per phase "
          + json.dumps({k: round(v) for k, v in walls.items()}))
    if failed:
        print(f"chip_smoke: FAILED phases {failed} (passed: {passed})",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
