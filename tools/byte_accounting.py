#!/usr/bin/env python
"""Per-step HBM byte accounting for the C2 headline (ResNet-50 / 224 / amp-O2
bf16, batch 256): is the step at its HBM-traffic floor?

Pure arithmetic (no device needed): enumerates every conv+BN+ReLU chain in
torchvision-parity ResNet-50, prices HBM traffic under explicit touch-count
models, and compares each against phase times and a bandwidth MEASURED on
the chip (tools/perf_probe.py gives --fwd-ms/--bwd-ms/--opt-ms,
tools/bw_micro.py gives --measured-bw; all four are required — a reading
from one machine is not a default for another).

Touch models (activation bf16 = 2 B; i/o = a chain's input/output bytes):

  FORWARD floor — conv+BN(stats-in-epilogue)+ReLU as ONE fused pass:
      read x_in (i) + write act_out (o); the residual skip adds one extra
      read of each block input at the add.  The saved set for backward is
      act_out itself (already materialized — saving it is free).

  BACKWARD floor — BN/ReLU-bwd folded into the conv grads:
      dy read twice (wgrad + dx-conv are separate loop nests: 2o),
      saved act_out read once for the BN backward (o),
      saved act_in read once for wgrad (i), dx written once (i)
      => 3o + 2i per chain (+ skip-grad add traffic per block).

  BN 2-pass — the form XLA's multi-output reduce fusions actually take
      (the 52%-of-device-time bucket): the stat sums (Σdy, Σdy·x̂) run as a
      SEPARATE pass over (dy, act_out) before the dx pass => floor + 2o.

  remat='conv' (models/resnet.py remat option) — saved set pinned to conv
      outputs y_conv: fwd additionally writes y_conv (+o), backward reads
      y_conv instead of act_out (same bytes) and recomputes BN/ReLU in
      registers/VMEM.  Net: helps only if XLA's default saves MORE than one
      tensor per chain (e.g. an explicit x̂) — measurement arbitrates.

Output: Σi/Σo totals, per-model GB + implied phase ms at the measured
bandwidth vs the measured phase times, and projected img/s at --spec-bw.
Run `python tools/byte_accounting.py` (no TPU touched).
"""

from __future__ import annotations

import argparse

BF16 = 2
FP32 = 4


def resnet50_chains(batch: int, image: int = 224):
    """(name, i_bytes, o_bytes, w_params, is_block_end, is_skip) per conv."""
    raw = [("stem", image, 3, image // 2, 64, 7, False, False)]
    stages = [(56, 64, 64, 3), (28, 256, 128, 4), (14, 512, 256, 6),
              (7, 1024, 512, 3)]
    for si, (h, cin_stage, f, blocks) in enumerate(stages):
        cin = cin_stage
        for b in range(blocks):
            hin = h * 2 if (si > 0 and b == 0) else h
            pre = f"s{si}b{b}"
            raw.append((f"{pre}.conv1", hin, cin, hin, f, 1, False, False))
            raw.append((f"{pre}.conv2", hin, f, h, f, 3, False, False))
            raw.append((f"{pre}.conv3", h, f, h, 4 * f, 1, True, False))
            if b == 0:
                raw.append((f"{pre}.down", hin, cin, h, 4 * f, 1,
                            False, True))
            cin = 4 * f
    out = []
    for name, hin, cin, hout, cout, k, end, skip in raw:
        out.append(dict(
            name=name, end=end, skip=skip,
            i=batch * hin * hin * cin * BF16,
            o=batch * hout * hout * cout * BF16,
            w=k * k * cin * cout))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--fwd-ms", type=float, required=True)
    ap.add_argument("--bwd-ms", type=float, required=True)
    ap.add_argument("--opt-ms", type=float, required=True)
    ap.add_argument("--measured-bw", type=float, required=True,
                    help="GB/s the chip delivers (tools/bw_micro.py)")
    ap.add_argument("--spec-bw", type=float, default=819.0)
    args = ap.parse_args()
    gbs = args.measured_bw

    ch = resnet50_chains(args.batch)
    Si = sum(c["i"] for c in ch)
    So = sum(c["o"] for c in ch)
    # one extra read of each block input at the residual add (16 blocks),
    # one extra read of each block-output grad in backward (fan-out 2)
    skip_fwd = sum(c["i"] for c in ch if c["name"].endswith("conv1"))
    skip_bwd = sum(c["o"] for c in ch if c["end"])
    params = sum(c["w"] for c in ch) + 2048 * 1000
    g = 1e9
    ms = lambda b: b / g / gbs * 1e3

    fwd_floor = Si + So + skip_fwd
    bwd_floor = 3 * So + 2 * Si + skip_bwd
    bwd_2pass = bwd_floor + 2 * So
    opt_bytes = params * (3 * FP32 * 2 + 2 * BF16)

    print(f"ResNet-50 batch {args.batch}: {len(ch)} conv chains, "
          f"{params/1e6:.1f}M params;  Σi={Si/g:.2f} GB  Σo={So/g:.2f} GB")
    print(f"measured: fwd {args.fwd_ms} ms, bwd {args.bwd_ms} ms, "
          f"opt {args.opt_ms} ms @ {gbs:.0f} GB/s measured bw\n")
    rows = [
        ("fwd floor (fused conv+BN+ReLU)", fwd_floor, args.fwd_ms),
        ("bwd floor (1-pass BN bwd)", bwd_floor, args.bwd_ms),
        ("bwd w/ 2-pass BN stat sums", bwd_2pass, args.bwd_ms),
        ("optimizer (p/m/v fp32 rw + bf16 copies)", opt_bytes, args.opt_ms),
    ]
    for name, b, meas in rows:
        print(f"  {name:<42} {b/g:6.2f} GB -> {ms(b):6.1f} ms  "
              f"(measured {meas:5.1f} ms => implied "
              f"{b/g/meas*1e3:5.0f} GB/s effective)")

    step_floor = fwd_floor + bwd_floor + opt_bytes
    step_2pass = fwd_floor + bwd_2pass + opt_bytes
    meas_total = args.fwd_ms + args.bwd_ms + args.opt_ms
    print(f"\n  step floor  {step_floor/g:6.2f} GB -> {ms(step_floor):6.1f} "
          f"ms; step 2-pass {step_2pass/g:6.2f} GB -> {ms(step_2pass):6.1f} "
          f"ms; measured {meas_total:.1f} ms")
    unexplained = meas_total - ms(step_floor)
    print(f"  measured minus floor: {unexplained:+.1f} ms "
          f"({unexplained/meas_total:+.1%} of step) — the 2-pass BN "
          f"backward models {ms(step_2pass)-ms(step_floor):.1f} ms of it")
    for name, b in [("floor", step_floor), ("2-pass", step_2pass)]:
        t_spec = b / g / args.spec_bw * 1e3
        print(f"  @spec {args.spec_bw:.0f} GB/s, {name}: {t_spec:5.1f} ms "
              f"-> {args.batch/t_spec*1e3:5.0f} img/s")
    # compute-bound floor for context: ~12.3 GFLOP/img fwd+bwd, bf16 MXU
    flops = 12.3e9 * args.batch
    for peak in (197e12,):
        print(f"  MXU floor @ {peak/1e12:.0f} TFLOP/s bf16: "
              f"{flops/peak*1e3:5.1f} ms -> {args.batch/(flops/peak)/1e0:,.0f}"
              f" img/s (not the binding constraint)")


if __name__ == "__main__":
    main()
