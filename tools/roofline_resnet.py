"""Analytic per-stage roofline for ResNet-50 training on a single v5e.

The measurement-backed answer to "why does ResNet-50 MFU cap well below
the 58% matmul ceiling on this chip" (builder session 2026-08-01,
record deleted in PR 21): computes
FLOPs and HBM bytes per conv site at the headline configuration
(b128, 224x224, bf16), classifies each against the v5e ridge point, and
converts the totals into per-step time lower bounds that the measured
numbers can be read against.

Model of record:
- v5e peak: 197 TFLOP/s bf16 (utils/platform.py table), 819 GB/s HBM.
- Forward conv FLOPs = 2*B*H'*W'*k*k*Cin*Cout; training ~= 3x forward
  (fwd + dX + dW passes), and training bytes ~= 3x forward activation
  traffic (dX re-reads weights + writes dAct; dW re-reads acts).
- Bytes per site = activations in + out + weights at bf16.  This is the
  OPTIMISTIC floor: BatchNorm statistics (a separate full read), ReLU,
  residual adds, and max-pool traffic are NOT counted, and no kernel
  attains 100% of HBM peak — so real ceilings sit meaningfully below
  the printed bounds.

Run: python tools/roofline_resnet.py  (pure arithmetic, no jax)
"""

from __future__ import annotations

PEAK = 197e12  # v5e bf16 FLOP/s
BW = 819e9     # v5e HBM bytes/s
B = 128        # headline batch


def conv(cin, cout, k, hw, stride=1, name=""):
    out_hw = hw // stride
    flops = 2 * B * out_hw * out_hw * k * k * cin * cout
    act_in = B * hw * hw * cin * 2
    act_out = B * out_hw * out_hw * cout * 2
    w = k * k * cin * cout * 2
    return name or f"conv{k}x{k}", flops, act_in + act_out + w


def main() -> None:
    stages = [conv(3, 64, 7, 224, 2, "stem 7x7/2 C3->64")]
    # (cin, cmid, cout, blocks, input hw, first stride) per bottleneck stage.
    defs = [
        (64, 64, 256, 3, 56, 1),
        (256, 128, 512, 4, 56, 2),
        (512, 256, 1024, 6, 28, 2),
        (1024, 512, 2048, 3, 14, 2),
    ]
    for cin, cmid, cout, blocks, hw, s in defs:
        for b in range(blocks):
            stride = s if b == 0 else 1
            inpc = cin if b == 0 else cout
            ihw = hw if b == 0 else hw // s
            tag = f"stage C{cmid} blk{b}"
            stages.append(conv(inpc, cmid, 1, ihw, 1, tag + " 1x1a"))
            stages.append(conv(cmid, cmid, 3, ihw, stride, tag + " 3x3"))
            stages.append(conv(cmid, cout, 1, ihw // stride, 1, tag + " 1x1b"))
            if b == 0:
                stages.append(conv(inpc, cout, 1, ihw, stride, tag + " proj"))
    stages.append(
        ("fc 2048->1000", 2 * B * 2048 * 1000,
         (B * 2048 + 2048 * 1000 + B * 1000) * 2)
    )

    ridge = PEAK / BW
    print(f"v5e ridge point: {ridge:.0f} FLOP/byte (bf16)")
    groups: dict[str, list[float]] = {}
    tot_f = tot_b = bw_f = 0.0
    for name, f, by in stages:
        tot_f += f
        tot_b += by
        if f / by < ridge:
            bw_f += f
        key = name.split(" blk")[0]
        g = groups.setdefault(key, [0.0, 0.0])
        g[0] += f
        g[1] += by
    print(f"{'group':18s} {'GFLOP':>9s} {'MB':>9s} {'FLOP/B':>8s} bound")
    for k, (f, by) in groups.items():
        ai = f / by
        print(
            f"{k:18s} {f/1e9:9.1f} {by/1e6:9.1f} {ai:8.0f} "
            f"{'MXU' if ai >= ridge else 'BW'}"
        )
    print(
        f"\nforward: {tot_f/1e9:.0f} GFLOP, {tot_b/1e6:.0f} MB, "
        f"mean intensity {tot_f/tot_b:.0f} FLOP/byte "
        f"({'NET BW-BOUND' if tot_f/tot_b < ridge else 'net MXU-bound'}); "
        f"{bw_f/tot_f:.0%} of FLOPs sit in BW-bound sites"
    )
    t_mxu = 3 * tot_f / PEAK
    t_bw = 3 * tot_b / BW
    print(
        f"train-step lower bounds (b{B}, optimistic bytes): "
        f"MXU {t_mxu*1e3:.1f} ms, HBM {t_bw*1e3:.1f} ms"
    )
    # True-FLOP convention throughout (2 FLOPs/MAC, like the LM 6ND
    # count); pre-r4 logs called 3200 ips "20% MFU" from
    # the MAC-based constant — it is 40% true MFU.
    for ips, label in [
        (2070.8, "r3 measured f32-BN"),
        (2630.2, "r3 measured bf16-BN"),
        (3200.0, "stretch (40% true MFU)"),
    ]:
        step = B / ips
        print(
            f"  {label}: {step*1e3:.1f} ms/step -> "
            f"MXU busy {t_mxu/step:.0%}, HBM busy {t_bw/step:.0%} "
            f"of the optimistic floor"
        )


if __name__ == "__main__":
    main()
