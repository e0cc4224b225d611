"""Hardware/software co-design sweeps (the paper's §V/§VI study, TPU-ized).

Sweeps the three TPU analogues of the paper's knobs against the optimized
kernels, using the analytical model in vmem_model.py:

  vector length  ->  block width bn (lane-dim elements per block)
  L2 cache size  ->  VMEM budget available for blocking
  vector lanes   ->  on-chip parallel compute (``lanes`` peak multiplier)

Outputs feed benchmarks/table2_blocksizes.py, table3_veclen.py and
fig_cache_sweep.py, which mirror Table II / Fig 6 / Figs 7-8 of the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.conv_spec import ConvSpec, arithmetic_intensity
from repro.core.vmem_model import (
    BlockConfig,
    GemmEstimate,
    GemmShape,
    autotune_gemm,
    predict_gemm,
)
from repro.hw import V5E, ChipSpec
from repro.util import ceil_to

MB = 1024 * 1024

# Default sweep ranges: VMEM budgets stand in for the 1MB..256MB L2 sweep;
# block widths stand in for 512-bit..16384-bit vectors (16..512 fp32 elems,
# scaled x8 to TPU lane granularity).
VMEM_BUDGETS = (1 * MB, 2 * MB, 4 * MB, 8 * MB, 16 * MB, 32 * MB, 64 * MB)
BLOCK_WIDTHS = (128, 256, 512, 1024, 2048)
LANES = (1, 2, 4, 8)


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    vmem_budget: int
    bn: int
    lanes: int
    block: BlockConfig
    estimate: GemmEstimate


def sweep_vector_length(
    shape: GemmShape,
    vmem_budget: int = 16 * MB,
    lanes: int = 1,
    widths: Sequence[int] = BLOCK_WIDTHS,
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
) -> List[SweepPoint]:
    """Fig 6 analogue: fixed cache (VMEM), sweep the vector (lane) width."""
    points = []
    for bn in widths:
        best: Tuple[Optional[BlockConfig], Optional[GemmEstimate]] = (None, None)
        for bm in (8, 16, 32, 64, 128, 256):
            for bk in (128, 256, 512, 1024, 2048):
                cfg = BlockConfig(bm, bn, bk)
                if cfg.vmem_bytes(dtype_bytes) > vmem_budget:
                    continue
                est = predict_gemm(shape, cfg, hw, dtype_bytes, lanes)
                if best[1] is None or est.total_s < best[1].total_s:
                    best = (cfg, est)
        if best[0] is not None:
            points.append(SweepPoint(vmem_budget, bn, lanes, best[0], best[1]))
    return points


def sweep_cache_size(
    shape: GemmShape,
    budgets: Sequence[int] = VMEM_BUDGETS,
    lanes: int = 1,
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
) -> Dict[int, List[SweepPoint]]:
    """Fig 7/8 analogue: per VMEM budget, the best config at each width."""
    return {
        budget: sweep_vector_length(shape, budget, lanes, hw=hw, dtype_bytes=dtype_bytes)
        for budget in budgets
    }


def sweep_lanes(
    shape: GemmShape,
    vmem_budget: int = 16 * MB,
    lanes: Sequence[int] = LANES,
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
) -> List[SweepPoint]:
    """§VI.B.c analogue: on-chip parallelism vs block width trade-off."""
    out = []
    for ln in lanes:
        cfg, est = autotune_gemm(shape, hw, vmem_budget, dtype_bytes, ln)
        out.append(SweepPoint(vmem_budget, cfg.bn, ln, cfg, est))
    return out


def predict_conv_time(
    spec: ConvSpec,
    h: int,
    w: int,
    algorithm,
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
    batch: int = 1,
    winograd_fused: bool = True,
) -> float:
    """Modeled seconds for one conv layer executed with ``algorithm``.

    Roofline time max(compute, HBM traffic) at this layer's dims.  GEMM-family
    algorithms (direct / im2col) move the patch matrix, the weights and the
    output; Winograd moves the tile/transform pipeline — by default the
    single-pass megakernel's traffic (transforms and M accumulation fused in
    VMEM, ``winograd_fused=True``), or the 3-pass pipeline's traffic with the
    V/M HBM round-trips (``winograd_fused=False``).  Activation terms scale
    with ``batch``; weight terms do not.

    Itemsize-aware: ``dtype_bytes`` prices the operand traffic and picks the
    fp32/bf16/int8 MXU peak; the output write is priced separately because
    the int8 kernels dequantize in the epilogue and write fp32.
    """
    from repro.core.conv_spec import ConvAlgorithm
    from repro.core.vmem_model import im2col_gemm_traffic_bytes, peak_flops
    from repro.core.winograd import winograd_flops

    oh, ow = spec.out_hw(h, w)
    cin, cout = spec.in_channels, spec.out_channels
    kh, kw = spec.kernel_size
    peak = peak_flops(hw, dtype_bytes)
    bw = hw.hbm_bandwidth
    if algorithm is ConvAlgorithm.WINOGRAD:
        from repro.core.vmem_model import winograd_traffic_bytes

        fl = winograd_flops(oh, ow, cin, cout)
        wino_bytes = winograd_traffic_bytes(
            oh, ow, cin, cout, batch, dtype_bytes, fused=winograd_fused
        )
        return max(batch * fl["winograd_flops"] / peak, wino_bytes / bw)
    # direct-1x1 and im2col share the GEMM roofline; direct just has K = Cin.
    gemm_bytes = im2col_gemm_traffic_bytes(
        oh, ow, cin, cout, kh, kw, batch=batch, dtype_bytes=dtype_bytes
    )
    flops = 2.0 * batch * oh * ow * kh * kw * cin * cout
    return max(flops / peak, gemm_bytes / bw)


def narrow_input(spec: ConvSpec, hw: ChipSpec = V5E) -> bool:
    """Is this a conv whose taps can fold: several taps over fewer input
    channels than a lane block (RGB stems; 32-channel layers)?"""
    return spec.kh * spec.kw > 1 and spec.in_channels < hw.lane_width


def predict_narrow_conv_time(
    spec: ConvSpec,
    h: int,
    w: int,
    algorithm,
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
    batch: int = 1,
    winograd_fused: bool = True,
    taps_folded: bool = False,
) -> float:
    """Modeled seconds of a narrow-input conv at the work each realization
    really does, for choosing between them (``predict_conv_time`` prices the
    logical shapes, which hide the padding this choice is about).

    Each realization is a roofline over its HBM traffic and its MXU work,
    at the rate fp32 runs at HIGHEST precision (six bf16 passes), plus the
    passes XLA makes to lay its operands out, at ``hw``'s measured
    ``relayout_bandwidth`` and ``lane_concat_bandwidth``.  Every
    realization reads the input once and writes the output at its
    lane-padded out channels.  The fused im2col kernel (``algorithm``
    IM2COL_GEMM) and the Winograd kernel multiply the input channels padded
    to a lane block: the wrapper writes that padded copy (one pass; the
    im2col wrapper splits a stride into phases, two more), and the kernel
    reads it once per tap window, or as overlapping 8x8 tiles (plus V and
    M through HBM when not ``winograd_fused``).  Tap folding
    (``taps_folded``) multiplies K = kh*kw*C rounded up to the lane width
    once, over a (B*OH*OW, K) patch matrix it writes and reads, which XLA
    builds by concatenating the taps' narrow channel slices along the
    lanes, after splitting a stride into its phases (a pass over the input
    per phase).
    """
    from repro.core.conv_spec import ConvAlgorithm
    from repro.core.vmem_model import peak_flops

    lane = hw.lane_width
    oh, ow = spec.out_hw(h, w)
    sh, sw = spec.stride
    m = batch * oh * ow
    op = ceil_to(spec.out_channels, lane)
    taps = spec.kh * spec.kw
    x = batch * h * w * spec.in_channels
    elems = x + m * op
    if taps_folded:
        k = taps * spec.in_channels
        kp = ceil_to(k, lane)
        flops = 2.0 * m * kp * op
        elems += 2 * m * kp + kp * op
        moved = (m * k / hw.lane_concat_bandwidth
                 + (sh * sw * x if sh * sw > 1 else 0)
                 / hw.relayout_bandwidth)
    else:
        cp = ceil_to(spec.in_channels, lane)
        x_p = batch * h * w * cp
        if algorithm is ConvAlgorithm.WINOGRAD:
            tiles = batch * -(-oh // 6) * -(-ow // 6)
            flops = 2.0 * tiles * 64 * cp * op
            elems += x_p + tiles * 64 * cp + 64 * cp * op
            if not winograd_fused:
                elems += 2 * tiles * 64 * (cp + op)
            moved = x_p / hw.relayout_bandwidth
        else:
            flops = 2.0 * m * taps * cp * op
            elems += 2 * x_p + taps * cp * op
            moved = (3 if sh * sw > 1 else 1) * x_p / hw.relayout_bandwidth
    peak = (hw.peak_flops_bf16 / 6 if dtype_bytes == 4
            else peak_flops(hw, dtype_bytes))
    return (max(flops / peak, dtype_bytes * elems / hw.hbm_bandwidth)
            + dtype_bytes * moved)


def select_algorithm_by_cost(
    spec: ConvSpec, h: int, w: int, hw: ChipSpec = V5E, dtype_bytes: int = 4,
    winograd_fused: bool = True, batch: int = 1,
):
    """Roofline-model-driven per-layer algorithm choice (beyond paper).

    The paper selects Winograd for every 3x3/stride-1 layer.  On v5e
    (critical AI ~120 fp32) that rule over-triggers: Winograd's 64/9x
    weight-traffic inflation loses for deep low-resolution layers.  This
    selector compares modeled times of im2col+GEMM vs the Winograd
    realization that would actually run (``winograd_fused``: the single-pass
    megakernel by default, the 3-pass pipeline when a planner forces it)
    and picks the winner.
    """
    from repro.core.conv_spec import ConvAlgorithm, select_algorithm

    base = select_algorithm(dataclasses.replace(spec, algorithm=ConvAlgorithm.AUTO))
    if base is not ConvAlgorithm.WINOGRAD:
        return base
    t_wino = predict_conv_time(
        spec, h, w, ConvAlgorithm.WINOGRAD, hw, dtype_bytes, batch,
        winograd_fused=winograd_fused,
    )
    t_im2col = predict_conv_time(
        spec, h, w, ConvAlgorithm.IM2COL_GEMM, hw, dtype_bytes, batch
    )
    return ConvAlgorithm.WINOGRAD if t_wino < t_im2col else ConvAlgorithm.IM2COL_GEMM


def layer_roofline(
    spec: ConvSpec, h: int, w: int, hw: ChipSpec = V5E, dtype_bytes: int = 4
) -> Dict[str, float]:
    """Table IV analogue: AI + % of single-chip peak for one conv layer."""
    m, n, k = spec.gemm_dims(h, w)
    ai = arithmetic_intensity(m, n, k, dtype_bytes)
    peak = hw.peak_flops_fp32 if dtype_bytes == 4 else hw.peak_flops_bf16
    ai_critical = peak / hw.hbm_bandwidth
    # Attainable fraction under the roofline, degraded by MXU padding waste.
    _, est = autotune_gemm(GemmShape(m, n, k), hw, dtype_bytes=dtype_bytes)
    attainable = min(1.0, ai / ai_critical)
    sustained = est.compute_s / est.total_s * est.mxu_utilization
    return {
        "M": m,
        "N": n,
        "K": k,
        "AI": ai,
        "ai_critical": ai_critical,
        "roofline_frac": attainable,
        "pct_of_peak": 100.0 * min(attainable, sustained),
    }
