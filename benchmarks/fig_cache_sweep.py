"""Paper Figs 7/8 (+9/10): L2-cache-size sweep x vector length, for
im2col+GEMM and Winograd.

TPU mapping: VMEM budget (1..64 MiB) x block width, over the YOLOv3
first-20-layer GEMMs and the VGG16 conv stack.  Reproduced findings:
  - larger budgets help more at wider blocks (Fig 7/8);
  - Winograd saturates at a smaller budget than im2col+GEMM (Figs 9/10,
    'Winograd has lower cache requirements').
"""
from __future__ import annotations

from benchmarks.common import emit, vgg16_gemms, yolov3_20_gemms
from repro.core.codesign import MB, sweep_cache_size
from repro.core.vmem_model import GemmShape
from repro.core.winograd import winograd_flops
from repro.hw import V5E

BUDGETS = (1 * MB, 4 * MB, 16 * MB, 64 * MB)


def _im2col_total(layers, budget):
    total = 0.0
    for d in layers:
        pts = sweep_cache_size(GemmShape(d["M"], d["N"], d["K"]),
                               budgets=(budget,))[budget]
        total += min(p.estimate.total_s for p in pts)
    return total


def _pipeline_traffic_bytes(oh, ow, cin, cout, dtype_bytes=4):
    """HBM bytes of the paper's Winograd pipeline, one stage after another:
    the overlapping 8x8 tile reads, the pre-transformed weights (read once)
    and the output, plus the V and M intermediates, each (64, tiles, C),
    written by one stage and read by the next."""
    tiles = -(-oh // 6) * -(-ow // 6)
    x_bytes = tiles * 64 * cin
    v_bytes = 2 * tiles * 64 * cin
    u_bytes = 64 * cin * cout
    m_bytes = 2 * tiles * 64 * cout
    y_bytes = tiles * 36 * cout
    return dtype_bytes * (x_bytes + v_bytes + u_bytes + m_bytes + y_bytes)


def _winograd_total(layers, budget):
    """Winograd time model: tuple-GEMM via the block model at the given
    budget + transform traffic (bandwidth-bound)."""
    total = 0.0
    for d in layers:
        if d["kernel"] != 3 or d["stride"] != 1:
            pts = sweep_cache_size(GemmShape(d["M"], d["N"], d["K"]),
                                   budgets=(budget,))[budget]
            total += min(p.estimate.total_s for p in pts)
            continue
        oh = ow = int(round(d["N"] ** 0.5))
        fl = winograd_flops(oh, ow, d["cin"], d["cout"])
        tiles = -(-oh // 6) * -(-ow // 6)
        # 64 independent (tiles x cin) @ (cin x cout) GEMMs.
        pts = sweep_cache_size(GemmShape(tiles, d["cout"], d["cin"]),
                               budgets=(budget,))[budget]
        tuple_t = 64 * min(p.estimate.total_s for p in pts)
        tf_t = (_pipeline_traffic_bytes(oh, ow, d["cin"], d["cout"])
                / V5E.hbm_bandwidth
                + fl["transform_flops"] / V5E.peak_flops_fp32)
        total += tuple_t + tf_t
    return total


def run() -> None:
    yolo = yolov3_20_gemms()
    vgg = vgg16_gemms()
    base_i = _im2col_total(yolo, BUDGETS[0])
    base_w = _winograd_total(vgg, BUDGETS[0])
    sat_budget_i = sat_budget_w = None
    prev_i = prev_w = None
    for b in BUDGETS:
        ti = _im2col_total(yolo, b)
        tw = _winograd_total(vgg, b)
        emit(f"fig7/yolo_im2col_vmem_{b // MB}MB", ti,
             f"speedup_vs_1MB={base_i / ti:.2f}")
        emit(f"fig10/vgg_winograd_vmem_{b // MB}MB", tw,
             f"speedup_vs_1MB={base_w / tw:.2f}")
        if prev_i is not None and ti > 0.98 * prev_i and sat_budget_i is None:
            sat_budget_i = b
        if prev_w is not None and tw > 0.98 * prev_w and sat_budget_w is None:
            sat_budget_w = b
        prev_i, prev_w = ti, tw
    emit("fig9_10/winograd_saturates_earlier", 0.0,
         f"winograd_sat={sat_budget_w and sat_budget_w // MB}MB;"
         f"im2col_sat={sat_budget_i and sat_budget_i // MB}MB")


if __name__ == "__main__":
    run()
