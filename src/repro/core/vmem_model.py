"""Analytical TPU memory-hierarchy model for blocked GEMM and Winograd.

This module is the repo's gem5 analogue.  The paper sweeps vector length,
vector lanes and L2 size in a cycle-accurate simulator; we sweep the TPU
equivalents — block *width* (lane dim), on-chip parallelism, and VMEM budget —
in a first-order analytical model grounded in the v5e constants (repro/hw.py).

Model for a Pallas GEMM with grid (N/bn, M/bm, K/bk), K-innermost
accumulation in a VMEM scratch (our kernels/gemm):

  VMEM working set = 2*(bm*bk + bk*bn)*dtype + bm*bn*4   (double-buffered
                     A/B blocks + fp32 accumulator)
  HBM traffic      = M*K*(N/bn) + K*N*(M/bm) + 2*M*N     (A re-read per
                     column-panel, B re-read per row-panel, C written once;
                     this is exactly the BLIS traffic equation the paper's
                     6-loop blocking minimizes)
  compute time     = 2*Mp*Np*Kp / peak    (padded to HW granularity — the
                     TPU analogue of partially-filled vectors)
  startup          = grid_steps * per-step overhead  (the paper's "vector
                     start-up time" analogue)
  time             = max(compute, memory) + startup
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Iterable, List, Optional, Tuple

from repro.hw import V5E, ChipSpec
from repro.util import ceil_to

# The single source of truth for element sizes in the model.  Keyed by dtype
# *name* so it accepts numpy/jnp dtypes, python types and plain strings — the
# same normalization the planner's dtype plumbing uses.  Unknown names model
# as 4 bytes (fp32), the conservative default.
_ITEMSIZE = {
    "float64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1,
    "fp8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def itemsize(dtype) -> int:
    """Bytes per element for a dtype given as a dtype object, type or name.

    Every byte count in this module routes through here — the accumulator,
    bias/scale-row and dequant-output terms use ``itemsize("float32")``
    explicitly instead of a bare ``4``, so the fp32-ness of those buffers is
    stated where it is assumed.
    """
    name = (
        getattr(dtype, "__name__", None)
        or getattr(dtype, "name", None)
        or str(dtype)
    )
    return _ITEMSIZE.get(name, 4)


# Accumulators, bias/scale epilogue rows and int8 dequant outputs are fp32 /
# int32 in every kernel family regardless of the operand itemsize.
ACC_BYTES = itemsize("float32")


def tiled_bytes(shape: Tuple[int, ...], dtype_bytes: int = 4) -> int:
    """VMEM bytes of one buffer as Mosaic lays it out.

    The two minor dims are padded to the native (sublane, lane) tile: 128
    lanes, and 8 sublanes of 32-bit words — 16 rows for 2-byte and 32 rows
    for 1-byte types, which pack along the sublanes.  A (1, 226, 8) fp32
    slab therefore holds 16x its element bytes; counting elements is what
    let the planner pick blocks the compiler then refused.
    """
    *major, sub, lane = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub_tile = 8 * max(1, 4 // dtype_bytes)
    n = 1
    for d in major:
        n *= d
    return n * ceil_to(sub, sub_tile) * ceil_to(lane, V5E.lane_width) * dtype_bytes


#: Mosaic's internal scratch, fitted (as an upper bound) against the
#: compiler's scoped-VMEM requirement on v5e — the least
#: ``vmem_limit_bytes`` at which each kernel compiles, bisected to 64 KiB
#: (tests/test_tpu_compile.py checks the bound on fixed layers and on every
#: kernel the planner picks for VGG-16, YOLOv3-tiny, YOLOv3-20 and
#: ResNet-50).  A fixed allowance per kernel plus fp32 planes of the
#: matmul-row x lane shape: the im2col patch and matmul result, the
#: Winograd transforms' row planes, V and products, and the direct GEMM's
#: operand and result values.  The fused Winograd kernel's need depends on
#: its grid as well as its blocks — at (32, 128, 128) it ranges from
#: 9.06 MiB to 13.0 MiB (one Cin step, several Cout steps) — and its planes
#: cover the largest.
INTERNAL_BYTES = 256 * 1024
IM2COL_INTERNAL_PLANES = 4
WINOGRAD_INTERNAL_PLANES = 24


@dataclasses.dataclass(frozen=True)
class GemmShape:
    m: int
    n: int
    k: int

    @property
    def flops(self) -> int:
        return 2 * self.m * self.n * self.k


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    bm: int
    bn: int
    bk: int

    def vmem_bytes(self, dtype_bytes: int = 4, double_buffer: bool = True) -> int:
        """The blocked GEMM kernel's full footprint at this blocking (the
        quantity the autotuner budgets): ``gemm_kernel_vmem_bytes`` with the
        int8 epilogue's two rows, the larger case."""
        return gemm_kernel_vmem_bytes(
            self.bm, self.bn, self.bk, dtype_bytes,
            double_buffer=double_buffer, epilogue_rows=2,
        )


@dataclasses.dataclass(frozen=True)
class GemmEstimate:
    compute_s: float
    memory_s: float
    startup_s: float
    vmem_bytes: int
    hbm_bytes: int
    mxu_utilization: float

    @property
    def total_s(self) -> float:
        return max(self.compute_s, self.memory_s) + self.startup_s

    @property
    def bound(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"


def peak_flops(hw: ChipSpec, dtype_bytes: int) -> float:
    """MXU peak for a given element size: fp32 / bf16 / int8 ladder.

    The itemsize is the model's dtype proxy everywhere else, so it is here
    too: 4 → fp32, 2 → bf16, 1 → int8 (2x the bf16 rate on v5e-class MXUs).
    """
    if dtype_bytes >= 4:
        return hw.peak_flops_fp32
    if dtype_bytes == 1:
        return getattr(hw, "peak_flops_int8", 2 * hw.peak_flops_bf16)
    return hw.peak_flops_bf16


def predict_gemm(
    shape: GemmShape,
    block: BlockConfig,
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
    lanes: int = 1,
) -> GemmEstimate:
    """First-order time prediction for one blocked GEMM on one chip.

    ``lanes`` models extra on-chip parallelism (the paper's vector-lane
    sweep): peak compute scales, per-step overhead does not shrink — exactly
    the start-up-latency trade-off the paper observes (§VI.B.c).
    """
    mp = ceil_to(shape.m, max(block.bm, hw.sublanes))
    np_ = ceil_to(shape.n, max(block.bn, hw.lane_width))
    kp = ceil_to(shape.k, block.bk)
    peak = peak_flops(hw, dtype_bytes) * lanes
    compute_s = 2.0 * mp * np_ * kp / peak
    grid = (mp // block.bm) * (np_ // block.bn) * (kp // block.bk)
    # int8 GEMMs accumulate in int32 and write fp32 (the fused dequant
    # epilogue), so the C term keeps the fp32 itemsize.
    out_bytes = ACC_BYTES if dtype_bytes == 1 else dtype_bytes
    traffic = dtype_bytes * (
        shape.m * shape.k * (np_ // block.bn)
        + shape.k * shape.n * (mp // block.bm)
    ) + out_bytes * 2 * shape.m * shape.n
    return GemmEstimate(
        compute_s=compute_s,
        memory_s=traffic / hw.hbm_bandwidth,
        startup_s=grid * hw.grid_step_overhead_s,
        vmem_bytes=block.vmem_bytes(dtype_bytes),
        hbm_bytes=traffic,
        mxu_utilization=shape.flops / (2.0 * mp * np_ * kp),
    )


def candidate_blocks(
    vmem_budget: int,
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
    bms: Iterable[int] = (8, 16, 32, 64, 128, 256, 512),
    bns: Iterable[int] = (128, 256, 512, 1024, 2048),
    bks: Iterable[int] = (128, 256, 512, 1024, 2048),
) -> List[BlockConfig]:
    """HW-aligned block configs whose working set fits the VMEM budget."""
    out = []
    for bm, bn, bk in itertools.product(bms, bns, bks):
        cfg = BlockConfig(bm, bn, bk)
        if cfg.vmem_bytes(dtype_bytes) <= vmem_budget:
            out.append(cfg)
    return out


def autotune_gemm(
    shape: GemmShape,
    hw: ChipSpec = V5E,
    vmem_budget: Optional[int] = None,
    dtype_bytes: int = 4,
    lanes: int = 1,
) -> Tuple[BlockConfig, GemmEstimate]:
    """Pick the predicted-fastest block config under a VMEM budget.

    This is the BLIS 'block size tuning' step (paper Table II) with VMEM in
    the role of L2.
    """
    budget = vmem_budget if vmem_budget is not None else hw.vmem_bytes
    best: Tuple[Optional[BlockConfig], Optional[GemmEstimate]] = (None, None)
    for cfg in candidate_blocks(budget, hw, dtype_bytes):
        # Don't bother with blocks bigger than the (padded) problem.
        if cfg.bm > ceil_to(shape.m, hw.sublanes) * 2:
            continue
        if cfg.bn > ceil_to(shape.n, hw.lane_width) * 2:
            continue
        if cfg.bk > ceil_to(shape.k, 128) * 2:
            continue
        est = predict_gemm(shape, cfg, hw, dtype_bytes, lanes)
        if best[1] is None or est.total_s < best[1].total_s:
            best = (cfg, est)
    assert best[0] is not None, "no feasible block config under VMEM budget"
    return best  # type: ignore[return-value]


def gemm_kernel_vmem_bytes(
    bm: int, bn: int, bk: int, dtype_bytes: int = 4,
    out_dtype_bytes: Optional[int] = None, double_buffer: bool = True,
    epilogue_rows: int = 0, three_loop: bool = False, internal: bool = True,
) -> int:
    """Full per-program VMEM footprint of the blocked GEMM kernels.

    The complete footprint the compiled kernel holds — the A/B blocks, the
    streamed output block, the fused epilogue's (1, bn) bias/scale rows and
    the accumulator scratch, each as ``tiled_bytes`` lays it out — plus,
    with ``internal``, Mosaic's internal-scratch allowance.  The autotuner
    budgets the full figure; the static verifier (repro.analysis) compares
    the buffers alone (``internal=False``) with the jaxpr-recovered
    footprint.

    ``epilogue_rows`` counts the (1, bn) fp32 rows the epilogue streams:
    one for a fused bias, two for int8's scale + bias.  ``three_loop``
    models the full-K-panel variant, which accumulates in its output block
    and has no separate scratch (pass ``bk`` = the full K for it).

    Mosaic's internal scratch is the fixed allowance plus the dot's operand
    values and its fp32 result, which it can hold beside the buffers.  At
    four of ResNet-50's batch-64 1x1 layers the compiler needed 0.23 to
    1.98 MiB more than the buffers and the allowance; the operand and result
    values are the least of the dot's values that cover the largest (2.0 MiB
    at bm, bn, bk = 512, 256, 512), and over-count the others by 0.6 to
    3.0 MiB (tests/test_tpu_compile.py keeps the bisected minima).
    """
    if out_dtype_bytes is None:
        out_dtype_bytes = ACC_BYTES if dtype_bytes == 1 else dtype_bytes
    buf = 2 if double_buffer else 1
    total = buf * (tiled_bytes((bm, bk), dtype_bytes)         # A block
                   + tiled_bytes((bk, bn), dtype_bytes))      # B block
    total += buf * tiled_bytes((bm, bn), out_dtype_bytes)     # output block
    total += buf * epilogue_rows * tiled_bytes((1, bn), ACC_BYTES)
    if not three_loop:
        total += tiled_bytes((bm, bn), ACC_BYTES)             # accumulator
    if internal:
        total += (INTERNAL_BYTES + tiled_bytes((bm, bk), dtype_bytes)
                  + tiled_bytes((bk, bn), dtype_bytes)
                  + tiled_bytes((bm, bn), ACC_BYTES))
    return total


def winograd_traffic_bytes(
    oh: int, ow: int, cin: int, cout: int, batch: int = 1, dtype_bytes: int = 4,
    fused: bool = False,
) -> int:
    """HBM traffic of the winograd pipeline (input/V/M/output + U once).

    ``fused=False`` models the 3-pass realization (input transform, tuple
    multiply, output transform as separate kernels): the V and M
    intermediates, each (64, tiles, C) fp32, round-trip through HBM between
    kernels — ``2*tiles*64*(cin+cout)`` elements that dominate the layer.
    ``fused=True`` models the single-pass megakernel
    (kernels/winograd/kernel.py:fused_winograd_nhwc_pallas): V lives in registers
    and M in a VMEM scratch accumulator, so both round-trips vanish and only
    the tile reads, the pre-transformed weights and the output remain.

    Winograd's working set per stage is smaller than im2col's K-panel —
    the reason the paper finds it needs less cache (§VII.B).
    """
    nth, ntw = -(-oh // 6), -(-ow // 6)
    tiles = batch * nth * ntw
    x_bytes = tiles * 64 * cin            # overlapping 8x8 reads
    u_bytes = 64 * cin * cout             # pre-transformed weights, read once
    y_bytes = tiles * 36 * cout           # output write
    if fused:
        return dtype_bytes * (x_bytes + u_bytes + y_bytes)
    v_bytes = 2 * tiles * 64 * cin        # V write + read
    m_bytes = 2 * tiles * 64 * cout       # M write + read
    return dtype_bytes * (x_bytes + v_bytes + u_bytes + m_bytes + y_bytes)


def im2col_gemm_traffic_bytes(
    oh: int, ow: int, cin: int, cout: int, kh: int = 3, kw: int = 3,
    batch: int = 1, dtype_bytes: int = 4, out_dtype_bytes: Optional[int] = None,
) -> int:
    """Ideal-reuse HBM traffic of one im2col+GEMM conv layer.

    The three terms of the paper's Table-IV GEMM, itemsize-aware: the
    logical patch matrix read (batch*oh*ow x kh*kw*cin), the weight read,
    and the output write.  Input/weight elements move at ``dtype_bytes``;
    the output moves at ``out_dtype_bytes`` (defaults to 4 for int8 inputs —
    the kernel's dequant epilogue writes fp32 — and to ``dtype_bytes``
    otherwise).  This is the quantity the int8 policy's ≤ 0.5x fp32 traffic
    gate compares (core/quant.py::int8_traffic_ratio).
    """
    if out_dtype_bytes is None:
        out_dtype_bytes = ACC_BYTES if dtype_bytes == 1 else dtype_bytes
    rows = batch * oh * ow
    taps = kh * kw
    return (
        dtype_bytes * (rows * taps * cin + taps * cin * cout)
        + out_dtype_bytes * rows * cout
    )


def im2col_window(
    toh: int, ow: int, kh: int = 3, kw: int = 3, sh: int = 1, sw: int = 1,
) -> Tuple[int, int, int]:
    """(owp, rows_in, wq) of the im2col kernel's output width and window.

    The kernel emits ``owp`` = OW rounded up to the 8-row sublane tile, and
    reads, per stride phase, ``rows_in`` = toh + (kh-1)//sh rows of
    ``wq`` = owp + (kw-1)//sw columns (kernels/im2col_gemm).
    """
    owp = ceil_to(ow, V5E.sublanes)
    return owp, toh + (kh - 1) // sh, owp + (kw - 1) // sw


def im2col_kernel_vmem_bytes(
    toh: int, ow: int, bc: int, bo: int,
    kh: int = 3, kw: int = 3, sh: int = 1, sw: int = 1, dtype_bytes: int = 4,
    double_buffer: bool = True, bias: bool = True,
    out_dtype_bytes: Optional[int] = None, internal: bool = True,
) -> int:
    """Per-program VMEM footprint of the fused im2col+GEMM conv kernel.

    The kernel (kernels/im2col_gemm/kernel.py) keeps live at once: the
    (sh, rows_in, sw*wq, bc) input window of its row tile and the
    (kh, kw, bc, bo) weight block (both double-buffered across the
    in-channel grid axis), the optional (1, bo) bias/scale rows, the
    (toh, OWp, bo) output block and the (toh*OWp, bo) fp32/int32
    accumulator scratch, plus (``internal``) Mosaic's internal scratch for
    the patch and the matmul result.  Every buffer is counted as
    ``tiled_bytes`` lays it out.

    ``out_dtype_bytes`` sizes the output block separately from the operands:
    an int8 conv reads int8 slabs/weights but writes fp32 (dequant
    epilogue), and its bias/scale rows and accumulator scratch stay
    fp32/int32 (4-byte) regardless of the operand itemsize.
    """
    if out_dtype_bytes is None:
        out_dtype_bytes = ACC_BYTES if dtype_bytes == 1 else dtype_bytes
    buf = 2 if double_buffer else 1
    owp, rows_in, wq = im2col_window(toh, ow, kh, kw, sh, sw)
    rows = toh * owp
    return (
        buf * tiled_bytes((sh, rows_in, sw * wq, bc), dtype_bytes)
        + buf * tiled_bytes((kh, kw, bc, bo), dtype_bytes)
        + (2 * buf * tiled_bytes((1, bo), ACC_BYTES) if bias else 0)
        + buf * tiled_bytes((toh, owp, bo), out_dtype_bytes)
        + tiled_bytes((rows, bo), ACC_BYTES)
        + (IM2COL_INTERNAL_PLANES * tiled_bytes((rows, max(bc, bo)), ACC_BYTES)
           + INTERNAL_BYTES if internal else 0)
    )


def winograd_kernel_vmem_bytes(
    bt: int, bc: int, bo: int, fused: bool = True, dtype_bytes: int = 4,
    double_buffer: bool = True, internal: bool = True,
) -> int:
    """Per-program VMEM footprint of the Winograd Pallas kernels.

    ``fused=True``: the single-pass megakernel holds the (8, 8, bt, bc) tile
    block and the (8, 8, bc, bo) weight block (both double-buffered across
    the Cin grid axis), the (8, 8, bt, bo) fp32 M accumulator scratch, the
    (6, 6, bt, bo) output block, the (1, bo) bias row, and (``internal``)
    Mosaic's internal scratch for the transform planes.  At bt = bb*k*ntw
    it bounds the same kernel tiling in VMEM from above: its NHWC input
    window (bb, 6k+2, 6 ntw + 8, bc) holds at most 56 planes per tile.

    ``fused=False``: the 3-pass pipeline's footprint is the max over its
    three kernels — each one's in/out blocks are live simultaneously (plus
    the tuple-multiply's fp32 accumulator scratch).
    """
    buf = 2 if double_buffer else 1
    d = dtype_bytes
    planes = WINOGRAD_INTERNAL_PLANES if internal else 0
    extra = INTERNAL_BYTES if internal else 0
    if fused:
        return (
            buf * tiled_bytes((8, 8, bt, bc), d)         # input tile block
            + buf * tiled_bytes((8, 8, bc, bo), d)       # transformed weights
            + tiled_bytes((8, 8, bt, bo), ACC_BYTES)     # M accumulator
            + buf * tiled_bytes((6, 6, bt, bo), d)       # output block
            + buf * tiled_bytes((1, bo), ACC_BYTES)      # bias row
            + planes * tiled_bytes((bt, bc + bo), ACC_BYTES)
            + extra
        )
    input_tf = (
        buf * 2 * tiled_bytes((8, 8, bt, bc), d)
        + planes * tiled_bytes((bt, bc), ACC_BYTES)
    )
    tuple_mul = (
        buf * (tiled_bytes((bt, bc), d) + tiled_bytes((bc, bo), d))
        + buf * tiled_bytes((bt, bo), d)
        + tiled_bytes((bt, bo), ACC_BYTES)
    )
    output_tf = (
        buf * tiled_bytes((8, 8, bt, bo), d)
        + buf * tiled_bytes((6, 6, bt, bo), d)
        + buf * tiled_bytes((1, bo), ACC_BYTES)
        + planes * tiled_bytes((bt, bo), ACC_BYTES)
    )
    return max(input_tf, tuple_mul, output_tf) + extra


# Candidate (bt, bc, bo) grids for the Winograd kernels: tiles on sublanes,
# channels on lanes — the same HW granularity the GEMM candidates use.
WINOGRAD_BTS = (8, 16, 32, 64, 128, 256)
WINOGRAD_BCS = (128, 256, 512)
WINOGRAD_BOS = (128, 256, 512)


def predict_winograd(
    tiles: int,
    cin: int,
    cout: int,
    blocks: Tuple[int, int, int],
    hw: ChipSpec = V5E,
    dtype_bytes: int = 4,
    fused: bool = True,
) -> GemmEstimate:
    """First-order time prediction for the Winograd kernels at one blocking.

    The traffic term is block-aware (BLIS-style panel re-reads: the tile
    panel per out-channel panel, the weight panel per tile panel), unlike
    ``winograd_traffic_bytes`` which reports the ideal-reuse totals; the
    3-pass variant additionally pays the V/M round trips and a 64x larger
    grid for the tuple-multiply stage.
    """
    bt, bc, bo = blocks
    tp = ceil_to(tiles, bt)
    cp = ceil_to(cin, bc)
    op = ceil_to(cout, bo)
    nt, nc, no = tp // bt, cp // bc, op // bo
    peak = peak_flops(hw, dtype_bytes)
    # The tuple multiply dominates compute: 64 GEMMs of (tp, cp) x (cp, op).
    compute_s = 2.0 * 64 * tp * cp * op / peak
    x_bytes = tiles * 64 * cin * dtype_bytes
    u_bytes = 64 * cin * cout * dtype_bytes
    y_bytes = tiles * 36 * cout * dtype_bytes
    if fused:
        grid = nt * no * nc
        traffic = x_bytes * no + u_bytes * nt + y_bytes
    else:
        v_bytes = tiles * 64 * cin * dtype_bytes
        m_bytes = tiles * 64 * cout * dtype_bytes
        grid = nt * nc + 64 * nt * no * nc + nt * no
        traffic = (
            (x_bytes + v_bytes)                       # input transform
            + (v_bytes * no + u_bytes * nt + m_bytes)  # tuple multiply
            + (m_bytes + y_bytes)                      # output transform
        )
    return GemmEstimate(
        compute_s=compute_s,
        memory_s=traffic / hw.hbm_bandwidth,
        startup_s=grid * hw.grid_step_overhead_s,
        vmem_bytes=winograd_kernel_vmem_bytes(bt, bc, bo, fused, dtype_bytes),
        hbm_bytes=traffic,
        mxu_utilization=(tiles * cin * cout) / float(tp * cp * op),
    )


def autotune_winograd_blocks(
    tiles: int,
    cin: int,
    cout: int,
    hw: ChipSpec = V5E,
    vmem_budget: Optional[int] = None,
    dtype_bytes: int = 4,
    fused: bool = True,
) -> Tuple[Tuple[int, int, int], GemmEstimate]:
    """Pick the predicted-fastest (bt, bc, bo) under a VMEM budget.

    The Winograd instance of the paper's Table-II block-size tuning: every
    HW-aligned candidate no bigger than the padded problem is scored with
    ``predict_winograd`` and checked against the *full* per-kernel footprint
    (``winograd_kernel_vmem_bytes``).  If even the granularity floor
    (8, 128, 128) overflows the budget it is returned anyway — block shapes
    cannot shrink below the (sublane, lane) tile.
    """
    budget = vmem_budget if vmem_budget is not None else hw.vmem_bytes
    bt_max = ceil_to(tiles, 8)
    bc_max = ceil_to(cin, 128)
    bo_max = ceil_to(cout, 128)
    candidates = [
        (bt, bc, bo)
        for bt in WINOGRAD_BTS
        for bc in WINOGRAD_BCS
        for bo in WINOGRAD_BOS
        if bt <= bt_max and bc <= bc_max and bo <= bo_max
        and winograd_kernel_vmem_bytes(bt, bc, bo, fused, dtype_bytes) <= budget
    ]
    if not candidates:
        candidates = [(8, 128, 128)]
    best = min(
        candidates,
        key=lambda b: predict_winograd(
            tiles, cin, cout, b, hw, dtype_bytes, fused
        ).total_s,
    )
    return best, predict_winograd(tiles, cin, cout, best, hw, dtype_bytes, fused)


@functools.lru_cache(maxsize=1024)
def winograd_nhwc_blocks(
    b: int, nth: int, ntw: int, bc: int, bo: int,
    hw: ChipSpec = V5E, vmem_budget: Optional[int] = None,
) -> Tuple[int, int, int]:
    """(bb images, k tile rows, ntw_b tile columns) per program of the
    fused Winograd kernel, which cuts its tiles from NHWC row windows, for
    an (nth x ntw)-tile output of ``b`` images and channel blocks (bc, bo).

    Fewest computed tiles first (tile columns pad to the ntw_b multiple of
    8 sublanes, tile rows to the k multiple), then the most tiles per block
    bb*k*ntw_b whose footprint — ``winograd_kernel_vmem_bytes`` at
    bt = bb*k*ntw_b, an upper bound of the fp32 window
    (bb, 6k+2, 6 ntw_b + 8, bc) — fits the budget: every block's 64 GEMMs
    push their weight planes into the MXU, and a block re-fetches the
    weights when Cp or Op spans several channel blocks, so fewer, larger
    blocks amortize both.  Then the smaller input window.  Several images
    share a block only when it holds all their tiles.  If not even one
    8-tile row fits, (1, 1, 8) is returned anyway: blocks cannot shrink
    below the sublane granule.
    """
    budget = vmem_budget if vmem_budget is not None else hw.vmem_bytes
    best, best_key = (1, 1, 8), None
    for nw in range(8, ceil_to(ntw, 8) + 1, 8):
        for k in range(1, nth + 1):
            whole = k == nth and nw >= ntw
            for bb in range(1, (b if whole else 1) + 1):
                te = bb * k * nw
                if b % bb or winograd_kernel_vmem_bytes(te, bc, bo) > budget:
                    continue
                key = (ceil_to(nth, k) * ceil_to(ntw, nw), -te,
                       bb * (6 * k + 2) * (6 * nw + 8))
                if best_key is None or key < best_key:
                    best, best_key = (bb, k, nw), key
    return best
