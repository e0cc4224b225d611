"""Jitted end-to-end Winograd conv on the Pallas kernel.

Pipeline (paper §IV.B):  tile -> input transform -> tuple multiply ->
output transform -> untile, with the weight transform done offline.  The
compute stages run as one megakernel whose transforms and M accumulation
never leave VMEM.  It reads element-offset row windows of the padded NHWC
activation, cuts the overlapping 8x8 tiles itself with loads strided by 6,
and writes NHWC.  Around it are one XLA pad (the conv's spatial padding plus
the tile tail) and, for a caller that drops padded channels, one crop.  The
tile columns of a block are padded to the 8-sublane granule, which is MXU
and VPU work on tiles that do not exist (``winograd_tiling`` counts it).
The jnp realization of the same decomposition is ``core/winograd.py``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.conv_spec import ConvSpec
from repro.core.vmem_model import (
    ACC_BYTES,
    winograd_kernel_vmem_bytes,
    winograd_nhwc_blocks,
)
from repro.core.winograd import OUT_TILE, transform_weights
from repro.hw import V5E
from repro.util import ceil_to, pad_bias_row


def pick_blocks(
    t: int, c: int, o: int, vmem_budget: Optional[int] = None,
    dtype_bytes: int = 4,
) -> Tuple[int, int, int]:
    """(bt, bc, bo) aligned to (sublane, lane) granularity, VMEM-bounded.

    Budgets the **full** per-kernel footprint via
    ``vmem_model.winograd_kernel_vmem_bytes``: the double-buffered tile +
    weight blocks, the (8, 8, bt, bo) fp32 M accumulator scratch and the
    output block.  (The old heuristic budgeted
    only the input-transform block, 2*bt*64*bc*4 bytes, and silently
    overflowed VMEM through the weight block and tuple-multiply scratch.)
    The channel blocks shrink first (they are what the weight block is
    quadratic in), then the tile block; nothing shrinks below the
    (sublane, lane) granularity floor (8, 128, 128).
    """
    budget = vmem_budget if vmem_budget is not None else V5E.vmem_bytes
    bt = min(ceil_to(t, 8), 256)
    bc = min(ceil_to(c, 128), 512)
    bo = min(ceil_to(o, 128), 512)

    def fits() -> bool:
        return winograd_kernel_vmem_bytes(
            bt, bc, bo, dtype_bytes=dtype_bytes
        ) <= budget

    # Shrink in granularity multiples: halving a non-power-of-two start
    # (e.g. bc = ceil_to(384, 128)) must land back on a 128-lane multiple,
    # never below the (8, 128, 128) floor.
    while not fits() and (bc > 128 or bo > 128):
        if bc >= bo and bc > 128:
            bc = max(128, ceil_to(bc // 2, 128))
        else:
            bo = max(128, ceil_to(bo // 2, 128))
    while not fits() and bt > 8:
        bt = max(8, ceil_to(bt // 2, 8))
    return bt, bc, bo


class WinogradTiling(NamedTuple):
    """Where a Winograd layer cuts its 8x8 tiles, and how many it computes.

    ``nhwc`` is the kernel's block in VMEM: (bb images, k tile rows, ntw
    tile columns).  ``computed`` counts the tiles the kernel computes,
    padding included; ``real`` is B*nTH*nTW."""

    nhwc: Tuple[int, int, int]
    computed: int
    real: int

    @property
    def ratio(self) -> float:
        return self.computed / self.real


def winograd_tiling(
    b: int, oh: int, ow: int, blocks: Tuple[int, int, int],
    vmem_budget: Optional[int] = None,
) -> WinogradTiling:
    """The tiling of a Winograd layer with a (b, oh, ow) output.

    The kernel cuts its tiles in VMEM, in (bb, k, ntw) blocks that
    ``winograd_nhwc_blocks`` picks for the plan's channel blocks under
    ``vmem_budget`` (the planner's; None: the chip's VMEM).  Padding the
    tile columns to the 8-sublane granule costs 5% at 224² (38 -> 40) and
    60% at 28² and 56² (5 -> 8, 10 -> 16).
    """
    _, bc, bo = blocks
    nth, ntw = -(-oh // OUT_TILE), -(-ow // OUT_TILE)
    real = b * nth * ntw
    nhwc = winograd_nhwc_blocks(b, nth, ntw, bc, bo, vmem_budget=vmem_budget)
    _, k, nw = nhwc
    return WinogradTiling(nhwc, b * ceil_to(nth, k) * ceil_to(ntw, nw), real)


def conv2d_winograd_padded_call(
    x: jnp.ndarray,
    u_p: jnp.ndarray,
    oh: int,
    ow: int,
    blocks: Tuple[int, int, int],
    interpret: bool = False,
    bias_p: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    padding: Tuple[int, int] = (0, 0),
    vmem_budget: Optional[int] = None,
) -> jnp.ndarray:
    """The Winograd compute stages on channel-pre-padded operands.

    ``x`` (B, H, W, Cp) has its channels padded to the bc multiple;
    ``padding`` is the conv's spatial (ph, pw), applied here ((0, 0) for an
    ``x`` that already carries it).  ``u_p`` (8, 8, Cp, Op) is the
    pre-transformed weight padded to the same channel blocks, and ``bias_p``
    (1, Op) or None.  The kernel cuts the tiles in VMEM
    (``winograd_vmem_tiled_call``, blocks from ``winograd_tiling`` under
    ``vmem_budget``).  The spatial padding and the tile-count padding are
    intra-layer data movement and stay here; the *channel* pad/crop pair is
    what the network executor (core/netplan.py) elides between consecutive
    layers.  Returns (B, OH, OW, Op): rows/cols
    cut to logical (the 6-multiple tail rows carry act(bias), never zeros,
    so they must not flow on), channels kept padded for the caller to crop
    — or to hand straight to the next layer.
    """
    tiling = winograd_tiling(x.shape[0], oh, ow, blocks,
                             vmem_budget=vmem_budget)
    return winograd_vmem_tiled_call(
        x, u_p, oh, ow, tiling.nhwc, blocks[1:], interpret=interpret,
        bias_p=bias_p, activation=activation, padding=padding,
    )


def winograd_vmem_tiled_call(
    x: jnp.ndarray,
    u_p: jnp.ndarray,
    oh: int,
    ow: int,
    nhwc: Tuple[int, int, int],
    channel_blocks: Tuple[int, int],
    interpret: bool = False,
    bias_p: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    padding: Tuple[int, int] = (0, 0),
) -> jnp.ndarray:
    """The fused kernel tiling in VMEM with (bb, k, ntw) = ``nhwc`` blocks:
    one pad of ``x`` by the conv's ``padding`` and the tile tail, to
    6 nTH + 2 rows and 6 nTW + 8 columns (nTH, nTW rounded up to the k and
    ntw multiples), then the kernel, which writes (B, OH, OW, Op).  The
    kernel's strided sublane access is 32-bit only, so a narrower ``x`` is
    widened to fp32 in the pad and the result narrowed back; the kernel
    computes in fp32 either way."""
    from repro.kernels.winograd.kernel import fused_winograd_nhwc_pallas

    bb, k, nw = nhwc
    bc, bo = channel_blocks
    ph, pw = padding
    _, h, w, cp = x.shape
    assert cp % bc == 0 and u_p.shape[-1] % bo == 0, (cp, bc, u_p.shape, bo)
    nth = ceil_to(-(-oh // OUT_TILE), k)
    ntw = ceil_to(-(-ow // OUT_TILE), nw)
    dtype = x.dtype
    x = jnp.pad(x.astype(jnp.float32),
                ((0, 0), (ph, OUT_TILE * nth + 2 - h - ph),
                 (pw, OUT_TILE * ntw + 8 - w - pw), (0, 0)))
    y = fused_winograd_nhwc_pallas(
        x, u_p, oh, ow, bb, k, nw, bc, bo, interpret=interpret, bias=bias_p,
        activation=activation,
    )
    return y.astype(dtype)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "blocks", "interpret", "pretransformed",
                     "activation", "vmem_budget"),
)
def conv2d_winograd_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    blocks: Optional[Tuple[int, int, int]] = None,
    pretransformed: bool = False,
    interpret: bool = False,
    bias: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    vmem_budget: Optional[int] = None,
) -> jnp.ndarray:
    """x (B,H,W,C), w (3,3,C,O) [or (8,8,C,O) pretransformed] -> (B,OH,OW,O).

    Runs the megakernel: one pallas_call that cuts its tiles from the NHWC
    activation in VMEM and whose V and M intermediates stay there, in blocks
    sized to ``vmem_budget`` (None: the chip's VMEM).

    ``bias`` (O,) and ``activation`` form the fused epilogue, applied on the
    fp32 accumulator after the inverse transform, before the store."""
    assert spec.kernel_size == (3, 3) and spec.stride == (1, 1)
    b, h, ww, c = x.shape
    o = w.shape[-1]
    oh, ow = spec.out_hw(h, ww)
    nth, ntw = -(-oh // OUT_TILE), -(-ow // OUT_TILE)
    t = b * nth * ntw
    bt, bc, bo = blocks or pick_blocks(
        t, c, o, vmem_budget=vmem_budget,
        dtype_bytes=jnp.dtype(x.dtype).itemsize,
    )
    cp, op = ceil_to(c, bc), ceil_to(o, bo)
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cp - c)))

    u = w if pretransformed else transform_weights(w, x.dtype)  # (8,8,C,O)
    u = jnp.pad(u, ((0, 0), (0, 0), (0, cp - c), (0, op - o)))

    bias_p = pad_bias_row(bias, op)

    y = conv2d_winograd_padded_call(
        x, u, oh, ow, (bt, bc, bo), interpret=interpret,
        bias_p=bias_p, activation=activation,
        padding=spec.padding, vmem_budget=vmem_budget,
    )
    return y[:, :, :, :o]


def winograd_call_descriptors(
    b: int, oh: int, ow: int, cp: int, op: int,
    blocks: Tuple[int, int, int], bias: bool = True,
    vmem_budget: Optional[int] = None,
) -> list:
    """Static description of the pallas_call ``conv2d_winograd_padded_call``
    emits for a (b, oh, ow) output on (cp, op)-channel-padded operands: the
    megakernel, tiled in VMEM at the blocks ``winograd_tiling`` picks under
    ``vmem_budget``, fp32 whatever the activation dtype (see
    ``winograd_vmem_tiled_call``).
    Traffic follows the verifier's fetch algebra (an operand re-fetches once
    per step of the grid prefix its index map depends on).
    ``model_vmem_bytes`` is ``winograd_kernel_vmem_bytes`` over the buffers
    alone (the compiler's internal scratch is not visible in a trace), an
    upper bound (the input window is smaller than an (8, 8, bt, bc) tile
    block) — so actuals are compared one-sided (``vmem_one_sided``).
    """
    _, bc, bo = blocks
    tiling = winograd_tiling(b, oh, ow, blocks, vmem_budget=vmem_budget)
    nc, no = cp // bc, op // bo
    # Kernel-interior contract: the Cin grid axis (innermost) is the
    # reduction, accumulated in the (8, 8, bb*k*ntw, bo) fp32 M scratch.
    # Winograd never runs int8 (quantization policy), so no k_elems.
    d = 4
    bb, k, nw = tiling.nhwc
    te = bb * k * nw
    nhw = tiling.computed // te                   # image x row x col blocks
    window = bb * (6 * k + 2) * (6 * nw + 8) * bc
    traffic = (
        d * nhw * no * nc * (window + 64 * bc * bo)   # x + U
        + (ACC_BYTES * nhw * no * bo if bias else 0)  # bias rows
        + d * nhw * no * 36 * te * bo                 # output
    )
    name = ("_fused_winograd_nhwc_bias_kernel" if bias
            else "_fused_winograd_nhwc_kernel")
    return [{
        "family": "winograd",
        "name": name,
        "grid": (b // bb, ceil_to(-(-oh // 6), k) // k,
                 ceil_to(-(-ow // 6), nw) // nw, no, nc),
        "model_vmem_bytes": winograd_kernel_vmem_bytes(
            te, bc, bo, dtype_bytes=d, internal=False),
        "traffic_bytes": traffic,
        "vmem_one_sided": True,
        "reduction_axes": (4,),
        "k_elems": None,
    }]
