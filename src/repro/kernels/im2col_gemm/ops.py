"""Jitted wrapper for the fused im2col+GEMM conv kernel, and tap folding.

Pads input/weights to HW-aligned block multiples, picks block sizes from the
co-design model (channel blocks sized so the *full* per-program footprint —
input slab, weight block, bias row, output block and accumulator — fits the
VMEM budget), runs the kernel, crops the output.

The pad/crop bookkeeping is split out of the jitted body
(`conv2d_im2col_padded_call` / the final channel crop) so the
network executor (core/netplan.py) can own the layer boundaries: a planned
network pads once at entry, flows block-padded activations between layers,
and crops once at exit.

Tap folding is the other realization of im2col+GEMM, for convs whose input
channels are narrower than a lane block: the fused kernel would multiply
every tap's channels padded to 128 lanes (a 3-channel RGB stem does 43x its
work), so the wrapper builds the (B*OH*OW, kh*kw*C) patch matrix once from
the logical channels and runs the blocked GEMM kernel (kernels/gemm) on it
with the weights folded to match.  K is one full-dimension block where it
fits the GEMM's K block, so XLA writes the matrix K-minor in one pass with
no pad; a deeper K is padded once to the lane width.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.conv_spec import ConvSpec
from repro.core.im2col import im2col
from repro.core.vmem_model import (
    ACC_BYTES,
    im2col_kernel_vmem_bytes,
    im2col_window,
)
from repro.hw import V5E
from repro.kernels.gemm.ops import matmul_padded_call
from repro.kernels.im2col_gemm.kernel import conv2d_im2col_gemm_pallas
from repro.util import ceil_to, pad_bias_row


def pick_blocks(
    oh: int, ow: int, c: int, o: int, kh: int = 3, kw: int = 3,
    sh: int = 1, sw: int = 1, dtype_bytes: int = 4,
    vmem_budget: Optional[int] = None, out_dtype_bytes: Optional[int] = None,
) -> Tuple[int, int, int]:
    """(toh, bc, bo): the largest row tile + channel blocks fitting VMEM.

    This is the conv-kernel instance of the paper's block-size tuning
    (Table II): the row window (toh + halo rows x Wp x bc) plays the role of
    the packed B panel, the accumulator (toh*OWp x bo) the role of the C
    block.  Budgets the **full** per-program footprint via
    ``vmem_model.im2col_kernel_vmem_bytes`` — tile-padded blocks, the
    weight block, the epilogue rows and Mosaic's internal scratch.  The
    channel blocks are lane-legal multiples of 128 (the wrappers pad
    narrower channel counts up, as the GEMM path does); the out-channel
    block shrinks first, then the row tile.
    """
    budget = vmem_budget if vmem_budget is not None else V5E.vmem_bytes
    bc = V5E.lane_width
    bo = min(ceil_to(o, V5E.lane_width), 256)
    toh = min(oh, 64)

    def fits() -> bool:
        return im2col_kernel_vmem_bytes(
            toh, ow, bc, bo, kh, kw, sh, sw, dtype_bytes,
            out_dtype_bytes=out_dtype_bytes,
        ) <= budget

    while not fits() and bo > V5E.lane_width:
        bo = max(V5E.lane_width, ceil_to(bo // 2, V5E.lane_width))
    while not fits() and toh > 1:
        toh = max(1, toh // 2)
    return toh, bc, bo


def phase_split(
    x: jnp.ndarray, spec: ConvSpec, oh: int, ow: int, toh: int
) -> jnp.ndarray:
    """(B, H, W, C) -> the kernel's (B, sh, Hq, sw*Wq, C) stride phases.

    Applies the conv's own spatial padding, pads (or crops) to exactly the
    rows and columns the row-tiled grid reads, and splits rows and columns
    into their stride phases: element [b, p, i, q*Wq + j] is padded
    x[b, i*sh + p, j*sw + q].  For a stride-1 conv the split is a reshape.
    Spatial axes only — the channel axis passes through untouched.
    """
    b, h, w, c = x.shape
    sh, sw = spec.stride
    ph, pw = spec.padding
    owp, rows_in, wq = im2col_window(toh, ow, spec.kh, spec.kw, sh, sw)
    hq = ceil_to(oh, toh) + rows_in - toh
    hn, wn = hq * sh, wq * sw
    pads = ((0, 0), (ph, max(hn - h - ph, 0)), (pw, max(wn - w - pw, 0)),
            (0, 0))
    if any(p != (0, 0) for p in pads):
        x = jnp.pad(x, pads)
    if x.shape[1] != hn or x.shape[2] != wn:
        x = x[:, :hn, :wn]
    if (sh, sw) == (1, 1):
        return x.reshape(b, 1, hn, wn, c)
    x = x.reshape(b, hq, sh, wq, sw, c).transpose(0, 2, 1, 4, 3, 5)
    return x.reshape(b, sh, hq, sw * wq, c)


def conv2d_im2col_padded_call(
    x_p: jnp.ndarray,
    w_p: jnp.ndarray,
    spec: ConvSpec,
    oh: int,
    ow: int,
    blocks: Tuple[int, int, int],
    out_dtype=None,
    interpret: bool = False,
    bias_p: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    scale_p: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """The kernel call on channel-padded operands; returns (B, OH, OW, Op).

    ``x_p`` (B, H, W, Cp) carries channels padded to the bc multiple but no
    spatial padding; ``w_p``/``bias_p`` must be padded to the same channel
    blocks.  ``scale_p`` (1, Op) selects the int8 dequant path (see
    kernel.py).  The stride-phase split and the row/column tails of the
    row-tiled grid are intra-layer movement and stay here; the channel
    crop belongs to the caller (public wrapper or network executor).
    """
    toh, bc, bo = blocks
    toh = min(toh, oh)
    sh, sw = spec.stride
    out = conv2d_im2col_gemm_pallas(
        phase_split(x_p, spec, oh, ow, toh), w_p, sh, sw, oh,
        ceil_to(ow, V5E.sublanes), toh, bc, bo,
        out_dtype=out_dtype, interpret=interpret,
        bias=bias_p, activation=activation, scale=scale_p,
    )
    if out.shape[1:3] != (oh, ow):
        out = out[:, :oh, :ow]
    return out


def folded_k(spec: ConvSpec, cin: int, bk: int) -> int:
    """The folded contraction depth at GEMM K block ``bk``: the kh*kw*cin
    taps of one output pixel, one full-dimension block when ``bk`` is that
    depth (147 for a 7x7 RGB stem), else padded to the lane width."""
    k = spec.kh * spec.kw * cin
    return k if bk == k else ceil_to(k, V5E.lane_width)


def fold_weights(w: jnp.ndarray, kp: int) -> jnp.ndarray:
    """(kh, kw, C, O) -> (kp, O): one row per tap and channel, in the
    (kh, kw, c) order ``core.im2col.im2col`` gives the patch columns, with
    zero rows to the folded depth ``kp``."""
    kh, kw, c, o = w.shape
    k = kh * kw * c
    return jnp.pad(w.reshape(k, o), ((0, kp - k), (0, 0)))


def conv2d_folded_call(
    x: jnp.ndarray,
    w_f: jnp.ndarray,
    spec: ConvSpec,
    blocks: Tuple[int, int, int],
    interpret: bool = False,
    bias: Optional[jnp.ndarray] = None,
    activation: str = "linear",
) -> jnp.ndarray:
    """Tap folding: x (B, H, W, C) at its logical channels and ``w_f`` from
    ``fold_weights`` -> (B, OH, OW, Np), Np the out channels rounded up to
    the GEMM's ``bn``.

    The patch matrix is built once (unit-stride slices of the input's
    stride phases), its K and M tails padded, and the blocked GEMM kernel
    runs it with the fused bias + activation epilogue.  ``blocks`` are the
    GEMM's (bm, bn, bk); bk is the folded K or divides it.  The channel
    crop belongs to the caller.
    """
    b, h, w, c = x.shape
    oh, ow = spec.out_hw(h, w)
    bm, bn, _ = blocks
    m, k = b * oh * ow, spec.kh * spec.kw * c
    kp, n = w_f.shape
    np_ = ceil_to(n, bn)
    if np_ != n:
        w_f = jnp.pad(w_f, ((0, 0), (0, np_ - n)))
    a = im2col(x, spec.kernel_size, spec.stride, spec.padding,
               spec.dilation).reshape(m, k)
    mp = ceil_to(m, bm)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    out = matmul_padded_call(
        a, w_f, blocks, interpret=interpret,
        bias_p=pad_bias_row(bias, np_), activation=activation,
    )
    if mp != m:
        out = out[:m]
    return out.reshape(b, oh, ow, np_)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "blocks", "interpret", "out_dtype", "activation"),
)
def conv2d_pallas_im2col(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    blocks: Optional[Tuple[int, int, int]] = None,
    out_dtype=None,
    interpret: bool = False,
    bias: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Fused-conv entry point: x (B,H,W,C), w (kh,kw,C,O) -> (B,OH,OW,O).

    ``bias`` (O,) and ``activation`` form the fused epilogue, applied inside
    the kernel's output stage (see kernel.py).  ``scale`` (O,) selects the
    int8 dequant path: int8 x/w, int32 accumulation, fp32 output."""
    _, h, ww, c = x.shape
    kh, kw, _, o = w.shape
    oh, ow = spec.out_hw(h, ww)
    sh, sw = spec.stride

    blocks = blocks or pick_blocks(
        oh, ow, c, o, kh, kw, sh, sw, jnp.dtype(x.dtype).itemsize,
    )
    _, bc, bo = blocks
    cp, op = ceil_to(c, bc), ceil_to(o, bo)
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cp - c)))
    w_p = jnp.pad(w, ((0, 0), (0, 0), (0, cp - c), (0, op - o)))
    out = conv2d_im2col_padded_call(
        x, w_p, spec, oh, ow, blocks,
        out_dtype=out_dtype, interpret=interpret,
        bias_p=pad_bias_row(bias, op), activation=activation,
        scale_p=pad_bias_row(scale, op),
    )
    return out[..., :o]


def im2col_call_descriptor(
    h: int, w: int, spec: ConvSpec, blocks: Tuple[int, int, int],
    cp: int, op: int, batch: int = 1, dtype_bytes: int = 4,
    bias: bool = True, scale: bool = False,
) -> dict:
    """Static description of the pallas_call ``conv2d_im2col_padded_call``
    emits for a (batch, h, w, cp) activation already channel-padded to the
    bc multiple, against weights padded to (cp, op).

    The verifier's expected side: kernel body name, grid, modeled VMEM
    footprint (``vmem_model.im2col_kernel_vmem_bytes``, buffers only — the
    compiler-internal allowance is not visible in a trace) and the modeled
    HBM traffic from the block/grid fetch algebra — the row window and
    weight block re-fetch on every grid step (their index maps touch the
    innermost in-channel axis), the epilogue rows once per (batch, row,
    out-channel) step, the output once per block.
    """
    oh, ow = spec.out_hw(h, w)
    sh, sw = spec.stride
    toh, bc, bo = blocks
    eff_toh = min(toh, oh)
    ohp = ceil_to(oh, eff_toh)
    owp, rows_in, wq = im2col_window(eff_toh, ow, spec.kh, spec.kw, sh, sw)
    grid = (batch, ohp // eff_toh, op // bo, cp // bc)
    nsteps = batch * (ohp // eff_toh) * (op // bo)
    full = nsteps * (cp // bc)
    rows = int(scale) + int(bias)
    out_bytes = ACC_BYTES if dtype_bytes == 1 else dtype_bytes
    traffic = (
        dtype_bytes * full * (
            sh * rows_in * sw * wq * bc + spec.kh * spec.kw * bc * bo
        )
        + ACC_BYTES * rows * nsteps * bo          # epilogue rows
        + out_bytes * nsteps * eff_toh * owp * bo  # output blocks
    )
    name = (
        "_conv" + ("_q8" if scale else "") + ("_bias" if bias else "")
        + "_kernel"
    )
    return {
        "family": "im2col",
        "name": name,
        "grid": grid,
        "model_vmem_bytes": im2col_kernel_vmem_bytes(
            eff_toh, ow, bc, bo, spec.kh, spec.kw, sh, sw, dtype_bytes,
            bias=bias or scale, internal=False,
        ),
        "traffic_bytes": traffic,
        "vmem_one_sided": False,
        # Kernel-interior contract: the in-channel grid axis (innermost) is
        # the K reduction, accumulated in VMEM scratch; the full reduction
        # depth spans every tap of every padded in-channel — the quantity
        # the int8 overflow pass certifies.
        "reduction_axes": (3,),
        "k_elems": spec.kh * spec.kw * cp,
    }
