"""Jitted end-to-end Winograd conv on the Pallas kernels.

Pipeline (paper §IV.B):  tile -> input transform -> tuple multiply ->
output transform -> untile.  The overlapping 8x8 tile extraction and the
offline weight transform are plain XLA data-movement ops.  The compute
stages run either as the single-pass fused megakernel (``fused=True``, the
default: transforms and M accumulation never leave VMEM) or as the 3-pass
kernel pipeline whose V/M intermediates round-trip through HBM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.conv_spec import ConvSpec
from repro.core.vmem_model import winograd_kernel_vmem_bytes
from repro.core.winograd import OUT_TILE, TILE, _tile_input, transform_weights
from repro.hw import V5E
from repro.util import ceil_to, pad_bias_row


def pick_blocks(
    t: int, c: int, o: int, vmem_budget: Optional[int] = None,
    fused: bool = True, dtype_bytes: int = 4,
) -> Tuple[int, int, int]:
    """(bt, bc, bo) aligned to (sublane, lane) granularity, VMEM-bounded.

    Budgets the **full** per-kernel footprint via
    ``vmem_model.winograd_kernel_vmem_bytes`` — for the fused megakernel the
    double-buffered tile + weight blocks, the (8, 8, bt, bo) fp32 M
    accumulator scratch and the output block; for the 3-pass pipeline the
    max footprint across its three kernels.  (The old heuristic budgeted
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
            bt, bc, bo, fused=fused, dtype_bytes=dtype_bytes
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


def conv2d_winograd_padded_call(
    x_sp: jnp.ndarray,
    u_p: jnp.ndarray,
    oh: int,
    ow: int,
    blocks: Tuple[int, int, int],
    interpret: bool = False,
    bias_p: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    fused: bool = True,
) -> jnp.ndarray:
    """The Winograd compute stages on channel-pre-padded operands.

    ``x_sp`` (B, H+2ph, W+2pw, Cp) already carries the conv's spatial
    padding and channels padded to the bc multiple; ``u_p`` (8, 8, Cp, Op)
    is the pre-transformed weight padded to the same channel blocks, and
    ``bias_p`` (1, Op) or None.  The overlapping-tile extraction and the
    tile-count padding to the bt multiple are intra-layer data movement and
    stay here; the *channel* pad/crop pair is what the network executor
    (core/netplan.py) elides between consecutive layers.  Returns
    (B, OH, OW, Op): rows/cols cropped to logical (the 6-multiple tail rows
    carry act(bias), never zeros, so they must not flow on), channels kept
    padded for the caller to crop — or to hand straight to the next layer.
    """
    from repro.kernels.winograd.kernel import (
        fused_winograd_pallas,
        input_transform_pallas,
        output_transform_pallas,
        tuple_multiply_pallas,
    )

    b = x_sp.shape[0]
    cp = x_sp.shape[-1]
    op = u_p.shape[-1]
    bt, bc, bo = blocks
    assert cp % bc == 0 and op % bo == 0, (cp, bc, op, bo)

    tiles, nth, ntw = _tile_input(x_sp, oh, ow)  # (B, nTH, nTW, 8, 8, Cp)
    t = b * nth * ntw
    # Position-major (8, 8, T, Cp): tiles on sublanes, channels on lanes.
    tiles = tiles.transpose(3, 4, 0, 1, 2, 5).reshape(TILE, TILE, t, cp)
    tp = ceil_to(t, bt)
    if tp != t:
        tiles = jnp.pad(tiles, ((0, 0), (0, 0), (0, tp - t), (0, 0)))

    if fused:
        y = fused_winograd_pallas(
            tiles, u_p, bt, bc, bo, interpret=interpret,
            bias=bias_p, activation=activation,
        )  # (6, 6, tp, op)
    else:
        v = input_transform_pallas(tiles, bt, bc, interpret=interpret)
        v = v.reshape(TILE * TILE, tp, cp)
        m = tuple_multiply_pallas(
            v, u_p.reshape(TILE * TILE, cp, op), bt, bc, bo,
            interpret=interpret,
        )
        y = output_transform_pallas(
            m.reshape(TILE, TILE, tp, op), bt, bo, interpret=interpret,
            bias=bias_p, activation=activation,
        )  # (6, 6, tp, op)

    y = y[:, :, :t].reshape(OUT_TILE, OUT_TILE, b, nth, ntw, op)
    y = y.transpose(2, 3, 0, 4, 1, 5).reshape(
        b, nth * OUT_TILE, ntw * OUT_TILE, op
    )
    return y[:, :oh, :ow, :]


@functools.partial(
    jax.jit,
    static_argnames=("spec", "blocks", "interpret", "pretransformed",
                     "activation", "fused"),
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
    fused: bool = True,
) -> jnp.ndarray:
    """x (B,H,W,C), w (3,3,C,O) [or (8,8,C,O) pretransformed] -> (B,OH,OW,O).

    ``fused=True`` (default) runs the single-pass megakernel: one
    pallas_call whose grid is (T/bt, O/bo, C/bc) and whose V and M
    intermediates stay in VMEM.  ``fused=False`` runs the 3-pass pipeline
    (input transform -> tuple multiply -> output transform), each stage a
    separate kernel with (64, T, C)-shaped HBM intermediates — kept for
    measure-mode comparison and as the reference realization of the paper's
    decomposition.

    ``bias`` (O,) and ``activation`` form the fused epilogue, applied on the
    fp32 accumulator after the inverse transform, before the store."""
    assert spec.kernel_size == (3, 3) and spec.stride == (1, 1)
    b, h, ww, c = x.shape
    o = w.shape[-1]
    oh, ow = spec.out_hw(h, ww)
    ph, pw = spec.padding
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))

    nth, ntw = -(-oh // OUT_TILE), -(-ow // OUT_TILE)
    t = b * nth * ntw
    bt, bc, bo = blocks or pick_blocks(
        t, c, o, fused=fused, dtype_bytes=jnp.dtype(x.dtype).itemsize
    )
    cp, op = ceil_to(c, bc), ceil_to(o, bo)
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cp - c)))

    u = w if pretransformed else transform_weights(w, x.dtype)  # (8,8,C,O)
    u = jnp.pad(u, ((0, 0), (0, 0), (0, cp - c), (0, op - o)))

    bias_p = pad_bias_row(bias, op)

    y = conv2d_winograd_padded_call(
        x, u, oh, ow, (bt, bc, bo), interpret=interpret,
        bias_p=bias_p, activation=activation, fused=fused,
    )
    return y[:, :, :, :o]


def winograd_call_descriptors(
    t: int, cp: int, op: int, blocks: Tuple[int, int, int],
    bias: bool = True, fused: bool = True, dtype_bytes: int = 4,
) -> list:
    """Static description of the pallas_call(s) ``conv2d_winograd_padded_call``
    emits for ``t`` logical tiles on (cp, op)-channel-padded operands.

    One descriptor for the fused megakernel, three (input transform, tuple
    multiply, output transform) for the 3-pass pipeline.  Traffic follows
    the verifier's fetch algebra (an operand re-fetches once per step of the
    grid prefix its index map depends on).  ``model_vmem_bytes`` is
    ``winograd_kernel_vmem_bytes`` over the buffers alone (the compiler's
    internal scratch is not visible in a trace), which for the 3-pass
    pipeline is the *max* over stages — per-stage actuals are compared
    one-sided (``vmem_one_sided``).
    """
    from repro.core.vmem_model import ACC_BYTES, winograd_kernel_vmem_bytes

    bt, bc, bo = blocks
    tp = ceil_to(t, bt)
    nt, nc, no = tp // bt, cp // bc, op // bo
    model = winograd_kernel_vmem_bytes(
        bt, bc, bo, fused=fused, dtype_bytes=dtype_bytes, internal=False,
    )
    if fused:
        traffic = (
            dtype_bytes * nt * no * nc * 64 * bc * (bt + bo)  # tiles + U
            + (ACC_BYTES * nt * no * bo if bias else 0)       # bias rows
            + dtype_bytes * tp * 36 * op                      # output write
        )
        name = "_fused_winograd_bias_kernel" if bias else "_fused_winograd_kernel"
        return [{
            "family": "winograd",
            "name": name,
            "grid": (nt, no, nc),
            "model_vmem_bytes": model,
            "traffic_bytes": traffic,
            "vmem_one_sided": False,
            # Kernel-interior contract: the Cin grid axis (innermost) is the
            # reduction, accumulated in the (8, 8, bt, bo) fp32 M scratch.
            # Winograd never runs int8 (quantization policy), so no k_elems.
            "reduction_axes": (2,),
            "k_elems": None,
        }]
    input_tf = {
        "family": "winograd",
        "name": "_input_transform_kernel",
        "grid": (nt, nc),
        "model_vmem_bytes": model,
        "traffic_bytes": dtype_bytes * 2 * nt * nc * 64 * bt * bc,
        "vmem_one_sided": True,
        "reduction_axes": (),
        "k_elems": None,
    }
    tuple_mul = {
        "family": "winograd",
        "name": "_tuple_multiply_kernel",
        "grid": (64, nt, no, nc),
        "model_vmem_bytes": model,
        "traffic_bytes": dtype_bytes * 64 * nt * no * nc * bc * (bt + bo)
        + dtype_bytes * 64 * nt * no * bt * bo,
        "vmem_one_sided": True,
        # The per-position GEMM reduces over the in-channel grid axis
        # (innermost) into the (bt, bo) fp32 scratch.
        "reduction_axes": (3,),
        "k_elems": None,
    }
    output_tf = {
        "family": "winograd",
        "name": (
            "_output_transform_bias_kernel" if bias
            else "_output_transform_kernel"
        ),
        "grid": (nt, no),
        "model_vmem_bytes": model,
        "traffic_bytes": dtype_bytes * nt * no * (64 + 36) * bt * bo
        + (ACC_BYTES * nt * no * bo if bias else 0),
        "vmem_one_sided": True,
        "reduction_axes": (),
        "k_elems": None,
    }
    return [input_tf, tuple_mul, output_tf]
