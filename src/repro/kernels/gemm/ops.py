"""Jitted public wrapper around the blocked GEMM kernels.

Handles HW-alignment padding (the TPU analogue of the paper's loop-tail /
`vsetvl` handling: we pad to block multiples instead of predicating) and
block autotuning via the co-design model when no block is given.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.vmem_model import (
    ACC_BYTES,
    BlockConfig,
    GemmShape,
    autotune_gemm,
    gemm_kernel_vmem_bytes,
)
from repro.hw import V5E
from repro.kernels.gemm.kernel import matmul_pallas
from repro.util import ceil_to, pad_bias_row


def default_block(m: int, n: int, k: int, dtype_bytes: int = 4) -> BlockConfig:
    """Autotuned block for this shape under the v5e VMEM budget, clamped to
    the (padded) problem so tiny test shapes don't over-pad."""
    cfg, _ = autotune_gemm(GemmShape(m, n, k), V5E, dtype_bytes=dtype_bytes)
    bm = min(cfg.bm, ceil_to(m, 8))
    bn = min(cfg.bn, ceil_to(n, 128))
    bk = min(cfg.bk, ceil_to(k, 128))
    return BlockConfig(bm, bn, bk)


def pad_gemm_operands(
    a: jnp.ndarray,
    b: jnp.ndarray,
    block: Tuple[int, int, int],
    bias: Optional[jnp.ndarray] = None,
):
    """Block-align (a, b, bias) for ``matmul_padded_call``.

    Runs under the caller's jit.  Split out of ``blocked_matmul`` so the
    network executor (core/netplan.py) can skip it when the operands already
    satisfy the planned layout (pre-padded activations / offline-padded
    weights) and no pad ops enter the jaxpr at the layer boundary.
    """
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = block
    mp, np_, kp = ceil_to(m, bm), ceil_to(n, bn), ceil_to(k, bk)
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else a
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp, np_) != (k, n) else b
    bias_p = pad_bias_row(bias, np_)
    return a_p, b_p, bias_p


def matmul_padded_call(
    a_p: jnp.ndarray,
    b_p: jnp.ndarray,
    block: Tuple[int, int, int],
    variant: str = "6loop",
    out_dtype=None,
    interpret: bool = False,
    bias_p: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    scale_p: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """The kernel call on block-aligned operands: no padding, no cropping.

    a_p (Mp, Kp), b_p (Kp, Np) with Mp % bm == Kp % bk == Np % bn == 0;
    bias_p (1, Np) or None; scale_p (1, Np) selects the int8 dequant path.
    Returns the raw (Mp, Np) kernel output — the caller owns any crop back
    to logical dims.
    """
    bm, bn, bk = block
    if variant == "3loop":
        bk = a_p.shape[1]
    return matmul_pallas(
        a_p, b_p, bm, bn, bk, variant=variant, out_dtype=out_dtype,
        interpret=interpret, bias=bias_p, activation=activation,
        scale=scale_p,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block", "variant", "interpret", "out_dtype", "activation"),
)
def blocked_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    block: Optional[Tuple[int, int, int]] = None,
    variant: str = "6loop",
    out_dtype=None,
    interpret: bool = False,
    bias: Optional[jnp.ndarray] = None,
    activation: str = "linear",
    scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """C = act(A @ B + bias) with BLIS-like VMEM blocking.

    Args:
      a: (M, K); b: (K, N).
      block: (bm, bn, bk) or None to autotune (co-design model).
      variant: '6loop' (K-blocked, VMEM accumulation) or '3loop' (full-K
        panel per output block).
      bias: optional (N,) vector fused into the kernel's output stage.
      activation: 'linear' | 'relu' | 'leaky', fused likewise.
      scale: optional (N,) dequant row — int8 a/b, int32 accumulation,
        act(acc * scale + bias) epilogue writing fp32.
    """
    m, k = a.shape
    _, n = b.shape
    if block is None:
        cfg = default_block(m, n, k, jnp.dtype(a.dtype).itemsize)
        block = (cfg.bm, cfg.bn, cfg.bk)
    a_p, b_p, bias_p = pad_gemm_operands(a, b, block, bias=bias)
    scale_p = pad_bias_row(scale, b_p.shape[1])
    out = matmul_padded_call(
        a_p, b_p, block, variant=variant, out_dtype=out_dtype,
        interpret=interpret, bias_p=bias_p, activation=activation,
        scale_p=scale_p,
    )
    return out[:m, :n]


def gemm_call_descriptor(
    mp: int, np_: int, kp: int, block: Tuple[int, int, int],
    dtype_bytes: int = 4, bias: bool = False, scale: bool = False,
    variant: str = "6loop",
) -> dict:
    """Static description of the pallas_call ``matmul_padded_call`` emits.

    The verifier's expected side: for block-aligned operands (Mp, Kp) x
    (Kp, Np) it predicts the kernel body name, the grid, the modeled VMEM
    footprint and the modeled HBM traffic — the same fetch algebra the
    jaxpr-recovered actuals follow (an operand whose index map depends on
    grid axes up to ``a`` is re-fetched once per step of ``grid[:a+1]``).
    """
    bm, bn, bk = block
    if variant == "3loop":
        bk = kp
    nm, nn, nk = mp // bm, np_ // bn, kp // bk
    rows = int(scale) + int(bias)
    out_bytes = ACC_BYTES if dtype_bytes == 1 else dtype_bytes
    if variant == "3loop":
        grid = (nm, nn)
        traffic = (
            dtype_bytes * (mp * kp + nm * nn * kp * bn)       # A once, B per j
            + ACC_BYTES * rows * nm * nn * bn                 # epilogue rows
            + out_bytes * mp * np_                            # output write
        )
    else:
        grid = (nm, nn, nk)
        traffic = (
            dtype_bytes * nm * nn * nk * (bm * bk + bk * bn)  # A/B per step
            + ACC_BYTES * rows * nm * nn * bn                 # epilogue rows
            + out_bytes * mp * np_                            # output write
        )
    name = (
        "_matmul"
        + ("_q8" if scale else "")
        + ("_bias" if bias else "")
        + "_kernel_"
        + variant
    )
    return {
        "family": "gemm",
        "name": name,
        "grid": grid,
        "model_vmem_bytes": gemm_kernel_vmem_bytes(
            bm, bn, bk, dtype_bytes, epilogue_rows=rows,
            three_loop=variant == "3loop", internal=False,
        ),
        "traffic_bytes": traffic,
        "vmem_one_sided": False,
        # Kernel-interior contract (the verifier's `kernel` rung): the
        # 6-loop variant reduces over the K grid axis into VMEM scratch;
        # the 3-loop variant streams the full K panel (no reduction axis).
        # ``k_elems`` is the reduction depth the int8 overflow pass
        # certifies against the traced operand shapes.
        "reduction_axes": () if variant == "3loop" else (2,),
        "k_elems": kp,
    }
