"""Blocked GEMM Pallas kernels — the paper's 3-loop / 6-loop GEMMs on TPU.

The 6-loop BLIS mapping (paper Fig. 3 -> TPU):
  - j1/i1/k1 cache-blocking loops  -> the pallas grid (nm, nn, nk)
  - packing of A/B panels          -> implicit HBM->VMEM block copies
                                      (hardware-tiled, contiguous)
  - prefetch into L1/L2            -> Pallas software pipelining
                                      (next block DMA overlaps compute)
  - micro-kernel (vfmacc chain)    -> one MXU `jnp.dot` per block step,
                                      fp32 accumulation in VMEM scratch
  - unroll factor / vector length  -> block shape (bm, bn)

The 3-loop variant (paper Fig. 2) streams the full K panel per output
block: no K-grid, no accumulator scratch.  The co-design study
(core/codesign.py) decides which wins for a given shape + VMEM budget —
reproducing the paper's "optimizations are not portable" finding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_spec import apply_activation
from repro.util import dot_precision


def _accumulate_k_block(a_ref, b_ref, acc_ref):
    """Shared 6-loop body: zero the VMEM accumulator on the first K step,
    then add this (bm, bk) x (bk, bn) block product."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=dot_precision(a_ref.dtype),
    )


def _matmul_kernel_6loop(a_ref, b_ref, c_ref, acc_ref, *, activation: str):
    """Grid (nm, nn, nk), K innermost: accumulate A@B blocks in VMEM."""
    _accumulate_k_block(a_ref, b_ref, acc_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        # Fused epilogue on the VMEM-resident fp32 accumulator (paper §IV.A:
        # absorb adjacent data movement into the micro-kernel's output stage).
        c_ref[...] = apply_activation(acc_ref[...], activation).astype(c_ref.dtype)


def _matmul_bias_kernel_6loop(a_ref, b_ref, bias_ref, c_ref, acc_ref, *,
                              activation: str):
    """6-loop variant with a fused (1, bn) bias row + activation epilogue."""
    _accumulate_k_block(a_ref, b_ref, acc_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        out = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        c_ref[...] = apply_activation(out, activation).astype(c_ref.dtype)


def _matmul_kernel_3loop(a_ref, b_ref, c_ref, *, activation: str):
    """Grid (nm, nn): one full-K panel per output block (paper Fig. 2)."""
    out = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
                  precision=dot_precision(a_ref.dtype))
    c_ref[...] = apply_activation(out, activation).astype(c_ref.dtype)


def _matmul_bias_kernel_3loop(a_ref, b_ref, bias_ref, c_ref, *, activation: str):
    """3-loop variant with a fused bias + activation epilogue."""
    out = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
                  precision=dot_precision(a_ref.dtype))
    out = out + bias_ref[...].astype(jnp.float32)
    c_ref[...] = apply_activation(out, activation).astype(c_ref.dtype)


# --- int8 variants -----------------------------------------------------------
# Same loop structures, integer arithmetic: int8 x int8 blocks accumulate in
# int32 (MXU native rate is 2x bf16), and the write-back stage dequantizes —
# out = act(acc * scale + bias) — so the quantized GEMM still costs exactly
# one HBM round trip for C, now in fp32.  ``scale`` is the (1, bn) folded
# activation x weight scale row (core/quant.py); ``bias`` stays fp32.


def _accumulate_k_block_q8(a_ref, b_ref, acc_ref):
    """6-loop int8 body: int32 VMEM accumulator over the K grid axis."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.int32
    )


def _dequant_epilogue(acc, scale_ref, bias_ref, activation: str):
    """Fused dequant + bias + activation on the int32 accumulator."""
    out = acc.astype(jnp.float32) * scale_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        out = out + bias_ref[...].astype(jnp.float32)
    return apply_activation(out, activation)


def _matmul_q8_kernel_6loop(a_ref, b_ref, scale_ref, c_ref, acc_ref, *,
                            activation: str):
    _accumulate_k_block_q8(a_ref, b_ref, acc_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        out = _dequant_epilogue(acc_ref[...], scale_ref, None, activation)
        c_ref[...] = out.astype(c_ref.dtype)


def _matmul_q8_bias_kernel_6loop(a_ref, b_ref, scale_ref, bias_ref, c_ref,
                                 acc_ref, *, activation: str):
    _accumulate_k_block_q8(a_ref, b_ref, acc_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        out = _dequant_epilogue(acc_ref[...], scale_ref, bias_ref, activation)
        c_ref[...] = out.astype(c_ref.dtype)


def _matmul_q8_kernel_3loop(a_ref, b_ref, scale_ref, c_ref, *, activation: str):
    acc = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.int32)
    out = _dequant_epilogue(acc, scale_ref, None, activation)
    c_ref[...] = out.astype(c_ref.dtype)


def _matmul_q8_bias_kernel_3loop(a_ref, b_ref, scale_ref, bias_ref, c_ref, *,
                                 activation: str):
    acc = jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.int32)
    out = _dequant_epilogue(acc, scale_ref, bias_ref, activation)
    c_ref[...] = out.astype(c_ref.dtype)


def matmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    bm: int,
    bn: int,
    bk: int,
    variant: str = "6loop",
    out_dtype=None,
    interpret: bool = False,
    bias=None,
    activation: str = "linear",
    scale=None,
) -> jnp.ndarray:
    """Blocked matmul; dims must already be padded to block multiples.

    ``bias`` (1, N) and ``activation`` form the fused epilogue, applied to
    the fp32 accumulator in the output stage (no extra HBM round trip).

    Passing ``scale`` (1, N) selects the int8 path: ``a``/``b`` must be
    int8, accumulation is int32, and the epilogue dequantizes —
    act(acc * scale + bias) — writing ``out_dtype`` (defaults to fp32).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bias is None or bias.shape == (1, n), (n, getattr(bias, "shape", None))
    quantized = scale is not None
    if quantized:
        assert a.dtype == jnp.int8 and b.dtype == jnp.int8, (a.dtype, b.dtype)
        assert scale.shape == (1, n), (n, scale.shape)
        out_dtype = out_dtype or jnp.float32
    else:
        out_dtype = out_dtype or a.dtype
    out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
    extras = (() if scale is None else (scale,)) + (
        () if bias is None else (bias,)
    )

    if variant == "3loop":
        if quantized:
            body = (_matmul_q8_bias_kernel_3loop if bias is not None
                    else _matmul_q8_kernel_3loop)
        else:
            body = (_matmul_bias_kernel_3loop if bias is not None
                    else _matmul_kernel_3loop)
        kern = functools.partial(body, activation=activation)
        in_specs = [
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
        ]
        for _ in extras:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        return pl.pallas_call(
            kern,
            grid=(m // bm, n // bn),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
            interpret=interpret,
        )(a, b, *extras)

    if quantized:
        body = (_matmul_q8_bias_kernel_6loop if bias is not None
                else _matmul_q8_kernel_6loop)
    else:
        body = (_matmul_bias_kernel_6loop if bias is not None
                else _matmul_kernel_6loop)
    kern = functools.partial(body, activation=activation)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    for _ in extras:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32 if quantized else jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b, *extras)
