"""Fused im2col+GEMM convolution Pallas kernel.

TPU adaptation of the paper's im2col+GEMM pipeline (§IV.A): instead of
materializing the (K x N) im2col matrix in HBM (what Darknet does), the
patch gather happens *inside* the kernel on the VMEM-resident input window —
i.e. im2col is fused into the GEMM the way the paper fuses packing into the
6-loop blocked GEMM.  Data layout is NHWC so channels ride the lane axis.

Grid: (batch, output-row tiles, out-channel blocks, in-channel blocks);
the in-channel grid axis is the K-reduction, accumulated in a VMEM
scratch.  The wrapper hands the kernel the input split into its stride
phases, (B, sh, Hq, sw*Wq, C) with element [b, p, i, q*Wq + j] =
x[b, i*sh + p, j*sw + q] (a reshape when the stride is 1), so every tap
reads a unit-stride window: no strided sublane loads, which Mosaic does not
lower for packed int8.  Each program DMAs only the rows its ``toh`` output
rows read — an element-offset window of toh + (kh-1)//sh rows per phase,
overlapping its neighbours by the halo — so the VMEM footprint is bounded
by the row tile, not the image.  For each of the kh*kw taps (static unroll —
the paper's loop unrolling) the kernel issues one (toh*OWp, bc) x (bc, bo)
MXU matmul.  The output width OWp is OW rounded up to the 8-row sublane
tile so the patch collapses to 2-D without a relayout; the wrapper crops
the extra columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_spec import apply_activation
from repro.util import dot_precision


def _accumulate_taps(x_ref, w_ref, acc_ref, *, kh, kw, sh, sw, toh, ow):
    """Shared K-reduction body: init the accumulator on the first in-channel
    block, then statically unroll over the kh*kw taps (paper's loop
    unrolling) — each tap is a unit-stride window of one stride phase -> one
    (toh*OWp, bc) x (bc, bo) MXU matmul accumulated in the (toh*OWp, bo)
    scratch.  int8 operands accumulate in int32 (the MXU's native quantized
    accumulation width), float operands in fp32."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bc = x_ref.shape[-1]
    wq = x_ref.shape[-2] // sw
    precision = dot_precision(x_ref.dtype)
    for di in range(kh):
        for dj in range(kw):
            patch = x_ref[
                di % sh,
                pl.ds(di // sh, toh),
                pl.ds((dj % sw) * wq + dj // sw, ow),
                :,
            ].reshape(toh * ow, bc)
            acc_ref[...] += jnp.dot(
                patch, w_ref[di, dj], preferred_element_type=acc_ref.dtype,
                precision=precision,
            )


def _store(o_ref, out, activation):
    o_ref[...] = apply_activation(out, activation).reshape(o_ref.shape).astype(
        o_ref.dtype
    )


def _conv_kernel(
    x_ref,  # (sh, rows_in, sw*Wq, bc) VMEM phase windows (one channel slab)
    w_ref,  # (kh, kw, bc, bo)
    o_ref,  # (toh, OWp, bo)
    acc_ref,  # (toh * OWp, bo) fp32 scratch
    *,
    kh: int,
    kw: int,
    sh: int,
    sw: int,
    toh: int,
    ow: int,
    activation: str = "linear",
):
    _accumulate_taps(x_ref, w_ref, acc_ref,
                     kh=kh, kw=kw, sh=sh, sw=sw, toh=toh, ow=ow)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        _store(o_ref, acc_ref[...], activation)


def _conv_bias_kernel(
    x_ref, w_ref, bias_ref, o_ref, acc_ref, *,
    kh: int, kw: int, sh: int, sw: int, toh: int, ow: int, activation: str,
):
    """_conv_kernel plus a fused (1, bo) bias row applied in the output
    stage, on the fp32 accumulator, after the full K reduction."""
    _accumulate_taps(x_ref, w_ref, acc_ref,
                     kh=kh, kw=kw, sh=sh, sw=sw, toh=toh, ow=ow)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        _store(o_ref, acc_ref[...] + bias_ref[...].astype(jnp.float32),
               activation)


def _conv_q8_kernel(
    x_ref, w_ref, scale_ref, o_ref, acc_ref, *,
    kh: int, kw: int, sh: int, sw: int, toh: int, ow: int, activation: str,
):
    """int8 conv: fused dequant epilogue act(acc * scale) on the int32
    accumulator; ``scale_ref`` is the (1, bo) per-out-channel row of folded
    activation x weight quantization scales (core/quant.py)."""
    _accumulate_taps(x_ref, w_ref, acc_ref,
                     kh=kh, kw=kw, sh=sh, sw=sw, toh=toh, ow=ow)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        out = acc_ref[...].astype(jnp.float32) * scale_ref[...].astype(
            jnp.float32
        )
        _store(o_ref, out, activation)


def _conv_q8_bias_kernel(
    x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
    kh: int, kw: int, sh: int, sw: int, toh: int, ow: int, activation: str,
):
    """int8 conv with the full fused epilogue: act(acc * scale + bias)."""
    _accumulate_taps(x_ref, w_ref, acc_ref,
                     kh=kh, kw=kw, sh=sh, sw=sw, toh=toh, ow=ow)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        out = acc_ref[...].astype(jnp.float32) * scale_ref[...].astype(
            jnp.float32
        )
        out = out + bias_ref[...].astype(jnp.float32)
        _store(o_ref, out, activation)


def conv2d_im2col_gemm_pallas(
    x: jnp.ndarray,  # (B, sh, Hq, sw*Wq, C) stride phases, C % bc == 0
    w: jnp.ndarray,  # (kh, kw, C, O), O % bo == 0
    sh: int,
    sw: int,
    oh: int,
    ow: int,
    toh: int,
    bc: int,
    bo: int,
    out_dtype=None,
    interpret: bool = False,
    bias=None,
    activation: str = "linear",
    scale=None,
) -> jnp.ndarray:
    """Run the fused conv kernel.  Returns (B, OHp, OW, O); caller crops.

    ``x`` is the conv-padded input split into its stride phases
    (``ops.phase_split``): Hq >= OHp + (kh-1)//sh rows per phase with
    OHp = ceil(oh/toh)*toh, Wq >= ow + (kw-1)//sw columns per phase.  ``ow``
    is the kernel's output width, a multiple of 8 (``ops.kernel_out_width``).
    ``bias`` (1, O) and ``activation`` are the fused epilogue, applied once
    after the full in-channel reduction.

    Passing ``scale`` (1, O) selects the int8 path: ``x``/``w`` must be
    int8, the accumulator scratch is int32, and the epilogue dequantizes —
    act(acc * scale + bias) — writing ``out_dtype`` (defaults to fp32).
    """
    b, xsh, hq, wsw, c = x.shape
    kh, kw, _, o = w.shape
    ohp = -(-oh // toh) * toh
    rows_in = toh + (kh - 1) // sh
    assert xsh == sh and wsw % sw == 0, (x.shape, sh, sw)
    assert hq >= ohp + (kh - 1) // sh, (hq, ohp, sh, kh)
    assert wsw // sw >= ow + (kw - 1) // sw, (wsw, ow, sw, kw)
    assert c % bc == 0 and o % bo == 0
    assert bias is None or bias.shape == (1, o), (o, getattr(bias, "shape", None))
    quantized = scale is not None
    if quantized:
        assert x.dtype == jnp.int8 and w.dtype == jnp.int8, (x.dtype, w.dtype)
        assert scale.shape == (1, o), (o, scale.shape)
        out_dtype = out_dtype or jnp.float32
    else:
        out_dtype = out_dtype or x.dtype

    in_specs = [
        # Element-offset row window: phase rows [r*toh, r*toh + rows_in).
        pl.BlockSpec(
            (pl.squeezed, pl.Element(sh), pl.Element(rows_in),
             pl.Element(wsw), pl.Element(bc)),
            lambda bi, r, oc, ic: (bi, 0, r * toh, 0, ic * bc),
        ),
        pl.BlockSpec((kh, kw, bc, bo), lambda bi, r, oc, ic: (0, 0, ic, oc)),
    ]
    if quantized:
        body = _conv_q8_bias_kernel if bias is not None else _conv_q8_kernel
    else:
        body = _conv_bias_kernel if bias is not None else _conv_kernel
    kernel = functools.partial(
        body, kh=kh, kw=kw, sh=sh, sw=sw, toh=toh, ow=ow,
        activation=activation,
    )
    extras = (() if scale is None else (scale,)) + (
        () if bias is None else (bias,)
    )
    for _ in extras:
        in_specs.append(pl.BlockSpec((1, bo), lambda bi, r, oc, ic: (0, oc)))
    return pl.pallas_call(
        kernel,
        grid=(b, ohp // toh, o // bo, c // bc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (pl.squeezed, toh, ow, bo), lambda bi, r, oc, ic: (bi, r, 0, oc)
        ),
        out_shape=jax.ShapeDtypeStruct((b, ohp, ow, o), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((toh * ow, bo), jnp.int32 if quantized else jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w, *extras)
