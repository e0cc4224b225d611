"""Winograd F(6x6,3x3) Pallas kernels with inter-tile channel parallelism.

TPU realization of the paper's §IV.B scheme.  The paper packs one 8x8 tile
from each of VL/16 channels along the vector register; here every operand
is position-major with a trailing (tiles, channels) block — (8, 8, T, C) in,
(6, 6, T, O) out — so the 128-lane axis is filled by channels and the 8
sublanes by tiles: the same inter-tile parallelization, expressed through
BlockSpec tiling instead of `svcntw`.

Every transform is a sum over whole (tiles, channels) planes with the
constant B^T / A^T coefficients baked in at trace time, i.e. vector FMAs
with no relayout, and every tuple multiply is one 2-D MXU matmul per
transform position — the only matmul form Mosaic lowers.

Two realizations of the same pipeline:

The 3-pass decomposition (one kernel per stage, V and M via HBM):
  input_transform:   V = B^T d B     (per tile x channel)
  tuple_multiply:    M[p] = V[p] @ U[p]  batched GEMM over the 64 positions
                     (the paper's "increase the number of blocks for GEMM")
  output_transform:  Y = A^T M A     (per tile x out-channel)

The single-pass megakernel (``fused_winograd_pallas``): one grid
(T/bt, O/bo, C/bc) where each program transforms its tile block in
registers, runs the 64 per-position GEMMs, accumulates M in an
(8, 8, bt, bo) fp32 VMEM scratch across the Cin (reduction) grid axis, and
on the last Cin step applies Y = A^T M A plus the fused bias+activation
epilogue — V and M never touch HBM, which is where Winograd's FLOP
advantage is won or lost (cf. the follow-up co-design paper).

The weight transform U = G g G^T runs offline (ops.py), as in the paper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_spec import apply_activation
from repro.core.winograd import AT, BT
from repro.util import dot_precision

_BT = tuple(tuple(float(c) for c in row) for row in BT)
_AT = tuple(tuple(float(c) for c in row) for row in AT)


def _combine(coeffs, load):
    """sum_k coeffs[k] * load(k) over the nonzero (static) coefficients."""
    acc = None
    for k, c in enumerate(coeffs):
        if c == 0.0:
            continue
        t = load(k)
        t = t if c == 1.0 else (-t if c == -1.0 else c * t)
        acc = t if acc is None else acc + t
    return acc


def _input_rows(d_ref, a):
    """Row pass of V = B^T d B for one position row ``a``: the 8 planes
    sum_i BT[a, i] d[i, j], each (bt, bc) fp32."""
    return [
        _combine(_BT[a], lambda i, j=j: d_ref[i, j].astype(jnp.float32))
        for j in range(8)
    ]


def _output_tiles(m_ref, bias_ref, activation):
    """Y = A^T M A on an (8, 8, bt, bo) fp32 ref, as 36 (x, y) planes with
    the bias + activation epilogue applied."""
    bias = None if bias_ref is None else bias_ref[...].astype(jnp.float32)
    for x in range(6):
        rows = [
            _combine(_AT[x], lambda a, b=b: m_ref[a, b].astype(jnp.float32))
            for b in range(8)
        ]
        for y in range(6):
            out = _combine(_AT[y], lambda b: rows[b])
            if bias is not None:
                out = out + bias
            yield x, y, apply_activation(out, activation)


def _input_transform_kernel(d_ref, v_ref):
    """d (8, 8, bt, bc) -> V (8, 8, bt, bc)."""
    for a in range(8):
        rows = _input_rows(d_ref, a)
        for b in range(8):
            v_ref[a, b] = _combine(_BT[b], lambda j: rows[j]).astype(v_ref.dtype)


def _tuple_multiply_kernel(v_ref, u_ref, m_ref, acc_ref):
    """Grid (64, nt, no, nc): per-position GEMM with K(=cin) accumulation."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        v_ref[0], u_ref[0], preferred_element_type=jnp.float32,
        precision=dot_precision(v_ref.dtype),
    )

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        m_ref[...] = acc_ref[...].astype(m_ref.dtype)[None]


def _output_transform_kernel(m_ref, y_ref, *, activation: str = "linear"):
    """M (8, 8, bt, bo) -> Y (6, 6, bt, bo)."""
    for x, y, out in _output_tiles(m_ref, None, activation):
        y_ref[x, y] = out.astype(y_ref.dtype)


def _output_transform_bias_kernel(m_ref, bias_ref, y_ref, *, activation: str):
    """Output transform with the fused epilogue: bias (1, bo) + activation
    applied to the fp32 transform result before the store."""
    for x, y, out in _output_tiles(m_ref, bias_ref, activation):
        y_ref[x, y] = out.astype(y_ref.dtype)


def _fused_accumulate(cstep, d_ref, u_ref, acc_ref):
    """Shared megakernel reduction step: V in registers, M into scratch."""

    @pl.when(cstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    precision = dot_precision(jnp.float32)
    for a in range(8):
        rows = _input_rows(d_ref, a)
        for b in range(8):
            # V[a, b] (never stored to HBM) @ U[a, b]: one 2-D MXU matmul.
            v = _combine(_BT[b], lambda j: rows[j])
            acc_ref[a, b] += jnp.dot(
                v, u_ref[a, b].astype(jnp.float32),
                preferred_element_type=jnp.float32, precision=precision,
            )


def _fused_winograd_kernel(d_ref, u_ref, y_ref, acc_ref, *,
                           activation: str = "linear"):
    """Single-pass megakernel body: transform, tuple-GEMM, inverse transform.

    Grid (T/bt, O/bo, C/bc) with Cin innermost (the reduction axis).  The M
    accumulator scratch (8, 8, bt, bo) fp32 persists across the Cin steps;
    V exists only as per-position register values.
    """
    cstep = pl.program_id(2)
    _fused_accumulate(cstep, d_ref, u_ref, acc_ref)

    @pl.when(cstep == pl.num_programs(2) - 1)
    def _done():
        for x, y, out in _output_tiles(acc_ref, None, activation):
            y_ref[x, y] = out.astype(y_ref.dtype)


def _fused_winograd_bias_kernel(d_ref, u_ref, bias_ref, y_ref, acc_ref, *,
                                activation: str):
    """Fused megakernel with the bias (1, bo) + activation epilogue applied
    to the fp32 inverse-transform result before the store."""
    cstep = pl.program_id(2)
    _fused_accumulate(cstep, d_ref, u_ref, acc_ref)

    @pl.when(cstep == pl.num_programs(2) - 1)
    def _done():
        for x, y, out in _output_tiles(acc_ref, bias_ref, activation):
            y_ref[x, y] = out.astype(y_ref.dtype)


def fused_winograd_pallas(
    tiles: jnp.ndarray,  # (8, 8, T, C)
    u: jnp.ndarray,      # (8, 8, C, O) pre-transformed weights
    bt: int,
    bc: int,
    bo: int,
    interpret: bool = False,
    bias=None,           # (1, O) or None
    activation: str = "linear",
) -> jnp.ndarray:
    """(8, 8, T, C) x (8, 8, C, O) -> (6, 6, T, O) in one pallas_call.

    T % bt == 0, C % bc == 0, O % bo == 0 (ops.py pads).  Cin is the
    innermost ('arbitrary') grid axis so the per-(tile, out-channel) block's
    M accumulator survives in scratch between reduction steps; the tile and
    weight blocks stream through VMEM double-buffered.
    """
    _, _, t, c = tiles.shape
    o = u.shape[-1]
    assert bias is None or bias.shape == (1, o), (o, getattr(bias, "shape", None))
    in_specs = [
        pl.BlockSpec((8, 8, bt, bc), lambda i, j, k: (0, 0, i, k)),
        pl.BlockSpec((8, 8, bc, bo), lambda i, j, k: (0, 0, k, j)),
    ]
    inputs = [tiles, u]
    if bias is not None:
        kernel = functools.partial(
            _fused_winograd_bias_kernel, activation=activation
        )
        in_specs.append(pl.BlockSpec((1, bo), lambda i, j, k: (0, j)))
        inputs.append(bias)
    else:
        kernel = functools.partial(
            _fused_winograd_kernel, activation=activation
        )
    return pl.pallas_call(
        kernel,
        grid=(t // bt, o // bo, c // bc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((6, 6, bt, bo), lambda i, j, k: (0, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((6, 6, t, o), tiles.dtype),
        scratch_shapes=[pltpu.VMEM((8, 8, bt, bo), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*inputs)


def input_transform_pallas(
    tiles: jnp.ndarray, bt: int, bc: int, interpret: bool = False
) -> jnp.ndarray:
    """(8, 8, T, C) -> (8, 8, T, C); T % bt == 0, C % bc == 0."""
    _, _, t, c = tiles.shape
    return pl.pallas_call(
        _input_transform_kernel,
        grid=(t // bt, c // bc),
        in_specs=[pl.BlockSpec((8, 8, bt, bc), lambda i, j: (0, 0, i, j))],
        out_specs=pl.BlockSpec((8, 8, bt, bc), lambda i, j: (0, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((8, 8, t, c), tiles.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(tiles)


def tuple_multiply_pallas(
    v: jnp.ndarray,  # (64, T, C)
    u: jnp.ndarray,  # (64, C, O)
    bt: int,
    bc: int,
    bo: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched per-position GEMM -> M (64, T, O)."""
    p, t, c = v.shape
    _, _, o = u.shape
    return pl.pallas_call(
        _tuple_multiply_kernel,
        grid=(p, t // bt, o // bo, c // bc),
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda pp, i, j, k: (pp, i, k)),
            pl.BlockSpec((1, bc, bo), lambda pp, i, j, k: (pp, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bt, bo), lambda pp, i, j, k: (pp, i, j)),
        out_shape=jax.ShapeDtypeStruct((p, t, o), v.dtype),
        scratch_shapes=[pltpu.VMEM((bt, bo), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(v, u)


def output_transform_pallas(
    m: jnp.ndarray, bt: int, bo: int, interpret: bool = False,
    bias=None, activation: str = "linear",
) -> jnp.ndarray:
    """(8, 8, T, O) -> (6, 6, T, O), with an optional fused bias (1, O) +
    activation epilogue applied to the fp32 transform output."""
    _, _, t, o = m.shape
    assert bias is None or bias.shape == (1, o), (o, getattr(bias, "shape", None))
    in_specs = [pl.BlockSpec((8, 8, bt, bo), lambda i, j: (0, 0, i, j))]
    if bias is not None:
        kernel = functools.partial(
            _output_transform_bias_kernel, activation=activation
        )
        in_specs.append(pl.BlockSpec((1, bo), lambda i, j: (0, j)))
    else:
        kernel = functools.partial(
            _output_transform_kernel, activation=activation
        )
    return pl.pallas_call(
        kernel,
        grid=(t // bt, o // bo),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((6, 6, bt, bo), lambda i, j: (0, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((6, 6, t, o), m.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(m, *(() if bias is None else (bias,)))
