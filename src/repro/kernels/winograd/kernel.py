"""Winograd F(6x6,3x3) Pallas kernels with inter-tile channel parallelism.

TPU realization of the paper's §IV.B scheme.  The paper packs one 8x8 tile
from each of VL/16 channels along the vector register; here every transform
operand is a (tiles, channels) plane per transform position, so the
128-lane axis is filled by channels and the 8 sublanes by tiles: the same
inter-tile parallelization, expressed through BlockSpec tiling instead of
`svcntw`.

Every transform is a sum over whole (tiles, channels) planes with the
constant B^T / A^T coefficients baked in at trace time, i.e. vector FMAs
with no relayout, and every tuple multiply is one 2-D MXU matmul per
transform position — the only matmul form Mosaic lowers.

Two realizations of the same pipeline:

The 3-pass decomposition (one kernel per stage, V and M via HBM) on
position-major operands, (8, 8, T, C) in and (6, 6, T, O) out:
  input_transform:   V = B^T d B     (per tile x channel)
  tuple_multiply:    M[p] = V[p] @ U[p]  batched GEMM over the 64 positions
                     (the paper's "increase the number of blocks for GEMM")
  output_transform:  Y = A^T M A     (per tile x out-channel)

The single-pass megakernel (``fused_winograd_nhwc_pallas``): one grid
(B/bb, nTH/k, nTW/ntw, O/bo, C/bc) over the padded NHWC activation itself.
A program reads the element-offset window of its bb images x (6k+2) rows x
(6 ntw + 8) columns; tile position (i, j) of its bb*k*ntw tiles is one
load strided by 6 along rows and sublanes (rows i, i+6, ..., columns j,
j+6, ...).  It
transforms the tiles in registers, runs the 64 per-position GEMMs,
accumulates M in an (8, 8, bb*k*ntw, bo) fp32 VMEM scratch across the Cin
(reduction) grid axis, and on the last Cin step applies Y = A^T M A plus
the fused bias+activation epilogue, writing the 36 output planes with
stores strided the same way into an NHWC (bb, 6k, 6 ntw, bo) block of the
(B, OH, OW, O) output.  No tile tensor, V or M touches HBM, which is where
Winograd's FLOP advantage is won or lost (cf. the follow-up co-design
paper).  Strided sublane access is 32-bit only (Mosaic does not lower it
for packed types), so the kernel runs on fp32.

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


def _input_rows(load, a):
    """Row pass of V = B^T d B for one position row ``a``: the 8 planes
    sum_i BT[a, i] d[i, j], each (bt, bc) fp32; ``load(i, j)`` reads the
    (bt, bc) plane of tile position (i, j)."""
    return [
        _combine(_BT[a], lambda i, j=j: load(i, j).astype(jnp.float32))
        for j in range(8)
    ]


def _tile_planes(d_ref):
    """Plane loader of a position-major (8, 8, bt, bc) tile block."""
    return lambda i, j: d_ref[i, j]


def _nhwc_planes(x_ref, k, ntw):
    """Plane loader of an NHWC (bb, 6k+2, 6 ntw + 8, bc) row window: plane
    (i, j) holds tile (b, kr, tc) at row (b*k + kr)*ntw + tc, read from
    row 6 kr + i, column 6 tc + j — one load, strided by 6 along rows and
    sublanes."""
    bb = x_ref.shape[0]

    def load(i, j):
        plane = x_ref[:, pl.ds(i, k, stride=6), pl.ds(j, ntw, stride=6), :]
        return plane.reshape(bb * k * ntw, plane.shape[-1])

    return load


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
        rows = _input_rows(_tile_planes(d_ref), a)
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


def _fused_accumulate(cstep, load, u_ref, acc_ref):
    """Shared megakernel reduction step: V in registers, M into scratch."""

    @pl.when(cstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    precision = dot_precision(jnp.float32)
    for a in range(8):
        rows = _input_rows(load, a)
        for b in range(8):
            # V[a, b] (never stored to HBM) @ U[a, b]: one 2-D MXU matmul.
            v = _combine(_BT[b], lambda j: rows[j])
            acc_ref[a, b] += jnp.dot(
                v, u_ref[a, b].astype(jnp.float32),
                preferred_element_type=jnp.float32, precision=precision,
            )


def _store_nhwc(y_ref, tiles, k, ntw):
    """Write the 36 output planes of ``tiles`` ((x, y, (bt, bo)) triples)
    into the NHWC (bb, 6k, 6 ntw, bo) block, one store each, strided by 6
    along rows and sublanes: the inverse of ``_nhwc_planes``'s placement."""
    bb, _, _, bo = y_ref.shape
    for x, y, out in tiles:
        y_ref[:, pl.ds(x, k, stride=6), pl.ds(y, ntw, stride=6), :] = (
            out.astype(y_ref.dtype).reshape(bb, k, ntw, bo)
        )


def _fused_winograd_nhwc_kernel(x_ref, u_ref, y_ref, acc_ref, *, k: int,
                                ntw: int, activation: str = "linear"):
    """Single-pass megakernel body on an NHWC row window: grid (B/bb,
    nTH/k, nTW/ntw, O/bo, C/bc), Cin innermost (the reduction axis).  The
    M accumulator scratch (8, 8, bb*k*ntw, bo) fp32 persists across the Cin
    steps; V exists only as per-position register values."""
    cstep = pl.program_id(4)
    _fused_accumulate(cstep, _nhwc_planes(x_ref, k, ntw), u_ref, acc_ref)

    @pl.when(cstep == pl.num_programs(4) - 1)
    def _done():
        _store_nhwc(y_ref, _output_tiles(acc_ref, None, activation), k, ntw)


def _fused_winograd_nhwc_bias_kernel(x_ref, u_ref, bias_ref, y_ref, acc_ref,
                                     *, k: int, ntw: int, activation: str):
    """``_fused_winograd_nhwc_kernel`` with the bias (1, bo) + activation
    epilogue."""
    cstep = pl.program_id(4)
    _fused_accumulate(cstep, _nhwc_planes(x_ref, k, ntw), u_ref, acc_ref)

    @pl.when(cstep == pl.num_programs(4) - 1)
    def _done():
        _store_nhwc(y_ref, _output_tiles(acc_ref, bias_ref, activation),
                    k, ntw)


def fused_winograd_nhwc_pallas(
    x: jnp.ndarray,      # (B, 6 nTH + 2, 6 nTW + 8, C) padded NHWC
    u: jnp.ndarray,      # (8, 8, C, O) pre-transformed weights
    oh: int,
    ow: int,
    bb: int,
    k: int,
    ntw: int,
    bc: int,
    bo: int,
    interpret: bool = False,
    bias=None,           # (1, O) or None
    activation: str = "linear",
) -> jnp.ndarray:
    """(B, 6 nTH + 2, 6 nTW + 8, C) x (8, 8, C, O) -> (B, OH, OW, O).

    The megakernel tiled in VMEM: a program computes the bb*k*ntw output
    tiles of bb images x k tile rows x ntw tile columns from the
    element-offset input window that covers them, halo included (rows
    [6k r, 6k r + 6k + 2), columns [6 ntw c, 6 ntw c + 6 ntw + 8)), and
    writes them as NHWC.  The last row and column blocks overhang the
    (OH, OW) output, whose writeback drops what lies outside it, so no crop
    follows.  B % bb == 0, nTH % k == 0, nTW % ntw == 0, ntw % 8 == 0,
    C % bc == 0, O % bo == 0 (ops.py pads), and every block holds some of
    the output.  Cin is the innermost ('arbitrary') grid axis, so the
    (8, 8, bb*k*ntw, bo) M accumulator survives in scratch between
    reduction steps.
    """
    b, hp, wp, c = x.shape
    o = u.shape[-1]
    nth, ntw_all = (hp - 2) // 6, (wp - 8) // 6
    assert hp == 6 * nth + 2 and wp == 6 * ntw_all + 8, x.shape
    assert b % bb == 0 and nth % k == 0 and ntw_all % ntw == 0, (
        x.shape, bb, k, ntw)
    assert 6 * (nth - k) < oh <= 6 * nth and 6 * (ntw_all - ntw) < ow <= (
        6 * ntw_all), (x.shape, oh, ow, k, ntw)
    assert ntw % 8 == 0 and c % bc == 0 and o % bo == 0, (ntw, c, bc, o, bo)
    assert x.dtype == jnp.float32, x.dtype
    assert bias is None or bias.shape == (1, o), (o, getattr(bias, "shape", None))
    in_specs = [
        # Element-offset window: each block overlaps the next by its halo.
        pl.BlockSpec(
            (pl.Element(bb), pl.Element(6 * k + 2), pl.Element(6 * ntw + 8),
             pl.Element(bc)),
            lambda n, r, q, j, i: (n * bb, r * 6 * k, q * 6 * ntw, i * bc),
        ),
        pl.BlockSpec((8, 8, bc, bo), lambda n, r, q, j, i: (0, 0, i, j)),
    ]
    inputs = [x, u]
    if bias is not None:
        kernel = _fused_winograd_nhwc_bias_kernel
        in_specs.append(pl.BlockSpec((1, bo), lambda n, r, q, j, i: (0, j)))
        inputs.append(bias)
    else:
        kernel = _fused_winograd_nhwc_kernel
    return pl.pallas_call(
        functools.partial(kernel, k=k, ntw=ntw, activation=activation),
        grid=(b // bb, nth // k, ntw_all // ntw, o // bo, c // bc),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, 6 * k, 6 * ntw, bo),
                               lambda n, r, q, j, i: (n, r, q, j)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, o), x.dtype),
        scratch_shapes=[pltpu.VMEM((8, 8, bb * k * ntw, bo), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 4 + ("arbitrary",)
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
