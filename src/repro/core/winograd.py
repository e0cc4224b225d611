"""Winograd F(6x6, 3x3) convolution with inter-tile channel parallelism.

This is the paper's novel contribution (§IV.B): rather than growing the tile
beyond 8x8 (which destroys numerical accuracy), the transforms are vectorized
by packing one 8x8 tile from each of several channels along the vector.  On
TPU we realize the same scheme by keeping **channels as the minormost (lane)
axis** of every transform operand: an (..., tiles, channels) block fills the
128-wide lane axis with channels exactly as the paper fills a 512..2048-bit
vector with 4..16 channels.  The tuple multiplication (§IV.B last paragraph)
becomes a batched GEMM over the 64 transform positions:
    M[p] = V[p] @ U[p],  p in 0..63,  V[p]: (tiles, Cin), U[p]: (Cin, Cout)
which maps directly onto the MXU.

Transform matrices are the standard Lavin/Cook-Toom F(6,3) set with
interpolation points (0, ±1, ±2, ±1/2, ∞) — the same family NNPACK uses.
Their correctness is asserted against direct convolution in the test-suite.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.conv_spec import ConvSpec, Epilogue, apply_epilogue
from repro.util import dot_precision

TILE = 8          # input tile (paper's default 8x8)
OUT_TILE = 6      # output tile of F(6,3)
R = 3             # filter size

# B^T (8x8): input transform.  V = B^T d B.
BT = np.array(
    [
        [1, 0, -21 / 4, 0, 21 / 4, 0, -1, 0],
        [0, 1, 1, -17 / 4, -17 / 4, 1, 1, 0],
        [0, -1, 1, 17 / 4, -17 / 4, -1, 1, 0],
        [0, 1 / 2, 1 / 4, -5 / 2, -5 / 4, 2, 1, 0],
        [0, -1 / 2, 1 / 4, 5 / 2, -5 / 4, -2, 1, 0],
        [0, 2, 4, -5 / 2, -5, 1 / 2, 1, 0],
        [0, -2, 4, 5 / 2, -5, -1 / 2, 1, 0],
        [0, -1, 0, 21 / 4, 0, -21 / 4, 0, 1],
    ],
    dtype=np.float64,
)

# G (8x3): weight transform.  U = G g G^T.
G = np.array(
    [
        [1, 0, 0],
        [-2 / 9, -2 / 9, -2 / 9],
        [-2 / 9, 2 / 9, -2 / 9],
        [1 / 90, 1 / 45, 2 / 45],
        [1 / 90, -1 / 45, 2 / 45],
        [32 / 45, 16 / 45, 8 / 45],
        [32 / 45, -16 / 45, 8 / 45],
        [0, 0, 1],
    ],
    dtype=np.float64,
)

# A^T (6x8): output transform.  Y = A^T M A.
AT = np.array(
    [
        [1, 1, 1, 1, 1, 1, 1, 0],
        [0, 1, -1, 2, -2, 1 / 2, -1 / 2, 0],
        [0, 1, 1, 4, 4, 1 / 4, 1 / 4, 0],
        [0, 1, -1, 8, -8, 1 / 8, -1 / 8, 0],
        [0, 1, 1, 16, 16, 1 / 16, 1 / 16, 0],
        [0, 1, -1, 32, -32, 1 / 32, -1 / 32, 1],
    ],
    dtype=np.float64,
)


def transform_weights(w: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """U = G w G^T per (cin, cout) pair.

    Done **once, offline** for inference — the paper excludes the weight
    transform from timing for the same reason (§VII.A).

    Args:
      w: (3, 3, Cin, Cout).
    Returns:
      (8, 8, Cin, Cout) transformed weights.
    """
    g = jnp.asarray(G, dtype)
    # U[a,b,c,o] = sum_{i,j} G[a,i] w[i,j,c,o] G[b,j]
    return jnp.einsum("ai,bj,ijco->abco", g, g, w.astype(dtype),
                      precision=dot_precision(dtype))


def _tile_input(x: jnp.ndarray, oh: int, ow: int) -> Tuple[jnp.ndarray, int, int]:
    """Pad + extract overlapping 8x8 input tiles with stride 6.

    Args:
      x: (B, H, W, C) *already padded* with the conv's own padding.
    Returns:
      tiles (B, nTH, nTW, 8, 8, C), and the tile grid (nTH, nTW).
    """
    b, h, w, c = x.shape
    nth = -(-oh // OUT_TILE)  # ceil
    ntw = -(-ow // OUT_TILE)
    need_h = nth * OUT_TILE + R - 1
    need_w = ntw * OUT_TILE + R - 1
    x = jnp.pad(x, ((0, 0), (0, need_h - h), (0, need_w - w), (0, 0)))
    rows = (jnp.arange(nth) * OUT_TILE)[:, None] + jnp.arange(TILE)[None, :]
    cols = (jnp.arange(ntw) * OUT_TILE)[:, None] + jnp.arange(TILE)[None, :]
    tiles = x[:, rows[:, None, :, None], cols[None, :, None, :], :]
    return tiles, nth, ntw


def input_transform(tiles: jnp.ndarray) -> jnp.ndarray:
    """V = B^T d B, channels kept minormost (inter-tile channel packing).

    Args:
      tiles: (B, nTH, nTW, 8, 8, C).
    Returns:
      (8, 8, B*nTH*nTW, C) — position-major, (tiles, channels) trailing so the
      lane axis is the channel axis, as in the paper's Fig. 5 scheme.
    """
    bt = jnp.asarray(BT, tiles.dtype)
    b, nth, ntw = tiles.shape[:3]
    v = jnp.einsum("ai,bj,BtuijC->abBtuC", bt, bt, tiles)
    return v.reshape(TILE, TILE, b * nth * ntw, tiles.shape[-1])


def tuple_multiply(v: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Batched tuple multiplication over the 64 transform positions.

    M[a,b] = V[a,b] @ U[a,b]:  (8,8,T,Cin) x (8,8,Cin,Cout) -> (8,8,T,Cout).
    This is the paper's "increase the number of blocks for the GEMM kernel"
    (§IV.B): each position is an independent GEMM; on TPU all 64 run as one
    batched MXU matmul.
    """
    return jnp.einsum("abtc,abco->abto", v, u)


def output_transform(m: jnp.ndarray, b: int, nth: int, ntw: int) -> jnp.ndarray:
    """Y = A^T M A back to spatial tiles.

    Args:
      m: (8, 8, B*nTH*nTW, Cout).
    Returns:
      (B, nTH*6, nTW*6, Cout).
    """
    at = jnp.asarray(AT, m.dtype)
    cout = m.shape[-1]
    m = m.reshape(TILE, TILE, b, nth, ntw, cout)
    y = jnp.einsum("xa,yb,abBtuC->BtxuyC", at, at, m)
    return y.reshape(b, nth * OUT_TILE, ntw * OUT_TILE, cout)


def conv2d_winograd(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    pretransformed: bool = False,
    epilogue: Optional[Epilogue] = None,
) -> jnp.ndarray:
    """Full Winograd F(6,3) convolution, stride 1, 3x3 kernels.

    Args:
      x: (B, H, W, Cin).
      w: (3, 3, Cin, Cout) raw weights, or (8, 8, Cin, Cout) if
         ``pretransformed`` (offline weight transform, inference mode).
    Returns:
      (B, OH, OW, Cout).
    """
    assert spec.kernel_size == (3, 3) and spec.stride == (1, 1), (
        "Winograd F(6,3) requires 3x3 stride-1; the selector routes "
        "everything else to im2col+GEMM (paper §VII.A)."
    )
    bsz, h, ww, _ = x.shape
    oh, ow = spec.out_hw(h, ww)
    ph, pw = spec.padding
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    u = w if pretransformed else transform_weights(w, x.dtype)
    tiles, nth, ntw = _tile_input(x, oh, ow)
    v = input_transform(tiles)
    m = tuple_multiply(v, u.astype(x.dtype))
    y = output_transform(m, bsz, nth, ntw)
    # Epilogue on the transformed output (bias + activation are elementwise,
    # so applying before the crop is exact).
    return apply_epilogue(y, epilogue)[:, :oh, :ow, :]


def winograd_flops(oh: int, ow: int, cin: int, cout: int) -> dict:
    """Multiply counts for F(6,3) vs direct 3x3 — the paper's 2.4x source.

    Per 6x6 output tile: direct = 36*9*Cin*Cout MACs; winograd tuple mult =
    64*Cin*Cout MACs (5.06x fewer) + transform overhead.
    """
    nth, ntw = -(-oh // OUT_TILE), -(-ow // OUT_TILE)
    tiles = nth * ntw
    direct = 2 * oh * ow * 9 * cin * cout
    tuple_mult = 2 * tiles * 64 * cin * cout
    # B^T d B: two 8x8 @ 8x8 per tile-channel; A^T M A: 6x8 @ 8x8 + 6x8 @ 8x6.
    in_tf = tiles * cin * 2 * (8 * 8 * 8) * 2
    out_tf = tiles * cout * 2 * (6 * 8 * 8 + 6 * 8 * 6)
    return {
        "direct_flops": direct,
        "winograd_flops": tuple_mult + in_tf + out_tf,
        "tuple_flops": tuple_mult,
        "transform_flops": in_tf + out_tf,
        "mult_reduction": direct / tuple_mult,
    }
