"""im2col and conv-as-GEMM, in pure JAX (NHWC layout).

The paper's im2col+GEMM pipeline (§IV.A): lower the convolution to a GEMM
with A = weights (M x K), B = im2col(input) (K x N), C = output (M x N),
M = out_channels, K = kh*kw*in_channels, N = oh*ow.

On TPU we keep everything channels-last so the innermost (lane) axis is the
channel axis — the same layout decision the paper makes when it packs
channels along the vector (§IV.B).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

from repro.core.conv_spec import ConvSpec, Epilogue, apply_epilogue


def im2col(
    x: jnp.ndarray,
    kernel_size: Tuple[int, int],
    stride: Tuple[int, int] = (1, 1),
    padding: Tuple[int, int] = (0, 0),
    dilation: Tuple[int, int] = (1, 1),
) -> jnp.ndarray:
    """Extract convolution patches.

    Args:
      x: (B, H, W, C) input.
    Returns:
      (B, OH, OW, kh*kw*C) patches, K ordered as (kh, kw, C) to match a
      weight reshaped from (kh, kw, C, O).
    """
    b, h, w, c = x.shape
    kh, kw = kernel_size
    sh, sw = stride
    ph, pw = padding
    dh, dw = dilation
    eff_kh = (kh - 1) * dh + 1
    eff_kw = (kw - 1) * dw + 1
    oh = (h + 2 * ph - eff_kh) // sh + 1
    ow = (w + 2 * pw - eff_kw) // sw + 1

    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))

    # The input is split into its stride phases once, and each tap is a
    # unit-stride slice of one phase, concatenated along channels: a
    # strided slice per tap reads the whole map with a lane stride for
    # every tap (on a TPU, 5x slower for a 7x7/2 stem).  lax.slice, not
    # strided indexing, which jnp lowers to a gather per tap.
    phases = {}
    taps = []
    for i in range(kh):
        for j in range(kw):
            r, q = i * dh, j * dw
            key = (r % sh, q % sw)
            if key not in phases:
                phases[key] = lax.slice(x, (0, *key, 0), x.shape, (1, sh, sw, 1))
            taps.append(lax.slice(
                phases[key], (0, r // sh, q // sw, 0),
                (b, r // sh + oh, q // sw + ow, c)))
    return jnp.concatenate(taps, axis=-1) if len(taps) > 1 else taps[0]


def conv2d_im2col(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    epilogue: Optional[Epilogue] = None,
) -> jnp.ndarray:
    """Convolution via im2col + GEMM, with an optional fused epilogue.

    Args:
      x: (B, H, W, C); w: (kh, kw, C, O).
    Returns:
      (B, OH, OW, O).
    """
    b, h, _w, c = x.shape
    kh, kw, wc, o = w.shape
    assert (kh, kw) == spec.kernel_size and wc == c and o == spec.out_channels
    oh, ow = spec.out_hw(h, _w)
    patches = im2col(x, spec.kernel_size, spec.stride, spec.padding, spec.dilation)
    k = kh * kw * c
    # (B*OH*OW, K) @ (K, O): N-major output, channels-last (lane axis = O).
    out = patches.reshape(b * oh * ow, k) @ w.reshape(k, o)
    return apply_epilogue(out, epilogue).reshape(b, oh, ow, o)


def conv2d_direct_1x1(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    epilogue: Optional[Epilogue] = None,
) -> jnp.ndarray:
    """1x1 convolution as a plain GEMM (the paper's Direct path for 1x1)."""
    b, h, ww, c = x.shape
    assert spec.kernel_size == (1, 1)
    sh, sw = spec.stride
    ph, pw = spec.padding
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        h, ww = h + 2 * ph, ww + 2 * pw
    if (sh, sw) != (1, 1):
        x = x[:, ::sh, ::sw, :]
    oh, ow = x.shape[1], x.shape[2]
    out = x.reshape(b * oh * ow, c) @ w.reshape(c, spec.out_channels)
    return apply_epilogue(out, epilogue).reshape(b, oh, ow, spec.out_channels)
