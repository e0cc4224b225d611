"""Convolution specification and per-layer algorithm selection.

This encodes the paper's central "no one-size-fits-all convolution" finding
(§II.c, §VII): 1x1 kernels run as a direct GEMM, 3x3 stride-1 kernels run
Winograd F(6x6,3x3), everything else falls back to im2col+GEMM.  The selector
is a first-class, overridable feature of the framework: every conv layer
carries a ConvSpec and the dispatcher in core/conv2d.py consults it.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Optional, Tuple


class ConvAlgorithm(enum.Enum):
    """Convolution algorithm choices studied by the paper."""

    AUTO = "auto"
    AUTO_COST = "auto_cost"      # roofline-model-driven selection (beyond
                                 # paper: v5e eligibility also requires the
                                 # layer be activation-dominated; see
                                 # EXPERIMENTS.md §Perf CNN section)
    DIRECT = "direct"            # 1x1 → plain GEMM (no patch expansion)
    IM2COL_GEMM = "im2col_gemm"  # generic path (paper §IV.A)
    WINOGRAD = "winograd"        # F(6x6,3x3), 8x8 tiles (paper §IV.B)


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Static description of one convolutional layer."""

    in_channels: int
    out_channels: int
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (1, 1)   # symmetric (ph, pw)
    dilation: Tuple[int, int] = (1, 1)
    algorithm: ConvAlgorithm = ConvAlgorithm.AUTO

    @property
    def kh(self) -> int:
        return self.kernel_size[0]

    @property
    def kw(self) -> int:
        return self.kernel_size[1]

    def out_hw(self, h: int, w: int) -> Tuple[int, int]:
        """Output spatial dims for an (h, w) input."""
        ph, pw = self.padding
        sh, sw = self.stride
        dh, dw = self.dilation
        eff_kh = (self.kh - 1) * dh + 1
        eff_kw = (self.kw - 1) * dw + 1
        oh = (h + 2 * ph - eff_kh) // sh + 1
        ow = (w + 2 * pw - eff_kw) // sw + 1
        return oh, ow

    def gemm_dims(self, h: int, w: int) -> Tuple[int, int, int]:
        """(M, N, K) of the im2col GEMM for an (h, w) input.

        Matches the paper's formulation: M = n_filters, K = kh*kw*c,
        N = oh*ow (Table IV uses exactly these).
        """
        oh, ow = self.out_hw(h, w)
        return self.out_channels, oh * ow, self.kh * self.kw * self.in_channels


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Per-layer conv epilogue fused into the kernel's output stage.

    The paper's BLIS lesson (§IV.A) applied to the layer pipeline: instead of
    bouncing the conv output through HBM three more times (add_bias →
    activation as separate elementwise passes), the bias add and activation
    run on the fp32 accumulator while it is still VMEM-resident.  Inference-
    mode batchnorm is first folded into the conv weights + this bias
    (``models/cnn.fold_batchnorm``), so every conv layer reduces to
    conv + bias + activation.

    ``bias`` is a traced (out_channels,) vector or None; ``activation`` is a
    static kind ('linear' | 'relu' | 'leaky') so jitted kernel wrappers can
    specialize on it.

    ``scale`` extends the same fused write-back to int8 dequantization: a
    per-output-channel (O,) vector multiplied into the raw accumulator
    *before* the bias add, so y = act(acc * scale + bias).  For int8 convs
    the accumulator is int32 and ``scale`` carries the folded
    activation x weight quantization scales (core/quant.py); for fp32 convs
    it stays None and the epilogue is unchanged.
    """

    bias: Optional[Any] = None      # (O,) jnp vector, traced through jit
    activation: str = "linear"      # linear | relu | leaky
    scale: Optional[Any] = None     # (O,) dequant row, traced through jit


def apply_activation(x, kind: str):
    """Darknet's activate_array, shared by kernels and reference paths."""
    import jax.numpy as jnp

    if kind == "leaky":
        return jnp.where(x > 0, x, 0.1 * x)
    if kind == "relu":
        return jnp.maximum(x, 0)
    if kind == "linear":
        return x
    raise ValueError(f"unknown activation {kind!r}")


def max_pool(x, layer):
    """A maxpool layer on NHWC ``x``: "SAME" windows, or ``layer.pad``
    rows and columns of -inf on each side.  Either way every window holds
    a real element, so all-zero channels stay zero."""
    import jax
    import jax.numpy as jnp

    if layer.pad is None:
        padding = "SAME"
    else:
        p = (layer.pad, layer.pad)
        padding = ((0, 0), p, p, (0, 0))
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, layer.size, layer.size, 1),
        (1, layer.stride, layer.stride, 1), padding,
    )


def max_pool_out_hw(layer, h: int, w: int) -> Tuple[int, int]:
    """The (H, W) ``max_pool`` gives a (h, w) map."""
    s = layer.stride
    if layer.pad is None:
        return -(-h // s), -(-w // s)
    p, k = layer.pad, layer.size
    return (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1


def apply_epilogue(y, epilogue: Optional[Epilogue]):
    """Reference epilogue: y * scale + bias, then activation (pure jnp)."""
    if epilogue is None:
        return y
    if epilogue.scale is not None:
        import jax.numpy as jnp

        y = y.astype(jnp.float32) * epilogue.scale
    if epilogue.bias is not None:
        y = y + epilogue.bias
    return apply_activation(y, epilogue.activation)


def select_algorithm(spec: ConvSpec) -> ConvAlgorithm:
    """The paper's per-layer selection rule (§VII.A, §II.c).

    - 1x1, stride 1: the im2col matrix equals the input — run a direct GEMM.
    - 3x3, stride 1, no dilation: Winograd F(6,3) is 2.4x faster (paper §VII).
    - 3x3 stride 2: the paper measured Winograd 1.4x *slower* → im2col+GEMM.
    - everything else: im2col+GEMM.
    """
    if spec.algorithm is not ConvAlgorithm.AUTO:
        return spec.algorithm
    if spec.kernel_size == (1, 1) and spec.stride == (1, 1):
        return ConvAlgorithm.DIRECT
    if (
        spec.kernel_size == (3, 3)
        and spec.stride == (1, 1)
        and spec.dilation == (1, 1)
    ):
        return ConvAlgorithm.WINOGRAD
    return ConvAlgorithm.IM2COL_GEMM


def arithmetic_intensity(m: int, n: int, k: int, bytes_per_elem: int = 4) -> float:
    """AI of a GEMM as defined in the paper (§VI.C):

    AI = 2*M*N*K / (bytes * (M*N + K*N + M*K)).
    """
    return (2.0 * m * n * k) / (bytes_per_elem * (m * n + k * n + m * k))
