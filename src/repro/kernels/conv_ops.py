"""Kernel-level dispatcher used by core.conv2d(impl='pallas').

Routes per the paper's selector: 1x1 -> blocked GEMM (direct), 3x3 stride-1
-> Winograd kernels, everything else -> fused im2col+GEMM kernel.  When a
``ConvPlan`` is supplied the kernels run with its autotuned block sizes
instead of their built-in heuristics, and an im2col plan that folds its taps
runs the patch matrix through the blocked GEMM kernel instead.

With an explicit ``Layout`` pair (core/netplan.py) the dispatcher runs the
network executor's contract instead of the self-contained wrappers: the
input activation (and the offline-prepared weights/bias) already carry
block-padded channels, so no channel pads enter the jaxpr here, and with a
non-trivial ``out_layout`` the channel crop is deferred — the padded
activation flows straight into the next layer's pallas_call.
"""
from __future__ import annotations

from typing import Optional, TYPE_CHECKING

import jax.numpy as jnp

from repro.core.conv_spec import ConvAlgorithm, ConvSpec, Epilogue
from repro.util import ceil_to, pad_bias_row

if TYPE_CHECKING:
    from repro.core.netplan import Layout
    from repro.core.planner import ConvPlan


def conv2d_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    algo: ConvAlgorithm,
    interpret: Optional[bool] = None,
    plan: Optional["ConvPlan"] = None,
    epilogue: Optional[Epilogue] = None,
    in_layout: Optional["Layout"] = None,
    out_layout: Optional["Layout"] = None,
    pretransformed: bool = False,
    vmem_budget: Optional[int] = None,
) -> jnp.ndarray:
    """x (B,H,W,C), w (kh,kw,C,O) -> (B,OH,OW,O) via Pallas kernels.

    ``epilogue`` (bias + activation, plus the int8 dequant ``scale``) is
    forwarded into each kernel family's output stage — no separate
    elementwise pass over HBM.  An int8 ``x`` requires an epilogue scale and
    never routes to Winograd: the F(6, 3) transform amplifies the data range
    past the int8 error budget (core/quant.py::winograd_int8_budget_ok), so
    the planner rewrites such layers to im2col/direct or keeps them fp32.
    ``pretransformed`` declares offline Winograd-transformed weights
    ((8, 8, C, O)); it is an explicit contract, never inferred from the
    weight shape (raw kh == 8 kernels share that shape).  ``vmem_budget``
    (None: the chip's VMEM) bounds the fused Winograd kernel's NHWC blocks,
    which the plan does not carry.
    """
    import jax

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    blocks = plan.kernel_blocks if plan is not None else None
    bias = epilogue.bias if epilogue is not None else None
    activation = epilogue.activation if epilogue is not None else "linear"
    scale = epilogue.scale if epilogue is not None else None
    if x.dtype == jnp.int8:
        assert scale is not None, "int8 conv requires an epilogue dequant scale"
        assert algo is not ConvAlgorithm.WINOGRAD, (
            "int8 never routes to Winograd (transform-stage error budget)"
        )

    if in_layout is not None or out_layout is not None:
        return _conv2d_pallas_laidout(
            x, w, spec, algo, blocks, interpret, bias, activation,
            in_layout, out_layout, plan, pretransformed, scale, vmem_budget,
        )

    if algo is ConvAlgorithm.DIRECT:
        from repro.kernels.gemm import blocked_matmul

        sh, sw = spec.stride
        ph, pw = spec.padding
        # Pad BEFORE subsampling, exactly like core.im2col.conv2d_direct_1x1:
        # dropping spec.padding here silently shrank the output (wrong shape
        # *and* values for any padded 1x1 layer).
        if ph or pw:
            x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        if (sh, sw) != (1, 1):
            x = x[:, ::sh, ::sw, :]
        b, oh, ow, c = x.shape
        out = blocked_matmul(
            x.reshape(b * oh * ow, c),
            w.reshape(c, spec.out_channels),
            block=blocks,
            interpret=interpret,
            bias=bias,
            activation=activation,
            scale=scale,
        )
        return out.reshape(b, oh, ow, spec.out_channels)

    if algo is ConvAlgorithm.WINOGRAD:
        from repro.kernels.winograd import conv2d_winograd_pallas

        # The single-pass fused megakernel is the default; a plan can pin
        # the 3-pass pipeline (e.g. a measure-mode planner that timed both).
        fused = plan.winograd_fused if plan is not None else True
        return conv2d_winograd_pallas(
            x, w, spec, blocks=blocks, interpret=interpret,
            pretransformed=pretransformed,
            bias=bias, activation=activation, fused=fused,
            vmem_budget=vmem_budget,
        )

    if plan is not None and plan.taps_folded:
        from repro.kernels.im2col_gemm.ops import (
            conv2d_folded_call,
            fold_weights,
            folded_k,
        )

        assert scale is None, "int8 im2col runs tap-wise"
        kp = folded_k(spec, x.shape[-1], blocks[2])
        out = conv2d_folded_call(
            x, fold_weights(w, kp), spec, blocks, interpret=interpret,
            bias=bias, activation=activation,
        )
        return out[..., :spec.out_channels]

    from repro.kernels.im2col_gemm import conv2d_pallas_im2col

    return conv2d_pallas_im2col(
        x, w, spec, blocks=blocks, interpret=interpret,
        bias=bias, activation=activation, scale=scale,
    )


def _conv2d_pallas_laidout(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    algo: ConvAlgorithm,
    blocks,
    interpret: bool,
    bias: Optional[jnp.ndarray],
    activation: str,
    in_layout: Optional["Layout"],
    out_layout: Optional["Layout"],
    plan: Optional["ConvPlan"],
    pretransformed: bool = False,
    scale: Optional[jnp.ndarray] = None,
    vmem_budget: Optional[int] = None,
) -> jnp.ndarray:
    """Executor path: channels pre-padded in, channel crop deferred out.

    Contract (enforced by core/netplan): ``x``'s channel count equals
    ``in_layout.phys_c`` and divides the plan's channel block; ``w``/``bias``
    were padded offline to (in phys, out phys), and a plan that folds its
    taps gets ``w`` folded offline to (folded K, out phys); the out-channel
    padding is zeros-in → act(0 + 0) = 0 out, so a deferred crop is exact.
    Whatever padding remains here (row-tile tails, tile-count alignment, the
    M tail of the direct GEMM, a folded patch matrix's K and M tails) is
    intra-layer data movement the boundary cannot remove.
    """
    o_keep = (
        out_layout.phys_c
        if out_layout is not None and out_layout.pad_c
        else spec.out_channels
    )
    if in_layout is not None:
        assert x.shape[-1] == in_layout.phys_c, (x.shape, in_layout)

    if plan is not None and plan.taps_folded:
        from repro.kernels.im2col_gemm.ops import conv2d_folded_call, folded_k

        assert scale is None, "int8 im2col runs tap-wise"
        assert w.shape[0] == folded_k(spec, x.shape[-1], blocks[2]), (
            w.shape, x.shape)
        out = conv2d_folded_call(
            x, w, spec, blocks, interpret=interpret, bias=bias,
            activation=activation,
        )
        return out[..., :o_keep] if out.shape[-1] != o_keep else out

    assert w.shape[2] == x.shape[-1], (w.shape, x.shape)

    if algo is ConvAlgorithm.DIRECT:
        from repro.kernels.gemm.ops import (
            default_block,
            matmul_padded_call,
            pad_gemm_operands,
        )

        sh, sw = spec.stride
        ph, pw = spec.padding
        if ph or pw:
            x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
        if (sh, sw) != (1, 1):
            x = x[:, ::sh, ::sw, :]
        b, oh, ow, cp = x.shape
        a = x.reshape(b * oh * ow, cp)
        w2 = w.reshape(cp, w.shape[-1])
        m = a.shape[0]
        if blocks is None:
            cfg = default_block(
                m, w2.shape[1], cp, jnp.dtype(x.dtype).itemsize
            )
            blocks = (cfg.bm, cfg.bn, cfg.bk)
        a_p, b_p, bias_p = pad_gemm_operands(a, w2, blocks, bias=bias)
        scale_p = pad_bias_row(scale, b_p.shape[1])
        out = matmul_padded_call(
            a_p, b_p, blocks, interpret=interpret,
            bias_p=bias_p, activation=activation, scale_p=scale_p,
        )
        if out.shape != (m, o_keep):
            out = out[:m, :o_keep]
        return out.reshape(b, oh, ow, o_keep)

    if algo is ConvAlgorithm.WINOGRAD:
        from repro.core.winograd import transform_weights
        from repro.kernels.winograd.ops import (
            conv2d_winograd_padded_call,
            pick_blocks,
        )

        assert scale is None, "int8 never routes to Winograd"

        b, h, ww, cp = x.shape
        oh, ow = spec.out_hw(h, ww)
        # Offline-prepared weights arrive pre-transformed as (8, 8, Cp, Op);
        # the executor carries the flag explicitly (no shape sniffing).
        u = w if pretransformed else transform_weights(w, x.dtype)
        if blocks is None:
            t = b * -(-oh // 6) * -(-ow // 6)
            blocks = pick_blocks(
                t, cp, u.shape[-1], vmem_budget=vmem_budget,
                dtype_bytes=jnp.dtype(x.dtype).itemsize,
            )
        bt, bc, bo = blocks
        op = ceil_to(u.shape[-1], bo)
        if op != u.shape[-1]:
            u = jnp.pad(u, ((0, 0), (0, 0), (0, 0), (0, op - u.shape[-1])))
        bias_p = pad_bias_row(bias, op)
        fused = plan.winograd_fused if plan is not None else True
        y = conv2d_winograd_padded_call(
            x, u, oh, ow, blocks, interpret=interpret,
            bias_p=bias_p, activation=activation, fused=fused,
            padding=spec.padding, vmem_budget=vmem_budget,
        )
        return y[..., :o_keep] if y.shape[-1] != o_keep else y

    from repro.kernels.im2col_gemm.ops import (
        conv2d_im2col_padded_call,
        pick_blocks,
    )

    _, h, ww, cp = x.shape
    kh, kw, _, o_phys = w.shape
    oh, ow = spec.out_hw(h, ww)
    if blocks is None:
        blocks = pick_blocks(
            oh, ow, cp, o_phys, kh, kw, *spec.stride,
            jnp.dtype(x.dtype).itemsize,
        )
    op = ceil_to(o_phys, blocks[2])
    w_p = (
        jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, op - o_phys)))
        if op != o_phys else w
    )
    out = conv2d_im2col_padded_call(
        x, w_p, spec, oh, ow, blocks, interpret=interpret,
        bias_p=pad_bias_row(bias, op), activation=activation,
        scale_p=pad_bias_row(scale, op),
    )
    return out[..., :o_keep] if out.shape[-1] != o_keep else out
