"""Public convolution API with per-layer algorithm dispatch.

``conv2d`` is the single entry point used by the model zoo (models/cnn.py)
and the examples.  Routing comes from, in priority order: an explicit
``ConvPlan`` (the planner's cached co-design decision — algorithm, impl and
block sizes resolved once per layer/shape/chip), a ``Planner`` to look one
up, or the per-call selectors in core/conv_spec.py / core/codesign.py.
Execution goes to direct-GEMM / im2col+GEMM / Winograd, optionally through
the Pallas kernels (kernels/) when the impl is 'pallas'.
"""
from __future__ import annotations

from typing import Optional, TYPE_CHECKING

import jax.numpy as jnp

from repro.core.conv_spec import (
    ConvAlgorithm,
    ConvSpec,
    Epilogue,
    select_algorithm,
)
from repro.core.im2col import conv2d_direct_1x1, conv2d_im2col
from repro.core.winograd import conv2d_winograd

if TYPE_CHECKING:  # import cycle: planner imports conv2d for measure mode
    from repro.core.netplan import Layout
    from repro.core.planner import ConvPlan, Planner


def conv2d(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ConvSpec,
    impl: str = "jax",
    interpret: Optional[bool] = None,
    plan: Optional["ConvPlan"] = None,
    planner: Optional["Planner"] = None,
    epilogue: Optional[Epilogue] = None,
    in_layout: Optional["Layout"] = None,
    out_layout: Optional["Layout"] = None,
    pretransformed: bool = False,
    vmem_budget: Optional[int] = None,
) -> jnp.ndarray:
    """Convolve ``x`` (B,H,W,C) with ``w`` (kh,kw,C,O) per ``spec``.

    impl: 'jax' (pure jnp, the reference path) or 'pallas' (TPU kernels;
    ``interpret=True`` executes them on CPU for validation).  When ``plan``
    is given (or resolved via ``planner``) it overrides both the algorithm
    choice and ``impl``, and its block sizes are forwarded to the Pallas
    kernels — no per-call re-selection happens.  ``epilogue`` (bias +
    activation) is fused into the output stage of whichever path runs.

    ``in_layout``/``out_layout`` (core/netplan.Layout) are the network
    executor's inter-layer layout contract: with a non-trivial ``in_layout``
    the input (and the offline-prepared ``w``/``epilogue.bias``) already
    carry block-padded channels and the kernel wrappers pad nothing; with a
    non-trivial ``out_layout`` the channel crop is deferred and the padded
    activation flows to the next planned layer (pallas impl only).

    ``pretransformed`` declares that ``w`` already carries the offline
    Winograd weight transform ((8, 8, C, O) from ``transform_weights`` /
    ``prepare_net_params(pretransform=True)``).  The flag is explicit by
    contract — it is never inferred from weight shapes, because the old
    sniff (``w.shape[0] != spec.kh``) was ambiguous for any kh == 8 kernel,
    whose raw weights are (8, 8, C, O) too.

    ``vmem_budget`` is the VMEM budget the plan was made under (None: the
    ``planner``'s, else the chip's); kernels that size blocks the plan does
    not carry (the fused Winograd kernel's NHWC windows) keep within it.
    """
    if vmem_budget is None and planner is not None:
        vmem_budget = planner.vmem_budget
    if plan is None and planner is not None:
        plan = planner.plan(
            spec, x.shape[1], x.shape[2], batch=x.shape[0], dtype=x.dtype
        )
    if plan is not None:
        algo = plan.algorithm
        impl = plan.impl
    elif spec.algorithm is ConvAlgorithm.AUTO_COST:
        from repro.core.codesign import select_algorithm_by_cost

        algo = select_algorithm_by_cost(spec, x.shape[1], x.shape[2])
    else:
        algo = select_algorithm(spec)
    if impl == "pallas":
        # Imported lazily: kernels are optional at import time.
        from repro.kernels import conv_ops

        return conv_ops.conv2d_pallas(
            x, w, spec, algo, interpret=interpret, plan=plan,
            epilogue=epilogue, in_layout=in_layout, out_layout=out_layout,
            pretransformed=pretransformed, vmem_budget=vmem_budget,
        )
    if (in_layout is not None and in_layout.pad_c) or (
        out_layout is not None and out_layout.pad_c
    ):
        raise ValueError(
            "block-padded channel layouts require impl='pallas' — the pure "
            "jnp paths have no block padding to persist"
        )
    if algo is ConvAlgorithm.DIRECT:
        return conv2d_direct_1x1(x, w, spec, epilogue=epilogue)
    if algo is ConvAlgorithm.WINOGRAD:
        # Offline-prepared weights arrive pre-transformed as (8,8,C,O) —
        # declared by the caller, never sniffed from the shape.
        return conv2d_winograd(
            x, w, spec, pretransformed=pretransformed, epilogue=epilogue,
        )
    return conv2d_im2col(x, w, spec, epilogue=epilogue)


def conv2d_reference(x: jnp.ndarray, w: jnp.ndarray, spec: ConvSpec) -> jnp.ndarray:
    """XLA's own convolution — the oracle every algorithm is tested against."""
    import jax

    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=spec.stride,
        padding=[(spec.padding[0], spec.padding[0]), (spec.padding[1], spec.padding[1])],
        rhs_dilation=spec.dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
