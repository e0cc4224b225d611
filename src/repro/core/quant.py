"""Int8 inference quantization: offline scale computation + policy.

The paper's co-design thesis is that shrinking the working set is the
dominant lever for CNN inference throughput; int8 is the same lever applied
to dtype — quantizing activations and weights halves-to-quarters HBM
traffic on the im2col+GEMM side.  This module holds everything that happens
*offline* (scales, weight quantization, calibration) plus the two planner
policies that decide *whether* a layer quantizes:

  - traffic benefit: a layer only quantizes when its modeled int8 GEMM
    bytes are at most ``INT8_TRAFFIC_THRESHOLD`` times its fp32 bytes
    (``int8_traffic_ratio``).  A cin=3 stem layer, whose fp32 output write
    dominates, fails this test and stays fp32 — the bytes win would not pay
    for the quantization noise.
  - Winograd error budget: the F(6, 3) input transform amplifies the data
    range by ``winograd_transform_amplification()`` (~36x for our B^T), so
    an int8 V-matrix loses ~20*log10(amp) dB of SQNR.  Unless the estimate
    clears the budget (it does not for F(6, 3)), Winograd layers fall back
    to fp32 — cf. Maji et al.'s transform-stage precision handling.

Quantization scheme (symmetric, round-to-nearest, [-127, 127]):

  activations  per-input-channel scales sx (C,), calibrated offline from a
               sample batch (max-abs over B, H, W).  The per-channel scales
               are *folded into the weights* before weight quantization, so
               the kernel-side dequant stays a single per-output-channel
               row — the only granularity that factors out of the K
               reduction.
  weights      per-output-channel scales sw (O,) on the activation-folded
               weights w * sx[c].
  kernel       int8 x int8 -> int32 accumulation; the fused epilogue
               dequantizes on the accumulator (y = acc * sw + bias, then
               activation) and writes fp32 — inter-layer activations stay
               fp32, each int8 layer re-quantizes at entry with its static
               calibrated scales (a cheap fused elementwise pass; the GEMM
               reads, which dominate by the kh*kw reuse factor, are int8).

The block-scaling idiom (max-abs / 127 with a clamp floor) is shared with
``optim/quantized_state.py``; here the block axis is a channel, there a
flat 256-element run.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

QMAX = 127.0
SCALE_FLOOR = 1e-12        # all-zero channels quantize to zeros, not NaNs
INT8_TRAFFIC_THRESHOLD = 0.5
WINOGRAD_SQNR_BUDGET_DB = 30.0


# ---------------------------------------------------------------------------
# Scale computation / (de)quantization primitives


def activation_scales(x, axis: Optional[Tuple[int, ...]] = None):
    """Per-channel symmetric scales for an NHWC activation: amax/127.

    ``axis`` defaults to all-but-last (per-channel over B, H, W).  Returns
    fp32 (C,) with the ``SCALE_FLOOR`` clamp so dead channels stay finite.
    """
    import jax.numpy as jnp

    if axis is None:
        axis = tuple(range(x.ndim - 1))
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis)
    return jnp.maximum(amax / QMAX, SCALE_FLOOR)


def quantize_activation(x, scale):
    """x / scale, round-to-nearest, clip to [-127, 127], int8.

    ``scale`` is the per-channel (C,) calibration vector (broadcast over
    B, H, W).  Runs inside the jitted forward — XLA fuses it into a single
    elementwise pass feeding the int8 kernel.
    """
    import jax.numpy as jnp

    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


def quantize_conv_weights(w, x_scale):
    """Per-output-channel int8 weights with the activation scales folded in.

    w (kh, kw, C, O) fp32, x_scale (C,) -> (wq int8 (kh, kw, C, O),
    w_scale fp32 (O,)).  The folded weights w' = w * x_scale[c] make the
    kernel's integer product xq * wq ≈ (x / sx) * (w * sx) = x * w, so the
    dequant epilogue is a single per-output-channel row:

        y[o] ≈ w_scale[o] * sum_k xq * wq    (int32 accumulation)

    Zero-padded output channels get scale SCALE_FLOOR and all-zero int8
    weights, preserving the layout-elision invariant act(0 + 0) = 0.
    """
    import jax.numpy as jnp

    wf = w.astype(jnp.float32) * x_scale[None, None, :, None]
    amax = jnp.max(jnp.abs(wf), axis=(0, 1, 2))
    w_scale = jnp.maximum(amax / QMAX, SCALE_FLOOR)
    wq = jnp.clip(jnp.round(wf / w_scale), -QMAX, QMAX).astype(jnp.int8)
    return wq, w_scale


def sqnr_db(ref, test) -> float:
    """Signal-to-quantization-noise ratio in dB (fp64, conformance gate)."""
    ref = np.asarray(ref, np.float64)
    err = np.asarray(test, np.float64) - ref
    sig = float(np.sum(ref * ref))
    noise = float(np.sum(err * err))
    if noise == 0.0:
        return float("inf")
    return 10.0 * np.log10(max(sig, 1e-300) / noise)


# ---------------------------------------------------------------------------
# Offline calibration (fp32 reference walk)


def default_calibration_batch(h: int, w: int, in_channels: int,
                              batch: int = 2, seed: int = 0):
    """Deterministic synthetic calibration batch (standard-normal).

    Used when ``repro.compile(..., ExecutionOptions(dtype='int8'))`` gets no
    calibration data — zero caller changes, documented accuracy caveat: real
    sample inputs calibrate the activation ranges better.
    """
    import jax

    return jax.random.normal(
        jax.random.PRNGKey(seed), (batch, h, w, in_channels), "float32"
    )


def calibrate_activation_scales(
    netplan, folded_params: Sequence[Dict], x,
) -> Dict[int, Any]:
    """Per-conv-step activation scales from an fp32 oracle walk.

    Walks the layer table exactly like ``netplan.run_network`` but on
    *logical* (unpadded) channels through ``conv2d_reference``, recording
    each conv input's per-channel max-abs.  Returns {step index: (C,) fp32
    scales} for every conv step.  Runs eagerly, offline — the scales become
    constants of the jitted int8 forward.
    """
    import jax.numpy as jnp

    from repro.core.conv2d import conv2d_reference
    from repro.core.conv_spec import (
        Epilogue,
        apply_activation,
        apply_epilogue,
        max_pool,
    )

    scales: Dict[int, Any] = {}
    outputs: List[Any] = []
    cur = jnp.asarray(x, jnp.float32)
    for s in netplan.steps:
        l = s.layer
        p = folded_params[s.index]
        if l.kind == "conv":
            scales[s.index] = activation_scales(cur)
            y = conv2d_reference(cur, p["w"].astype(jnp.float32), s.spec)
            cur = apply_epilogue(
                y, Epilogue(bias=p["b"], activation=l.activation)
            )
        elif l.kind == "maxpool":
            cur = max_pool(cur, l)
        elif l.kind == "avgpool":
            cur = cur.mean(axis=(1, 2))
        elif l.kind == "upsample":
            cur = jnp.repeat(jnp.repeat(cur, l.size, axis=1), l.size, axis=2)
        elif l.kind == "shortcut":
            cur = apply_activation(cur + outputs[l.from_layers[0]],
                                   l.activation)
        elif l.kind == "route":
            cur = jnp.concatenate([outputs[j] for j in l.from_layers], axis=-1)
        elif l.kind == "fc":
            if cur.ndim == 4:
                cur = cur.mean(axis=(1, 2))
            cur = apply_activation(cur @ p["w"] + p["b"], l.activation)
        outputs.append(cur)
    return scales


# ---------------------------------------------------------------------------
# Planner policies


def int8_traffic_ratio(spec, h: int, w: int, batch: int = 1) -> float:
    """Modeled int8 / fp32 HBM bytes of this layer's im2col+GEMM.

    int8 moves int8 activations + int8 weights but still writes an fp32
    output (inter-layer activations stay fp32); the ratio is what the
    quantization policy gates on.
    """
    from repro.core.vmem_model import im2col_gemm_traffic_bytes

    oh, ow = spec.out_hw(h, w)
    fp32 = im2col_gemm_traffic_bytes(
        oh, ow, spec.in_channels, spec.out_channels, spec.kh, spec.kw,
        batch=batch, dtype_bytes=4, out_dtype_bytes=4,
    )
    q8 = im2col_gemm_traffic_bytes(
        oh, ow, spec.in_channels, spec.out_channels, spec.kh, spec.kw,
        batch=batch, dtype_bytes=1, out_dtype_bytes=4,
    )
    return q8 / fp32


def int8_worthwhile(spec, h: int, w: int, batch: int = 1,
                    threshold: float = INT8_TRAFFIC_THRESHOLD) -> bool:
    """The quantization-benefit gate: bytes ratio must clear the threshold.

    Quantization noise is only paid for when the HBM-bytes win is
    substantial; a stem layer (cin=3) whose fp32 output write dominates
    stays fp32.
    """
    return int8_traffic_ratio(spec, h, w, batch) <= threshold


def winograd_transform_amplification() -> float:
    """Worst-case data-range growth of the F(6, 3) input transform.

    V = B^T d B, so max|V| <= (max row-sum |B^T|)^2 * max|d| — the factor an
    int8 quantization grid for V must stretch by relative to quantizing d
    directly.  Computed from the repo's actual B^T matrix (not a literature
    constant) so a transform change re-prices the policy automatically.
    """
    from repro.core.winograd import BT

    row_sum = float(np.max(np.sum(np.abs(BT), axis=1)))
    return row_sum * row_sum


def winograd_int8_sqnr_estimate_db() -> float:
    """Estimated SQNR of an int8 F(6, 3) transform stage.

    Uniform-quantizer baseline SQNR for a max-abs-calibrated int8 grid is
    20*log10(127*sqrt(12)/kappa) with kappa ~ amax/sigma ~ 4 for conv
    activations; the transform multiplies the grid step by the
    amplification factor, subtracting 20*log10(amp) dB.
    """
    kappa = 4.0
    base = 20.0 * np.log10(QMAX * np.sqrt(12.0) / kappa)
    return float(base - 20.0 * np.log10(winograd_transform_amplification()))


def winograd_int8_budget_ok(
    threshold_db: float = WINOGRAD_SQNR_BUDGET_DB,
) -> bool:
    """Whether int8 Winograd clears the transform-stage error budget.

    False for F(6, 3) (the ~36x amplification costs ~31 dB, leaving the
    estimate far below the 30 dB conformance gate), so the planner runs
    Winograd layers in fp32 — or re-routes them to int8 im2col+GEMM when
    the cost model prices that faster.  The policy is a function, not a
    constant: a smaller-tile transform (e.g. F(2, 3)) could pass.
    """
    return winograd_int8_sqnr_estimate_db() >= threshold_db
