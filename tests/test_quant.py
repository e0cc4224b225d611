"""Unit tests for the int8 quantization machinery (core/quant.py) and the
planner policy that decides, per layer, whether an int8 request actually
executes in int8.

The conformance suite (test_conv_conformance.py) owns the kernel-vs-oracle
SQNR gates; this file pins the offline pieces: scale computation, the
round-trip error bound, the Winograd error budget, the traffic gate, and
the v5 plan-cache semantics of per-layer dtype resolution.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.conv_spec import ConvAlgorithm, ConvSpec
from repro.core.quant import (
    INT8_TRAFFIC_THRESHOLD,
    QMAX,
    WINOGRAD_SQNR_BUDGET_DB,
    activation_scales,
    int8_traffic_ratio,
    int8_worthwhile,
    quantize_activation,
    quantize_conv_weights,
    sqnr_db,
    winograd_int8_budget_ok,
    winograd_int8_sqnr_estimate_db,
    winograd_transform_amplification,
)


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), jnp.float32
    )


# ---------------------------------------------------------------------------
# Scales and round-trip error.


def test_activation_scales_per_channel():
    x = _rand((2, 6, 6, 4), 0) * jnp.asarray([1.0, 10.0, 0.1, 100.0])
    s = activation_scales(x, axis=(0, 1, 2))
    assert s.shape == (4,)
    np.testing.assert_allclose(
        s, jnp.max(jnp.abs(x), axis=(0, 1, 2)) / QMAX, rtol=1e-6
    )


def test_quantize_activation_round_trip_bound():
    """|x - dequant(quant(x))| <= scale/2 elementwise: symmetric
    round-to-nearest with a per-channel scale covering the range."""
    x = _rand((2, 8, 8, 8), 1) * jnp.asarray([0.01 * (i + 1) for i in range(8)])
    s = activation_scales(x, axis=(0, 1, 2))
    xq = quantize_activation(x, s)
    assert xq.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(xq.astype(jnp.int32)))) <= 127
    dq = xq.astype(jnp.float32) * s
    assert float(jnp.max(jnp.abs(x - dq))) <= float(jnp.max(s)) / 2 + 1e-7


def test_quantize_activation_zero_channel_survives():
    """An all-zero channel gets the scale floor, quantizes to 0, and
    dequantizes back to exactly 0 — no NaN/inf from a 0/0."""
    x = _rand((1, 4, 4, 3), 2).at[..., 1].set(0.0)
    s = activation_scales(x, axis=(0, 1, 2))
    assert bool(jnp.all(s > 0))
    xq = quantize_activation(x, s)
    assert bool(jnp.all(xq[..., 1] == 0))
    assert bool(jnp.all(jnp.isfinite(xq.astype(jnp.float32) * s)))


def test_quantize_conv_weights_folds_input_scales():
    """The per-input-channel activation scale is folded into the weights
    before per-output-channel quantization: dequantized effective weights
    reproduce w * sx to within the weight quantization step."""
    w = _rand((3, 3, 4, 8), 3) * 0.3
    sx = jnp.asarray([0.5, 1.0, 2.0, 4.0]) / QMAX
    wq, ws = quantize_conv_weights(w, sx)
    assert wq.dtype == jnp.int8 and ws.shape == (8,)
    eff = wq.astype(jnp.float32) * ws          # folded-weight reconstruction
    want = w * sx[None, None, :, None]
    assert float(jnp.max(jnp.abs(eff - want))) <= float(jnp.max(ws)) / 2 + 1e-7


def test_sqnr_db_basics():
    x = _rand((64,), 4)
    assert sqnr_db(x, x) == float("inf")
    noisy = x + 0.01 * _rand((64,), 5)
    q = sqnr_db(x, noisy)
    assert 20.0 < q < 60.0
    # Scaling both signals together leaves SQNR unchanged.
    assert abs(sqnr_db(10 * x, 10 * noisy) - q) < 1e-6


# ---------------------------------------------------------------------------
# The Winograd int8 error budget: F(6, 3) fails it, so int8 3x3 layers run
# im2col+GEMM.


def test_winograd_amplification_exceeds_budget():
    amp = winograd_transform_amplification()
    assert amp > 10.0  # F(6, 3) BT row sums are large by construction
    est = winograd_int8_sqnr_estimate_db()
    assert est < WINOGRAD_SQNR_BUDGET_DB
    assert not winograd_int8_budget_ok()
    # A sufficiently lax budget would pass — the predicate reads its
    # threshold rather than hard-coding False.
    assert winograd_int8_budget_ok(threshold_db=est - 1.0)


# ---------------------------------------------------------------------------
# The traffic gate.


def test_traffic_gate_rejects_shallow_accepts_deep():
    deep = ConvSpec(256, 512, (3, 3), (1, 1), (1, 1))
    entry = ConvSpec(3, 64, (3, 3), (1, 1), (1, 1))
    assert int8_worthwhile(deep, 32, 32)
    assert not int8_worthwhile(entry, 224, 224), (
        "cin=3: fp32 output writes dominate, int8 saves < 2x"
    )
    r = int8_traffic_ratio(deep, 32, 32)
    assert 0.25 <= r <= INT8_TRAFFIC_THRESHOLD


# ---------------------------------------------------------------------------
# Planner policy: per-layer dtype resolution, v5 cache round-trip.


def _plan(spec, h=16, w=16, dtype="int8", **kw):
    from repro.core.planner import Planner

    return Planner(impl="pallas", cache_path=None, **kw).plan(
        spec, h, w, dtype=dtype
    )


def test_planner_int8_deep_3x3_is_im2col():
    p = _plan(ConvSpec(256, 512, (3, 3), (1, 1), (1, 1)))
    assert p.dtype == "int8"
    assert p.algorithm is ConvAlgorithm.IM2COL_GEMM, (
        "int8 3x3 must not route to Winograd"
    )


def test_planner_int8_1x1_is_direct():
    """1x1 convs quantize only where the weight bytes dominate (tiny
    spatial dims — YOLO's deep 1x1s at low resolution); there the int8
    plan keeps the DIRECT GEMM route."""
    spec = ConvSpec(256, 512, (1, 1), (1, 1), (0, 0))
    p = _plan(spec, h=4, w=4)
    assert p.dtype == "int8"
    assert p.algorithm is ConvAlgorithm.DIRECT
    # At large spatial dims the fp32 output write dominates and the same
    # layer stays fp32 — the gate is shape-aware, not kernel-size-aware.
    assert _plan(spec, h=64, w=64).dtype == "float32"


def test_planner_int8_entry_layer_stays_fp32():
    p = _plan(ConvSpec(3, 64, (3, 3), (1, 1), (1, 1)), h=64, w=64)
    assert p.dtype == "float32", (
        "the traffic gate must keep the cin=3 entry conv fp32"
    )


def test_planner_int8_beats_fp32_prediction():
    """Where int8 is chosen, its modeled time beats the fp32 plan for the
    same layer — the policy never quantizes at a predicted slowdown."""
    spec = ConvSpec(256, 512, (3, 3), (1, 1), (1, 1))
    p8 = _plan(spec)
    p32 = _plan(spec, dtype="float32")
    assert p8.dtype == "int8"
    assert p8.predicted_s < p32.predicted_s


def test_planner_measure_mode_delegates_int8_to_cost_model():
    """Quantization is a policy decision, not a measurement: measure-mode
    planners resolve int8 through the same cost-model gate."""
    from repro.core.planner import Planner

    planner = Planner(impl="pallas", mode="measure", cache_path=None)
    p = planner.plan(ConvSpec(256, 512, (3, 3), (1, 1), (1, 1)), 16, 16,
                     dtype="int8")
    assert p.dtype == "int8"
    assert p.source == "cost_model"


def test_plan_dtype_cache_round_trip(tmp_path):
    """The resolved per-layer dtype rides the plan entry (since v5), and a
    warm planner re-tunes nothing for the same int8 request."""
    from repro.core.planner import PLAN_CACHE_VERSION, Planner

    assert PLAN_CACHE_VERSION >= 5
    cache = str(tmp_path / "plans.json")
    spec = ConvSpec(128, 256, (3, 3), (1, 1), (1, 1))
    p1 = Planner(impl="pallas", cache_path=cache)
    a = p1.plan(spec, 16, 16, dtype="int8")
    b = p1.plan(spec, 16, 16, dtype="float32")
    assert (a.dtype, b.dtype) == ("int8", "float32")
    p1.save()
    p2 = Planner(impl="pallas", cache_path=cache)
    a2 = p2.plan(spec, 16, 16, dtype="int8")
    b2 = p2.plan(spec, 16, 16, dtype="float32")
    assert p2.stats["tunes"] == 0, "warm v5 cache must re-tune nothing"
    assert a2.dtype == "int8" and b2.dtype == "float32"
    assert a2.algorithm is a.algorithm and a2.kernel_blocks == a.kernel_blocks


def test_execution_options_int8_surface():
    """ExecutionOptions: 'int8' validates, input_dtype stays fp32 (images
    are never cast to int8 at the boundary), and unknown dtypes are
    rejected loudly."""
    from repro.api import ExecutionOptions

    o = ExecutionOptions(dtype="int8")
    assert o.dtype == "int8" and o.input_dtype == "float32"
    assert ExecutionOptions(dtype="float32").input_dtype == "float32"
    with pytest.raises(ValueError, match="dtype"):
        ExecutionOptions(dtype="int4")


def test_calibration_walk_matches_entry_distribution():
    """calibrate_activation_scales records scales at each conv's *input*:
    for the first conv they must equal the calibration batch's own
    per-channel scales."""
    from repro.core.netplan import plan_network
    from repro.core.planner import Planner
    from repro.core.quant import calibrate_activation_scales
    from repro.models.cnn import CNNLayer, fold_batchnorm, init_cnn

    layers = (
        CNNLayer("conv", out_channels=32, kernel=3, activation="relu"),
        CNNLayer("conv", out_channels=48, kernel=3, activation="leaky"),
    )
    params = fold_batchnorm(
        init_cnn(jax.random.PRNGKey(0), layers, in_channels=16), layers
    )
    netplan = plan_network(
        layers, 16, 16, Planner(impl="jax", cache_path=None),
        in_channels=16, batch=1,
    )
    x = _rand((2, 16, 16, 16), 6)
    scales = calibrate_activation_scales(netplan, params, x)
    assert set(scales) == {0, 1}
    np.testing.assert_allclose(
        scales[0], activation_scales(x, axis=(0, 1, 2)), rtol=1e-6
    )
    assert scales[1].shape == (32,)
    assert bool(jnp.all(scales[1] > 0))
