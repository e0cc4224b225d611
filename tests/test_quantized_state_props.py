"""Property tests for the block-wise int8 optimizer-state quantizer
(optim/quantized_state.py), plus plain-pytest edge cases.

The quantizer shares its scaling idiom with the inference path
(core/quant.py: max-abs / 127, clamp floor); the properties pinned here are
the contract both rely on:

  round-trip error    |x - dq(q(x))| <= scale/2 per block (round-to-nearest
                      on a grid of step ``scale``)
  zero preservation   all-zero blocks survive exactly (the 1e-12 floor
                      avoids 0/0, and round(0) == 0)
  shape faithfulness  any shape round-trips to exactly its own shape, with
                      the non-multiple-of-256 tail padded internally and
                      cropped back out

Hypothesis is a dev-extra (pyproject [dev]); the module skips cleanly where
it is not installed so the core suite carries no new dependency.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.quantized_state import BLOCK, QTensor, dequantize, quantize

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402


def _round_trip_bound(x: np.ndarray) -> None:
    """Assert the per-block round-trip error bound on one array."""
    t = quantize(jnp.asarray(x))
    dq = np.asarray(dequantize(t))
    assert dq.shape == x.shape
    assert np.all(np.isfinite(dq))
    scale = np.asarray(t.scale, np.float64)
    flat_err = np.abs(dq.reshape(-1).astype(np.float64)
                      - x.reshape(-1).astype(np.float64))
    n = flat_err.shape[0]
    # Per-element bound: half the step of the block the element lives in
    # (plus fp32 slack for the division/multiplication round trip).
    block_of = np.arange(n) // BLOCK
    bound = scale[block_of] / 2.0
    slack = np.maximum(np.abs(x.reshape(-1)), 1.0) * 1e-6
    assert np.all(flat_err <= bound + slack), (
        float(np.max(flat_err - bound)), float(np.max(scale))
    )


# ---------------------------------------------------------------------------
# Hypothesis properties.

# Bounds must be exactly representable at width 32.
finite_f32 = st.floats(
    min_value=float(np.float32(-1e30)), max_value=float(np.float32(1e30)),
    allow_nan=False, allow_infinity=False, width=32,
)


@settings(max_examples=50, deadline=None)
@given(
    data=st.lists(finite_f32, min_size=1, max_size=700),
)
def test_round_trip_error_bound_random_lengths(data):
    """Arbitrary finite fp32 content at arbitrary (non-multiple-of-BLOCK)
    lengths round-trips within half a quantization step per block."""
    _round_trip_bound(np.asarray(data, np.float32))


@settings(max_examples=25, deadline=None)
@given(
    shape=st.lists(st.integers(1, 9), min_size=1, max_size=4),
    seed=st.integers(0, 2**31 - 1),
    log_scale=st.floats(min_value=-30.0, max_value=30.0),
)
def test_round_trip_extreme_dynamic_range(shape, seed, log_scale):
    """Normal data scaled across ~60 decades of magnitude: the per-block
    scale adapts, the bound holds, nothing overflows to inf or collapses
    to NaN."""
    x = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
    x = x * np.float32(10.0 ** log_scale)
    _round_trip_bound(x)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 4 * BLOCK + 3),
    seed=st.integers(0, 2**31 - 1),
)
def test_mixed_magnitude_blocks(n, seed):
    """Blocks with wildly different magnitudes quantize independently:
    a large block does not destroy a small block's precision."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n).astype(np.float32)
    # Scale alternating BLOCK-sized runs by 1e6.
    for start in range(0, n, 2 * BLOCK):
        x[start:start + BLOCK] *= 1e6
    _round_trip_bound(x)


# ---------------------------------------------------------------------------
# Plain edge cases (run even without hypothesis installed... except that the
# importorskip above is module-level; these double as the enumerated cases
# the properties are seeded around).


def test_zero_tensor_roundtrips_exactly():
    x = jnp.zeros((3, BLOCK + 7))
    t = quantize(x)
    assert bool(jnp.all(t.q == 0))
    dq = dequantize(t)
    np.testing.assert_array_equal(np.asarray(dq), np.zeros((3, BLOCK + 7)))


def test_single_element():
    t = quantize(jnp.asarray([-3.75], jnp.float32))
    dq = dequantize(t)
    assert dq.shape == (1,)
    np.testing.assert_allclose(dq, [-3.75], rtol=1e-2)
    # max-abs calibration: the extreme element itself is exact.
    assert abs(float(dq[0]) + 3.75) <= 3.75 / 127.0 / 2 + 1e-7


def test_scalar_shape():
    t = quantize(jnp.asarray(2.5, jnp.float32))
    dq = dequantize(t)
    assert dq.shape == ()
    np.testing.assert_allclose(float(dq), 2.5, rtol=1e-2)


def test_non_multiple_block_padding_is_invisible():
    """The internal pad to a BLOCK multiple never leaks: a (BLOCK + 1,)
    tensor whose tail element is the block max still reconstructs it."""
    x = np.ones(BLOCK + 1, np.float32) * 0.001
    x[-1] = 100.0
    t = quantize(jnp.asarray(x))
    assert t.q.shape == (2, BLOCK)
    dq = np.asarray(dequantize(t))
    assert dq.shape == (BLOCK + 1,)
    np.testing.assert_allclose(dq[-1], 100.0, rtol=1e-2)


def test_subnormal_block_floor():
    """A block whose max-abs sits below the 1e-12 floor quantizes to zeros
    (not NaN/inf) and dequantizes to exact zeros times the stored scale."""
    x = jnp.full((BLOCK,), 1e-20, jnp.float32)
    t = quantize(x)
    dq = dequantize(t)
    assert bool(jnp.all(jnp.isfinite(dq)))
    # Error is at most the original magnitude (everything rounds to 0).
    assert float(jnp.max(jnp.abs(dq - x))) <= 1e-20


def test_qtensor_is_a_pytree():
    """QTensor flattens/unflattens through jax.tree_util — the property the
    optimizer relies on to carry quantized moments in its state tree."""
    import jax

    t = quantize(jnp.arange(10, dtype=jnp.float32))
    leaves, treedef = jax.tree_util.tree_flatten(t)
    assert len(leaves) == 2
    t2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(t2, QTensor) and t2.shape == (10,)
    np.testing.assert_array_equal(
        np.asarray(dequantize(t2)), np.asarray(dequantize(t))
    )
