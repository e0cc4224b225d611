"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need it; skip, not error
from hypothesis import given, settings, strategies as st

from repro.core.conv_spec import ConvSpec
from repro.core.conv2d import conv2d, conv2d_reference
from repro.kernels.gemm import blocked_matmul, matmul_ref
from repro.kernels.im2col_gemm import conv2d_pallas_im2col
from repro.kernels.winograd import conv2d_winograd_pallas


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# Blocked GEMM


@pytest.mark.parametrize("shape", [(5, 7, 3), (64, 256, 128), (100, 300, 200),
                                   (8, 128, 128), (33, 190, 65)])
@pytest.mark.parametrize("variant", ["6loop", "3loop"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blocked_matmul_sweep(shape, variant, dtype):
    m, n, k = shape
    a, b = _rand((m, k), 1, dtype), _rand((k, n), 2, dtype)
    got = blocked_matmul(a, b, variant=variant, interpret=True)
    ref = matmul_ref(a, b)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol
    )


def test_blocked_matmul_explicit_blocks():
    a, b = _rand((64, 256), 3), _rand((256, 512), 4)
    for blk in [(8, 128, 128), (16, 256, 128), (64, 512, 256)]:
        got = blocked_matmul(a, b, block=blk, interpret=True)
        np.testing.assert_allclose(got, matmul_ref(a, b), rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 70), n=st.integers(1, 300), k=st.integers(1, 300),
       seed=st.integers(0, 2**31))
def test_blocked_matmul_property(m, n, k, seed):
    a, b = _rand((m, k), seed), _rand((k, n), seed + 1)
    got = blocked_matmul(a, b, interpret=True)
    np.testing.assert_allclose(got, matmul_ref(a, b), rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Fused im2col+GEMM conv


@pytest.mark.parametrize("case", [
    dict(h=12, w=14, c=5, o=7, k=3, s=1, p=1),
    dict(h=13, w=11, c=4, o=6, k=3, s=2, p=1),
    dict(h=10, w=10, c=3, o=5, k=5, s=1, p=2),
    dict(h=9, w=16, c=8, o=16, k=3, s=3, p=0),
    dict(h=8, w=8, c=16, o=32, k=1, s=1, p=0),
])
def test_im2col_gemm_kernel(case):
    spec = ConvSpec(case["c"], case["o"], (case["k"], case["k"]),
                    (case["s"], case["s"]), (case["p"], case["p"]))
    x = _rand((2, case["h"], case["w"], case["c"]), 11)
    w = _rand((case["k"], case["k"], case["c"], case["o"]), 12)
    got = conv2d_pallas_im2col(x, w, spec, interpret=True)
    ref = conv2d_reference(x, w, spec)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_im2col_gemm_explicit_blocks():
    spec = ConvSpec(8, 16, (3, 3), (1, 1), (1, 1))
    x, w = _rand((1, 16, 16, 8), 13), _rand((3, 3, 8, 16), 14)
    ref = conv2d_reference(x, w, spec)
    for blocks in [(4, 8, 128), (8, 8, 128), (16, 8, 256)]:
        got = conv2d_pallas_im2col(x, w, spec, blocks=blocks, interpret=True)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Winograd kernel (end-to-end)


@pytest.mark.parametrize("case", [
    dict(h=12, w=14, c=5, o=7), dict(h=6, w=6, c=3, o=4),
    dict(h=20, w=26, c=16, o=32), dict(h=13, w=7, c=2, o=9),
])
def test_winograd_conv_end_to_end(case):
    spec = ConvSpec(case["c"], case["o"], (3, 3), (1, 1), (1, 1))
    x = _rand((2, case["h"], case["w"], case["c"]), 31)
    w = _rand((3, 3, case["c"], case["o"]), 32)
    got = conv2d_winograd_pallas(x, w, spec, interpret=True)
    ref = conv2d_reference(x, w, spec)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_winograd_pretransformed_weights():
    from repro.core.winograd import transform_weights

    spec = ConvSpec(4, 6, (3, 3), (1, 1), (1, 1))
    x, w = _rand((1, 12, 12, 4), 33), _rand((3, 3, 4, 6), 34)
    u = transform_weights(w)
    got = conv2d_winograd_pallas(x, u, spec, pretransformed=True, interpret=True)
    np.testing.assert_allclose(got, conv2d_reference(x, w, spec), rtol=5e-4,
                               atol=5e-4)


# ---------------------------------------------------------------------------
# Dispatcher


@settings(max_examples=10, deadline=None)
@given(k=st.sampled_from([1, 3, 5]), s=st.integers(1, 2), seed=st.integers(0, 2**31))
def test_pallas_dispatch_property(k, s, seed):
    spec = ConvSpec(4, 8, (k, k), (s, s), (k // 2, k // 2))
    x = _rand((1, 10, 12, 4), seed)
    w = _rand((k, k, 4, 8), seed + 1)
    got = conv2d(x, w, spec, impl="pallas", interpret=True)
    ref = conv2d_reference(x, w, spec)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)
