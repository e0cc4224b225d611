"""Winograd edge cases: the Pallas kernel (interpret mode) and the jnp
realization (``core/winograd``) against the XLA oracle on every awkward
shape class.

The kernel runs at the chip's VMEM budget and, as ``multi_block``, at the
footprint of one 8-tile block (``winograd_kernel_vmem_bytes(8, bc, bo)``),
where ``winograd_nhwc_blocks`` must split every image into one tile row per
block: odd crops and padded channels then meet multi-block tiling.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.conv_spec import (
    ConvAlgorithm,
    ConvSpec,
    Epilogue,
    apply_epilogue,
)
from repro.core.conv2d import conv2d_reference
from repro.core.vmem_model import winograd_kernel_vmem_bytes
from repro.core.winograd import OUT_TILE, conv2d_winograd, transform_weights
from repro.kernels.winograd import conv2d_winograd_pallas
from repro.kernels.winograd.ops import pick_blocks, winograd_tiling

BUDGETS = ["chip", "multi_block"]


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def _kernel(x, w, spec, budget, blocks=None, **kw):
    """The kernel at the chip's budget, or (``multi_block``) at one 8-tile
    row per block, which the tiling must then be."""
    vmem_budget = None
    if budget == "multi_block":
        b, h, ww, c = x.shape
        oh, ow = spec.out_hw(h, ww)
        nth, ntw = -(-oh // OUT_TILE), -(-ow // OUT_TILE)
        _, bc, bo = blocks or pick_blocks(b * nth * ntw, c, w.shape[-1])
        vmem_budget = winograd_kernel_vmem_bytes(8, bc, bo)
        tiling = winograd_tiling(b, oh, ow, (8, bc, bo),
                                 vmem_budget=vmem_budget)
        bb, k, nw = tiling.nhwc
        assert (bb, nw) == (1, 8) and k < nth, (tiling, nth)
    return conv2d_winograd_pallas(x, w, spec, blocks=blocks, interpret=True,
                                  vmem_budget=vmem_budget, **kw)


@pytest.mark.parametrize("run", BUDGETS + ["jax"])
@pytest.mark.parametrize("h,w", [(10, 14), (13, 7), (9, 16), (11, 23)])
def test_winograd_crop_path(h, w, run):
    """Output sizes not divisible by 6: the tile grid over-covers and the
    final crop must discard exactly the padded rows/cols — in the kernel
    and in the jnp realization the default ``impl`` runs."""
    spec = ConvSpec(4, 8, (3, 3), (1, 1), (1, 1),
                    algorithm=ConvAlgorithm.WINOGRAD)
    oh, ow = spec.out_hw(h, w)
    assert oh % 6 != 0 or ow % 6 != 0
    x = _rand((2, h, w, 4), seed=h * 31 + w)
    wt = _rand((3, 3, 4, 8), seed=3)
    if run == "jax":
        got = conv2d_winograd(x, wt, spec)
    else:
        got = _kernel(x, wt, spec, run)
    ref = conv2d_reference(x, wt, spec)
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("budget", BUDGETS)
@pytest.mark.parametrize("blocks", [(8, 128, 128), (16, 128, 128),
                                    (8, 8, 8), (32, 16, 8)])
def test_winograd_block_padding_path(blocks, budget):
    """T/C/O not divisible by the block tuple: tiles (2*2*3=12), channels (5)
    and out-channels (7) all need zero-padding to block multiples, and the
    padded rows must not leak into the cropped result."""
    spec = ConvSpec(5, 7, (3, 3), (1, 1), (1, 1),
                    algorithm=ConvAlgorithm.WINOGRAD)
    x = _rand((2, 12, 12, 5), seed=sum(blocks))
    wt = _rand((3, 3, 5, 7), seed=5)
    got = _kernel(x, wt, spec, budget, blocks=blocks)
    ref = conv2d_reference(x, wt, spec)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("budget", BUDGETS)
def test_winograd_pretransformed_weights(budget):
    """Offline weight transform (inference mode): (8, 8, C, O) weights skip
    the in-graph G g G^T and must produce identical results."""
    spec = ConvSpec(4, 6, (3, 3), (1, 1), (1, 1))
    x = _rand((1, 13, 17, 4), seed=41)
    wt = _rand((3, 3, 4, 6), seed=42)
    u = transform_weights(wt)
    got = _kernel(x, u, spec, budget, pretransformed=True)
    ref = conv2d_reference(x, wt, spec)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("activation", ["linear", "relu", "leaky"])
@pytest.mark.parametrize("with_bias", [False, True], ids=["nobias", "bias"])
def test_winograd_fused_epilogue_cross_product(activation, with_bias):
    """The megakernel's in-VMEM epilogue (bias + activation on the fp32
    inverse-transform result) across the full cross-product, on a shape that
    exercises the crop and channel-padding paths at once."""
    spec = ConvSpec(5, 9, (3, 3), (1, 1), (1, 1))
    x = _rand((2, 10, 13, 5), seed=51)
    wt = _rand((3, 3, 5, 9), seed=52)
    bias = _rand((9,), seed=53) if with_bias else None
    got = conv2d_winograd_pallas(
        x, wt, spec, interpret=True, bias=bias, activation=activation,
    )
    epi = Epilogue(bias=bias, activation=activation)
    ref = apply_epilogue(conv2d_reference(x, wt, spec), epi)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_winograd_fused_matches_jnp_winograd():
    """The kernel and ``core/winograd`` realize the same tile -> transform ->
    multiply -> inverse decomposition, epilogue included, and agree to fp32
    Winograd rounding.  They round differently (the jnp path contracts the
    transforms with einsum, the kernel sums each transform's nonzero
    coefficients plane by plane), so they agree about as closely as either
    agrees with lax (1.3e-5 apart at outputs of 4.7 here), not bitwise."""
    spec = ConvSpec(4, 8, (3, 3), (1, 1), (1, 1))
    x = _rand((1, 18, 18, 4), seed=61)
    wt = _rand((3, 3, 4, 8), seed=62) / np.sqrt(36)
    bias = _rand((8,), seed=63)
    got = conv2d_winograd_pallas(x, wt, spec, blocks=(8, 128, 128),
                                 interpret=True, bias=bias,
                                 activation="leaky")
    want = conv2d_winograd(x, wt, spec,
                           epilogue=Epilogue(bias=bias, activation="leaky"))
    assert got.shape == want.shape == (1, 18, 18, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_winograd_pick_blocks_budgets_full_footprint():
    """Satellite: pick_blocks must budget the whole kernel footprint (weight
    block + M scratch + output block), not just the input-transform block."""
    for t, c, o in ((4096, 512, 512), (4096, 384, 384), (20, 512, 512)):
        for budget in (1 << 20, 4 << 20, 10 << 20, 16 << 20, 64 << 20):
            bt, bc, bo = pick_blocks(t, c, o, vmem_budget=budget)
            # Never below the (sublane, lane) granularity floor, even
            # when shrinking from a non-power-of-two start (384, 24...).
            assert bt % 8 == 0 and bc % 128 == 0 and bo % 128 == 0
            footprint = winograd_kernel_vmem_bytes(bt, bc, bo)
            # Either the footprint fits, or we are at the floor and
            # cannot shrink further.
            assert footprint <= budget or (bt, bc, bo) == (8, 128, 128)
