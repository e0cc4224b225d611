"""Cross-product conv conformance suite: every algorithm x impl x shape
variant x epilogue mode against the XLA oracle.

Routing gaps (like the Pallas DIRECT path silently dropping padding) cannot
land silently again: each eligible (algorithm, impl, stride, padding,
kernel, epilogue) cell is asserted against ``conv2d_reference`` followed by
the unfused reference epilogue.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.conv_spec import (
    ConvAlgorithm,
    ConvSpec,
    Epilogue,
    apply_epilogue,
)
from repro.core.conv2d import conv2d, conv2d_reference


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def _eligible(algo: ConvAlgorithm, k: int, s: int) -> bool:
    """Which forced algorithms can run a (k, k) stride-s conv at all."""
    if algo is ConvAlgorithm.DIRECT:
        return k == 1
    if algo is ConvAlgorithm.WINOGRAD:
        return k == 3 and s == 1
    return True  # im2col+GEMM is the generic path


ALGOS = [ConvAlgorithm.DIRECT, ConvAlgorithm.IM2COL_GEMM, ConvAlgorithm.WINOGRAD]


@pytest.mark.parametrize("algo", ALGOS, ids=lambda a: a.value)
@pytest.mark.parametrize("impl", ["jax", "pallas"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("pad", [0, 1])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "epilogue"])
def test_conv_conformance(algo, impl, stride, pad, k, fused):
    if not _eligible(algo, k, stride):
        pytest.skip(f"{algo.value} ineligible for k={k} s={stride}")
    spec = ConvSpec(4, 8, (k, k), (stride, stride), (pad, pad), algorithm=algo)
    oh, ow = spec.out_hw(10, 12)
    assert oh >= 1 and ow >= 1
    x = _rand((2, 10, 12, 4), seed=k * 100 + stride * 10 + pad)
    w = _rand((k, k, 4, 8), seed=7)
    epi = (
        Epilogue(bias=_rand((8,), seed=9), activation="leaky")
        if fused else None
    )
    got = conv2d(x, w, spec, impl=impl, interpret=True, epilogue=epi)
    ref = apply_epilogue(conv2d_reference(x, w, spec), epi)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_im2col_pick_blocks_budgets_full_footprint():
    """Satellite: the im2col pick_blocks must budget the whole per-program
    footprint — the (kh, kw, bc, bo) weight block and the bias row on top
    of the input slab and accumulator the old heuristic stopped at
    (mirroring the PR 3 fix to the Winograd pick_blocks)."""
    from repro.core.vmem_model import im2col_kernel_vmem_bytes
    from repro.kernels.im2col_gemm.ops import pick_blocks

    for oh, ow, c, o in (
        (16, 16, 512, 1024),      # deep layer: weight block dominates
        (224, 224, 64, 64),       # shallow layer: row window dominates
        (32, 32, 384, 768),
    ):
        for budget in (1 << 20, 3 << 20, 8 << 20, 64 << 20):
            toh, bc, bo = pick_blocks(oh, ow, c, o, vmem_budget=budget)
            assert toh >= 1 and bc % 128 == 0 and bo % 128 == 0
            footprint = im2col_kernel_vmem_bytes(toh, ow, bc, bo)
            # Either the full footprint fits, or every knob is at its floor.
            assert footprint <= budget or (toh, bc, bo) == (1, 128, 128), (
                (oh, ow, c, o), budget, (toh, bc, bo), footprint
            )

    # The confirmed gap: a config where the old heuristic (input slab +
    # accumulator only) accepts blocks whose *full* footprint overflows.
    budget = 3 << 20
    toh, bc, bo = pick_blocks(16, 16, 512, 1024, vmem_budget=budget)
    assert im2col_kernel_vmem_bytes(toh, 16, bc, bo) <= budget
    old_slab_only = (
        2 * 18 * 18 * 128 * 4 <= 2 * budget // 3     # old bc check passes
        and 16 * 16 * 256 * 4 <= budget // 3         # old toh check passes
    )
    overflow = im2col_kernel_vmem_bytes(16, 16, 128, 256) > budget
    assert old_slab_only and overflow, (
        "test setup: the old heuristic should overflow here"
    )


def test_pallas_direct_1x1_padding_regression():
    """The confirmed DIRECT-path bug: kernels/conv_ops.py subsampled
    x[:, ::sh, ::sw, :] without ever applying spec.padding, so a padded 1x1
    conv returned (1, 8, 8, 8) where the oracle returns (1, 10, 10, 8) —
    silently wrong shape *and* values."""
    spec = ConvSpec(4, 8, kernel_size=(1, 1), padding=(1, 1))
    x = _rand((1, 8, 8, 4), seed=1)
    w = _rand((1, 1, 4, 8), seed=2)
    ref = conv2d_reference(x, w, spec)
    assert ref.shape == (1, 10, 10, 8)
    got = conv2d(x, w, spec, impl="pallas", interpret=True)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Pre-transformed weights are an explicit flag, never a shape sniff.  The
# old detection (``pretransformed = (w.shape[0] != spec.kh)``) was ambiguous
# for kh == 8 kernels: raw 8x8 weights are (8, 8, C, O) exactly like an
# offline-transformed 3x3's, so any 8x8-aware path was one refactor away
# from misrouting them through the Winograd inverse transform.


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_conv_8x8_kernel_raw_weights_regression(impl):
    """An 8x8-kernel conv — whose raw weights share the (8, 8, C, O) shape
    of pre-transformed Winograd weights — must route as a plain conv."""
    spec = ConvSpec(4, 8, kernel_size=(8, 8), padding=(4, 4))
    x = _rand((1, 16, 16, 4), seed=7)
    w = _rand((8, 8, 4, 8), seed=8)
    ref = conv2d_reference(x, w, spec)
    got = conv2d(x, w, spec, impl=impl, interpret=True)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_conv2d_explicit_pretransformed_flag(impl):
    """conv2d(pretransformed=True) routes offline-transformed (8, 8, C, O)
    weights without any shape inference."""
    from repro.core.winograd import transform_weights

    spec = ConvSpec(4, 6, (3, 3), (1, 1), (1, 1),
                    algorithm=ConvAlgorithm.WINOGRAD)
    x = _rand((1, 12, 12, 4), seed=9)
    wt = _rand((3, 3, 4, 6), seed=10)
    u = transform_weights(wt)
    ref = conv2d_reference(x, wt, spec)
    got = conv2d(x, u, spec, impl=impl, interpret=True, pretransformed=True)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_network_with_8x8_conv_pretransform_flags():
    """End-to-end flag carriage: a network mixing an 8x8 conv with
    Winograd-eligible 3x3 convs, prepared with the offline weight transform
    (``pretransform=True``), must flow the explicit per-layer flags from
    ``prepare_net_params`` to execution — the 3x3 layers' (8, 8, C, O)
    weights route pre-transformed, the 8x8 layer's identically-shaped raw
    weights do not."""
    from repro.core.netplan import (
        NetworkExecutor,
        plan_network,
        pretransform_flags,
    )
    from repro.core.planner import Planner
    from repro.models.cnn import CNNLayer, cnn_forward, init_cnn

    layers = (
        CNNLayer("conv", out_channels=8, kernel=8, activation="relu"),
        CNNLayer("conv", out_channels=6, kernel=3, activation="leaky"),
        CNNLayer("conv", out_channels=5, kernel=3, activation="linear"),
    )
    params = init_cnn(jax.random.PRNGKey(0), layers)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
    ref = cnn_forward(params, layers, x, impl="xla")
    planner = Planner(impl="jax", cache_path=None)
    netplan = plan_network(layers, 16, 16, planner, batch=1)
    flags = pretransform_flags(netplan, True)
    assert flags[0] is False, "raw 8x8 kernel misread as pre-transformed"
    assert any(flags), "test setup: no Winograd layer left to pre-transform"
    got = NetworkExecutor(netplan, params, pretransform=True)(x)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)
    # And through the facade, which carries the same flags.
    import repro

    compiled = repro.compile(
        layers, params, repro.ExecutionOptions(impl="jax", cache_path=None),
        input_hw=(16, 16),
    )
    np.testing.assert_allclose(compiled.run(x), ref, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# Network-level acceptance: fused epilogue vs reference for every conv layer
# of the paper's two networks.


def _network_layer_specs(layers, h, w, in_ch=3):
    """(spec, h, w) for every conv layer at its actual input resolution."""
    from repro.models.cnn import _conv_spec

    out = []
    ch = []
    cur_ch, cur_h, cur_w = in_ch, h, w
    for l in layers:
        if l.kind == "conv":
            spec = _conv_spec(l, cur_ch)
            out.append((spec, cur_h, cur_w, l.activation))
            cur_h, cur_w = spec.out_hw(cur_h, cur_w)
            cur_ch = l.out_channels
        elif l.kind == "maxpool":
            cur_h, cur_w = -(-cur_h // l.stride), -(-cur_w // l.stride)
        elif l.kind == "upsample":
            cur_h, cur_w = cur_h * l.size, cur_w * l.size
        elif l.kind == "route":
            cur_ch = sum(ch[j][0] for j in l.from_layers)
            cur_h, cur_w = ch[l.from_layers[0]][1], ch[l.from_layers[0]][2]
        elif l.kind == "fc":
            cur_ch = l.out_channels
        ch.append((cur_ch, cur_h, cur_w))
    return out


@pytest.mark.parametrize("model", ["vgg16", "yolov3-tiny"])
def test_fused_epilogue_every_conv_layer(model):
    """Acceptance: fused conv+bias+activation matches conv2d_reference +
    unfused epilogue within 1e-4 for every conv layer shape of VGG-16 and
    YOLOv3-tiny (channel counts as published; spatial dims scaled down so
    the suite stays fast — the epilogue math is resolution-independent)."""
    from repro.configs import vgg16, yolov3

    layers = vgg16.LAYERS if model == "vgg16" else yolov3.TINY_LAYERS
    seen = set()
    for i, (spec, h, w, act) in enumerate(
        _network_layer_specs(layers, 32, 32)
    ):
        key = (spec.in_channels, spec.out_channels, spec.kernel_size,
               spec.stride, h, w)
        if key in seen or h < spec.kh or w < spec.kw:
            continue
        seen.add(key)
        x = _rand((1, h, w, spec.in_channels), seed=i)
        wt = _rand(
            (spec.kh, spec.kw, spec.in_channels, spec.out_channels), seed=i + 1
        ) * (1.0 / (spec.kh * spec.in_channels ** 0.5))
        bias = _rand((spec.out_channels,), seed=i + 2)
        epi = Epilogue(bias=bias, activation=act)
        ref = apply_epilogue(conv2d_reference(x, wt, spec), epi)
        got = conv2d(x, wt, spec, epilogue=epi)
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.parametrize("model", ["vgg16", "yolov3-tiny"])
def test_cnn_infer_matches_unfused_forward(model):
    """Whole-network acceptance: the jitted fused entry point (batchnorm
    folded, epilogues in-kernel) matches the unfused XLA-conv forward."""
    from repro.configs import vgg16, yolov3
    from repro.models.cnn import _cnn_infer, cnn_forward, init_cnn

    layers = vgg16.LAYERS if model == "vgg16" else yolov3.TINY_LAYERS
    params = init_cnn(jax.random.PRNGKey(0), layers)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3))
    ref = cnn_forward(params, layers, x, impl="xla")
    got = _cnn_infer(params, layers, x)
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4 * scale)


# ---------------------------------------------------------------------------
# Int8 quantized inference vs the fp32 oracle.  The conformance metric is
# SQNR (signal-to-quantization-noise, dB) rather than allclose: quantization
# error is by construction larger than fp32 rounding, and the acceptance
# criterion from the int8 PR is >= 30 dB against the fp32 reference.


INT8_SQNR_DB = 30.0


def _quantize_case(x, w, bias, activation):
    """Offline quantization exactly as prepare_net_params performs it:
    per-input-channel activation scales folded into the weights, then
    per-output-channel weight scales; returns (xq, wq, epilogue)."""
    from repro.core.quant import (
        activation_scales,
        quantize_activation,
        quantize_conv_weights,
    )

    sx = activation_scales(x, axis=(0, 1, 2))
    xq = quantize_activation(x, sx)
    wq, ws = quantize_conv_weights(w, sx)
    return xq, wq, Epilogue(bias=bias, activation=activation, scale=ws)


INT8_ALGOS = [ConvAlgorithm.DIRECT, ConvAlgorithm.IM2COL_GEMM]


@pytest.mark.parametrize("algo", INT8_ALGOS, ids=lambda a: a.value)
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("pad", [0, 1])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "epilogue"])
def test_int8_conv_conformance(algo, stride, pad, k, fused):
    """The int8 dtype axis of the conformance cross-product: every eligible
    (algorithm, stride, padding, kernel, epilogue) cell runs the quantized
    Pallas kernel (int8 operands, int32 accumulation, fused dequant) and
    must reach >= 30 dB SQNR against conv2d_reference on the same fp32
    inputs.  Winograd is deliberately absent: int8 never routes there
    (core/quant.py::winograd_int8_budget_ok)."""
    from repro.core.quant import sqnr_db
    from repro.kernels.conv_ops import conv2d_pallas

    if not _eligible(algo, k, stride):
        pytest.skip(f"{algo.value} ineligible for k={k} s={stride}")
    spec = ConvSpec(8, 16, (k, k), (stride, stride), (pad, pad),
                    algorithm=algo)
    x = _rand((2, 10, 12, 8), seed=k * 100 + stride * 10 + pad)
    w = _rand((k, k, 8, 16), seed=7) * 0.2
    bias = _rand((16,), seed=9) * 0.1 if fused else None
    activation = "leaky" if fused else "linear"
    ref = apply_epilogue(
        conv2d_reference(x, w, spec),
        Epilogue(bias=bias, activation=activation),
    )
    xq, wq, epi = _quantize_case(x, w, bias, activation)
    got = conv2d_pallas(xq, wq, spec, algo, interpret=True, epilogue=epi)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert got.dtype == jnp.float32
    q = float(sqnr_db(ref, got))
    assert q >= INT8_SQNR_DB, f"SQNR {q:.1f} dB < {INT8_SQNR_DB} dB"


def test_int8_pure_jnp_matches_pallas_kernel():
    """The pure-jnp int8 path (fp32 integer math + apply_epilogue dequant)
    and the Pallas int8 kernel are the same integer computation — they must
    agree to fp32 rounding, far tighter than either agrees with the
    oracle."""
    from repro.core.im2col import conv2d_im2col
    from repro.kernels.conv_ops import conv2d_pallas

    spec = ConvSpec(8, 16, (3, 3), (1, 1), (1, 1))
    x = _rand((2, 10, 10, 8), seed=21)
    w = _rand((3, 3, 8, 16), seed=22) * 0.2
    bias = _rand((16,), seed=23) * 0.1
    xq, wq, epi = _quantize_case(x, w, bias, "relu")
    a = conv2d_pallas(xq, wq, spec, ConvAlgorithm.IM2COL_GEMM,
                      interpret=True, epilogue=epi)
    b = conv2d_im2col(
        xq.astype(jnp.float32), wq.astype(jnp.float32), spec, epilogue=epi
    )
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_int8_never_routes_to_winograd():
    """The dispatcher refuses int8 Winograd outright — the F(6, 3) transform
    amplification blows the error budget, so reaching that path is a planner
    bug, not a numerics question."""
    from repro.kernels.conv_ops import conv2d_pallas

    spec = ConvSpec(8, 16, (3, 3), (1, 1), (1, 1))
    x = _rand((1, 12, 12, 8), seed=31)
    w = _rand((3, 3, 8, 16), seed=32)
    xq, wq, epi = _quantize_case(x, w, None, "linear")
    with pytest.raises(AssertionError, match="Winograd"):
        conv2d_pallas(xq, wq, spec, ConvAlgorithm.WINOGRAD,
                      interpret=True, epilogue=epi)


def test_int8_im2col_traffic_at_most_half_of_fp32():
    """Acceptance: the modeled int8 im2col+GEMM HBM traffic is <= 0.5x fp32
    (int8 operands, fp32 output writes included) for every layer the
    planner's traffic gate admits — which is every k>=3 conv past the cin=3
    entry in both networks.  The gate and the ratio must also agree layer
    by layer: the layers it rejects (the cin=3 entry; YOLO's 1x1 detection
    head, whose fp32 output writes dominate) genuinely exceed 0.5x."""
    from repro.core.quant import (
        INT8_TRAFFIC_THRESHOLD,
        int8_traffic_ratio,
        int8_worthwhile,
    )
    from repro.configs import vgg16, yolov3

    checked = rejected = 0
    for layers in (vgg16.LAYERS, yolov3.TINY_LAYERS):
        for spec, h, w, _act in _network_layer_specs(layers, 416, 416):
            ratio = int8_traffic_ratio(spec, h, w)
            assert int8_worthwhile(spec, h, w) == (
                ratio <= INT8_TRAFFIC_THRESHOLD
            ), (spec, ratio)
            if spec.kh >= 3 and spec.in_channels >= 16:
                assert ratio <= INT8_TRAFFIC_THRESHOLD, (spec, ratio)
                checked += 1
            elif not int8_worthwhile(spec, h, w):
                rejected += 1
    assert checked >= 15
    assert rejected >= 1  # the gate actually rejects something real


@pytest.mark.parametrize("model", ["vgg16", "yolov3-tiny"])
@pytest.mark.parametrize("batch", [1, 4, 8])
def test_int8_network_acceptance(model, batch, tmp_path):
    """Whole-network acceptance: ``repro.compile(..., dtype='int8')`` runs
    VGG-16 and YOLOv3-tiny end-to-end (32x32 input so the suite stays fast;
    channel structure as published) and the network output reaches >= 30 dB
    SQNR against the fp32 compilation of the same params at batches 1/4/8.
    Also pins the planner policy: the cin=3 entry conv stays fp32 (the
    traffic gate fails), deeper convs quantize, and a warm v5 cache
    re-tunes nothing."""
    import repro
    from repro.api import ExecutionOptions
    from repro.configs import vgg16, yolov3
    from repro.core.quant import sqnr_db
    from repro.models.cnn import init_cnn

    m = (vgg16.MODEL if model == "vgg16" else yolov3.TINY_MODEL)
    m = m.with_input_hw((32, 32))
    params = init_cnn(jax.random.PRNGKey(0), m.layers, m.in_channels)
    x = jnp.asarray(
        np.random.default_rng(batch).normal(size=(batch, 32, 32, 3)),
        jnp.float32,
    )
    cache = str(tmp_path / "plans.json")
    fp32 = repro.compile(
        m, params, ExecutionOptions(impl="jax", cache_path=cache, batch=batch)
    )
    ref = fp32.run(x)
    opts = ExecutionOptions(
        impl="jax", cache_path=cache, dtype="int8", batch=batch
    )
    q = repro.compile(m, params, opts, calibration=x)
    out = q.run(x)
    assert out.shape == ref.shape
    quality = float(sqnr_db(ref, out))
    assert quality >= INT8_SQNR_DB, (
        f"{model} batch={batch}: whole-network SQNR {quality:.1f} dB"
    )
    rows = q.plan_report()["layers"]
    dtypes = [r["dtype"] for r in rows]
    assert dtypes[0] == "float32", "cin=3 entry conv must stay fp32"
    assert dtypes.count("int8") >= len(dtypes) - 2, dtypes
    # Warm path: a fresh compilation against the same v5 cache re-tunes
    # zero layers — the per-layer dtype rides the plan entries.
    warm = repro.compile(m, params, opts, calibration=x)
    rep = warm.plan_report()
    assert rep["tunes"] == 0 and rep["network_hits"] >= 1, rep


def test_int8_network_pallas_interpret_smoke():
    """The Pallas int8 kernels end-to-end (interpret mode): a small conv
    stack through the facade with dtype='int8' and impl='pallas' must match
    its own fp32 compilation to >= 30 dB."""
    import repro
    from repro.api import ExecutionOptions
    from repro.core.quant import sqnr_db
    from repro.models.cnn import CNNLayer, init_cnn

    layers = (
        CNNLayer("conv", out_channels=32, kernel=3, activation="leaky"),
        CNNLayer("maxpool", size=2, stride=2),
        CNNLayer("conv", out_channels=48, kernel=3, activation="relu"),
        CNNLayer("conv", out_channels=32, kernel=1, activation="linear"),
    )
    params = init_cnn(jax.random.PRNGKey(2), layers, in_channels=16)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 16))
    fp32 = repro.compile(
        layers, params,
        repro.ExecutionOptions(impl="pallas", interpret=True,
                               cache_path=None),
        input_hw=(16, 16), in_channels=16,
    )
    ref = fp32.run(x)
    q = repro.compile(
        layers, params,
        repro.ExecutionOptions(impl="pallas", interpret=True,
                               cache_path=None, dtype="int8"),
        input_hw=(16, 16), in_channels=16, calibration=x,
    )
    out = q.run(x)
    quality = float(sqnr_db(ref, out))
    assert quality >= INT8_SQNR_DB, f"SQNR {quality:.1f} dB"
    assert any(r["dtype"] == "int8" for r in q.plan_report()["layers"])


def test_fold_batchnorm_matches_batchnorm_inference():
    """Folded weights+bias reproduce conv -> bn exactly (up to fp32)."""
    from repro.models.cnn import (
        CNNLayer,
        batchnorm_inference,
        fold_batchnorm,
        init_cnn,
    )

    layers = (CNNLayer("conv", out_channels=8, kernel=3, batch_norm=True),)
    params = init_cnn(jax.random.PRNGKey(3), layers)
    # Non-trivial bn statistics.
    bn = {
        "gamma": _rand((8,), 4) + 2.0,
        "beta": _rand((8,), 5),
        "mean": _rand((8,), 6),
        "var": jnp.abs(_rand((8,), 7)) + 0.5,
    }
    params[0]["bn"] = bn
    folded = fold_batchnorm(params, layers)
    assert "bn" not in folded[0] and "b" in folded[0]
    spec = ConvSpec(3, 8, (3, 3), (1, 1), (1, 1))
    x = _rand((1, 12, 12, 3), 8)
    ref = batchnorm_inference(conv2d_reference(x, params[0]["w"], spec), bn)
    got = conv2d_reference(x, folded[0]["w"], spec) + folded[0]["b"]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
