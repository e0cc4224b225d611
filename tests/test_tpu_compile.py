"""Ahead-of-time compiles of the main path's kernels for a described v5e.

No chip is needed: the TPU compiler is installed and compiles for a
``v5e:2x2`` topology that is described, not attached.  This catches what
interpret mode cannot — block shapes Mosaic refuses (unaligned lane dims,
matmuls with two batch dims) and kernels that overrun scoped VMEM.

Every compile sets ``vmem_limit_bytes`` to the footprint ``core/vmem_model``
predicts for the kernel's blocks, so a pass also proves the model covers
what the compiler allocates.  Two sets of kernels are compiled: fixed cases,
each a layer the compiler once refused or a block shape the Winograd
kernel tiled in VMEM takes (YOLOv3-20's stem, several images per block,
ResNet-50's 7x7 layers at batch 64), among them four of ResNet-50's 1x1
convs at batch 64 and its 7x7/2 stem with the taps folded into one GEMM,
and every distinct kernel call the planner picks for the benchmark's
networks (VGG-16@224 batch 8, YOLOv3-tiny@416 and YOLOv3-20@608 batch 1,
ResNet-50@224 batch 64; fp32 and int8).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.
"""
import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

import repro
from repro.api.model import CNNModel
from repro.configs import resnet50, vgg16, yolov3
from repro.core.conv_spec import ConvAlgorithm, ConvSpec
from repro.core.netplan import (
    plan_network,
    prepare_net_params,
    pretransform_flags,
    run_network,
    winograd_step_tiling,
)
from repro.core.vmem_model import (
    gemm_kernel_vmem_bytes,
    im2col_kernel_vmem_bytes,
    itemsize,
    winograd_kernel_vmem_bytes,
)
from repro.hw import V5E
from repro.models.cnn import CNNLayer
from repro.util import ceil_to


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # Otherwise loading the TPU library writes its logs to a fixed directory
    # outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the persistent
    cache; keep it off so nothing warns."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


# ---------------------------------------------------------------------------
# Cases: name -> (fn, operand shapes, modeled VMEM footprint) at the blocks
# the planner picks for that layer.


def _im2col_case(b, hw, cin, cout, stride, dtype, cin_phys=None):
    """The im2col kernel on a channel-padded (B, H, W, Cp) activation with
    the blocks ``pick_blocks`` chooses under the default budget."""
    from repro.kernels.im2col_gemm.ops import conv2d_im2col_padded_call, pick_blocks

    spec = ConvSpec(cin, cout, stride=(stride, stride))
    oh, ow = spec.out_hw(hw, hw)
    nbytes = jnp.dtype(dtype).itemsize
    cp = cin_phys or ceil_to(cin, V5E.lane_width)
    toh, bc, bo = blocks = pick_blocks(
        oh, ow, cp, cout, 3, 3, stride, stride, nbytes
    )
    op = ceil_to(cout, bo)
    q8 = dtype == jnp.int8
    model = im2col_kernel_vmem_bytes(
        min(toh, oh), ow, bc, bo, 3, 3, stride, stride, nbytes
    )

    def fn(x, w, row):
        return conv2d_im2col_padded_call(
            x, w, spec, oh, ow, blocks, bias_p=row, activation="relu",
            scale_p=row if q8 else None,
        )

    args = [((b, hw, hw, cp), dtype), ((3, 3, cp, op), dtype),
            ((1, op), jnp.float32)]
    return fn, args, model


def _winograd_case(b, hw, cin, cout, channel_blocks=None):
    """The fused kernel as ``conv2d_winograd_padded_call`` runs it: tiles
    cut in VMEM from a conv-padded, channel-padded (B, H+2, W+2, Cp)
    activation, at the (bb, k, ntw) blocks ``winograd_tiling`` derives for
    the channel blocks (``pick_blocks``' unless given); modeled as a tile
    block of bt = bb*k*ntw tiles."""
    from repro.kernels.winograd.ops import (
        conv2d_winograd_padded_call,
        pick_blocks,
        winograd_tiling,
    )

    t = b * (-(-hw // 6)) ** 2            # 6x6 output tiles (same padding)
    bt, bc, bo = pick_blocks(t, cin, cout)
    bc, bo = channel_blocks or (bc, bo)
    cp, op = ceil_to(cin, bc), ceil_to(cout, bo)
    nhwc = winograd_tiling(b, hw, hw, (bt, bc, bo)).nhwc

    def fn(x_sp, u, bias):
        return conv2d_winograd_padded_call(x_sp, u, hw, hw, (bt, bc, bo),
                                           bias_p=bias, activation="relu")

    args = [((b, hw + 2, hw + 2, cp), jnp.float32),
            ((8, 8, cp, op), jnp.float32), ((1, op), jnp.float32)]
    return fn, args, winograd_kernel_vmem_bytes(math.prod(nhwc), bc, bo)


def _gemm_case(m, k, n, dtype):
    from repro.kernels.gemm.ops import default_block, matmul_padded_call

    nbytes = jnp.dtype(dtype).itemsize
    cfg = default_block(m, n, k, nbytes)
    block = (cfg.bm, cfg.bn, cfg.bk)
    mp, kp, np_ = ceil_to(m, cfg.bm), ceil_to(k, cfg.bk), ceil_to(n, cfg.bn)
    q8 = dtype == jnp.int8

    def fn(a, w, row):
        return matmul_padded_call(a, w, block, bias_p=row, activation="relu",
                                  scale_p=row if q8 else None)

    args = [((mp, kp), dtype), ((kp, np_), dtype), ((1, np_), jnp.float32)]
    model = gemm_kernel_vmem_bytes(
        cfg.bm, cfg.bn, cfg.bk, nbytes, epilogue_rows=2 if q8 else 1
    )
    return fn, args, model


def _direct_1x1_case(b, hw, cin, cout, block, dtype):
    """A 1x1 conv planned alone on a (B, hw, hw, cin) map, run by the direct
    GEMM kernel at ``block`` (bm, bn, bk) as the network executor runs it."""
    return _single_conv_case(b, hw, cin, cout, 1, 1, block, dtype)


def _single_conv_case(b, hw, cin, cout, kernel, stride, block, dtype,
                      folded=False):
    """One conv planned alone on a (B, hw, hw, cin) map, run as the network
    executor runs it at kernel blocks ``block``; ``folded`` says whether
    the planner folds its taps into one GEMM."""
    model = CNNModel((CNNLayer("conv", out_channels=cout, kernel=kernel,
                               stride=stride, activation="relu"),),
                     (hw, hw), cin)
    ((key, call),) = _planned_calls(model, b, dtype,
                                    blocks={0: block}).items()
    assert key[1] is folded, key
    _, fn, args, modeled = call
    return fn, args, modeled


CASES = {
    # VGG-16's first 3x3 layer plans onto the fused megakernel, which cuts
    # its tiles in VMEM, 40 tile columns (a 248-column window) at a time.
    "winograd_fused_224x64-64": lambda: _winograd_case(8, 224, 64, 64),
    # ResNet-50's 7x7 3x3 layers (67 and 71), 512 -> 512 at batch 64, the
    # cell's top two device ops: two whole images per block, 2 x 8 tiles
    # each (2 of 8 tile columns real), over 4 Cin and 4 Cout blocks.
    "winograd_nhwc_resnet50_l067_7x512-512": lambda: _winograd_case(
        64, 7, 512, 512, (128, 128)),
    # Tiled in VMEM: YOLOv3-20's stem as the Winograd kernel ran it before
    # the stem folded its taps, a 610 x 610 x 128 padded window (3 channels
    # padded to 128) cut 6 tile rows x 8 tile columns at a time.
    "winograd_nhwc_yolov3_20_l0_608x3-32": lambda: _winograd_case(
        1, 608, 3, 32, (128, 128)),
    # Tiled in VMEM, two whole 18x18 images per block (3 x 8 tiles each),
    # over 4 Cin and 4 Cout blocks.  Two 28x28 images (5 x 8 tiles each)
    # overrun the 16 MiB budget.
    "winograd_nhwc_b8_18x512-512": lambda: _winograd_case(
        8, 18, 512, 512, (128, 128)),
    # im2col at VGG-16 widths, batch 8, fp32 and int8.
    **{
        f"im2col_{jnp.dtype(dt).name}_{hw}x{ci}-{co}": functools.partial(
            _im2col_case, 8, hw, ci, co, 1, dt
        )
        for dt in (jnp.float32, jnp.int8)
        for hw, ci, co in ((224, 3, 64), (112, 128, 128), (56, 256, 256))
    },
    # YOLOv3-20 layer 1 on the tap-wise kernel, as it ran before it folded
    # its taps: 3x3/s2 at 608, 32 -> 64, over the activation layer 0 left
    # channel-padded to 128.
    "im2col_yolov3_20_l1_608s2_32-64": functools.partial(
        _im2col_case, 1, 608, 32, 64, 2, jnp.float32, cin_phys=128
    ),
    # VGG-16's classifier GEMMs.
    "gemm_float32_8x25088-4096": lambda: _gemm_case(8, 25088, 4096, jnp.float32),
    "gemm_int8_8x4096-1000": lambda: _gemm_case(8, 4096, 1000, jnp.int8),
    # ResNet-50's 1x1 convs at batch 64 on the direct GEMM kernel, at the
    # blocks the planner picks in the network: the four the compiler refused
    # while the model counted no dot operand or result values (layers 4, 34,
    # 40 and 66; layer 66 then took bk = 1024, which the model now prices
    # over the budget).
    **{
        f"direct_resnet50_l{i:03d}_{hw}x{ci}-{co}": functools.partial(
            _direct_1x1_case, 64, hw, ci, co, block, "float32"
        )
        for i, hw, ci, co, block in (
            (4, 56, 64, 256, (512, 256, 128)),
            (34, 28, 512, 256, (512, 256, 512)),
            (40, 14, 1024, 256, (512, 256, 1024)),
            (66, 7, 2048, 512, (512, 512, 512)),
        )
    },
    # ResNet-50's 7x7/2 stem at batch 64 with its taps folded: the GEMM
    # kernel over the (802816, 147) patch matrix, K in one block.
    "folded_resnet50_l000_224s2_7x7x3-64": functools.partial(
        _single_conv_case, 64, 224, 3, 64, 7, 2, (512, 128, 147), "float32",
        folded=True,
    ),
}


#: The least ``vmem_limit_bytes`` at which each case compiled for v5e
#: (bisected to 64 KiB with ``compile_with_limit``; jax 0.9.0, libtpu
#: 0.0.34).  Each layer here but the Winograd ones was refused before the
#: VMEM model counted tile padding and Mosaic's internal scratch (so was
#: VGG-16's first layer on the fused kernel, when it still read tiles XLA
#: had gathered in HBM).  ResNet-50's direct 1x1 kernels needed 0.23, 1.98,
#: 0.48 and 1.97 MiB (layer 66 at bk = 1024: 13893632) more than their
#: buffers and the fixed allowance; of the dot's values, A + B + the fp32
#: result (2.0 MiB at layer 34) is the least sum that covers layer 34.
#: ResNet-50's folded stem is the GEMM kernel at (512, 128, 147).  Its
#: 7x7 Winograd layer needs 1.19 MiB less than the model gives its
#: (2, 2, 8) blocks.
COMPILER_MIN_VMEM = {
    "direct_resnet50_l004_56x64-256": 2883584,
    "direct_resnet50_l034_28x512-256": 7077888,
    "direct_resnet50_l040_14x1024-256": 8650752,
    "direct_resnet50_l066_7x2048-512": 7602176,
    "folded_resnet50_l000_224s2_7x7x3-64": 2228224,
    "gemm_float32_8x25088-4096": 8716288,
    "gemm_int8_8x4096-1000": 4325376,
    "im2col_float32_112x128-128": 6291456,
    "im2col_float32_224x3-64": 8650752,
    "im2col_float32_56x256-256": 16121856,
    "im2col_int8_112x128-128": 3670016,
    "im2col_int8_224x3-64": 7340032,
    "im2col_int8_56x256-256": 6094848,
    "im2col_yolov3_20_l1_608s2_32-64": 10551296,
    "winograd_fused_224x64-64": 5570560,
    "winograd_nhwc_b8_18x512-512": 14417920,
    "winograd_nhwc_resnet50_l067_7x512-512": 12517376,
    "winograd_nhwc_yolov3_20_l0_608x3-32": 6422528,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_vmem_model_covers_compiler_requirement(case):
    """CPU-only: the modeled footprint of the blocks the planner picks is at
    least what the compiler needs, and within the budget it planned for."""
    _, _, model = CASES[case]()
    assert COMPILER_MIN_VMEM[case] <= model <= V5E.vmem_bytes, (
        case, COMPILER_MIN_VMEM[case], model
    )


def compile_with_limit(fn, args, sharding, vmem_limit_bytes):
    """AOT-compile ``fn`` on described-device shapes with
    ``vmem_limit_bytes`` set in every kernel's compiler params.  ``args``
    are (shape, dtype) pairs or pytrees of ``jax.ShapeDtypeStruct``."""
    cls = pltpu.CompilerParams
    shapes = [
        jax.ShapeDtypeStruct(a[0], a[1], sharding=sharding)
        if isinstance(a, tuple) else jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
            a,
        )
        for a in args
    ]
    try:
        pltpu.CompilerParams = functools.partial(
            cls, vmem_limit_bytes=int(vmem_limit_bytes)
        )
        # A fresh jit per call: the kernels read CompilerParams at trace time.
        return jax.jit(lambda *a: fn(*a)).lower(*shapes).compile()
    finally:
        pltpu.CompilerParams = cls


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e_within_modeled_vmem(
    case, one_chip, no_compile_cache
):
    fn, args, model = CASES[case]()
    assert model <= V5E.vmem_bytes, (case, model)
    compiled = compile_with_limit(fn, args, one_chip, vmem_limit_bytes=model)
    assert "tpu_custom_call" in compiled.as_text(), case


# ---------------------------------------------------------------------------
# Every kernel call the planner picks for the benchmark's networks.

CELLS = {
    f"{name}_{dtype}": (model, batch, dtype)
    for name, model, batch in (
        ("vgg16@224_b8", vgg16.MODEL, 8),
        ("yolov3-tiny@416_b1", yolov3.TINY_MODEL, 1),
        ("yolov3-20@608_b1", yolov3.MODEL_20, 1),
        ("resnet50@224_b64", resnet50.MODEL, 64),
    )
    for dtype in ("float32", "int8")
}


def _modeled_vmem(netplan, step) -> int:
    """The footprint ``core/vmem_model`` gives the kernel a planned conv step
    runs, at the step's blocks and dtype (the bias, and int8's scale, rows
    included).  A fused Winograd step, which tiles in VMEM, is modeled as a
    tile block of its bb*k*ntw tiles; a step that folds its taps runs the
    GEMM kernel."""
    plan, spec = step.plan, step.spec
    d = itemsize(plan.dtype)
    if plan.algorithm is ConvAlgorithm.WINOGRAD:
        _, bc, bo = plan.kernel_blocks
        nhwc = winograd_step_tiling(netplan, step).nhwc
        return winograd_kernel_vmem_bytes(math.prod(nhwc), bc, bo,
                                          dtype_bytes=d)
    if plan.algorithm is ConvAlgorithm.DIRECT or plan.taps_folded:
        bm, bn, bk = plan.kernel_blocks
        return gemm_kernel_vmem_bytes(bm, bn, bk, d,
                                      epilogue_rows=2 if d == 1 else 1)
    toh, bc, bo = plan.kernel_blocks
    oh, ow = step.out_hw
    return im2col_kernel_vmem_bytes(min(toh, oh), ow, bc, bo, spec.kh,
                                    spec.kw, *spec.stride, d)


def _planned_calls(model, batch, dtype, blocks=None):
    """{key: (step index, fn, args, modeled bytes)} for each distinct conv
    kernel call of the planned network.  Params and the int8 calibration
    batch are abstract, so nothing runs on the host.  ``blocks`` ({step
    index: kernel blocks}) overrides the planner's blocks of those steps."""
    opts = repro.ExecutionOptions(impl="pallas", interpret=False, batch=batch,
                                  dtype=dtype, cache_path=None)
    netplan = plan_network(model.layers, *model.input_hw, opts.make_planner(),
                           in_channels=model.in_channels, batch=batch,
                           dtype=dtype)
    if blocks:
        netplan = dataclasses.replace(netplan, steps=tuple(
            dataclasses.replace(s, plan=dataclasses.replace(
                s.plan, kernel_blocks=tuple(blocks[s.index])))
            if s.index in blocks else s
            for s in netplan.steps))
    x = jax.ShapeDtypeStruct((batch, *model.input_hw, model.in_channels),
                             jnp.float32)
    params = jax.eval_shape(
        lambda c: prepare_net_params(
            netplan, model.init_params(jax.random.PRNGKey(0)),
            pretransform=True, calibration=c,
        ), x,
    )
    flags = pretransform_flags(netplan, True)
    calls = {}
    for s in netplan.steps:
        if s.plan is None:
            continue
        p = params[s.index]
        xs = jax.ShapeDtypeStruct((batch, *s.in_hw, s.in_layout.phys_c),
                                  jnp.float32)
        key = (s.plan.algorithm.value,
               s.plan.taps_folded, s.plan.dtype,
               s.spec.kernel_size, s.spec.stride, xs.shape,
               tuple(p["w"].shape), tuple(s.plan.kernel_blocks),
               s.out_layout.phys_c)
        if key in calls:
            continue
        fn = functools.partial(run_network, netplan, interpret=False,
                               pretransformed=flags, start=s.index,
                               stop=s.index + 1)
        calls[key] = (s.index, lambda pp, xx, fn=fn: fn([pp], xx), [p, xs],
                      _modeled_vmem(netplan, s))
    return calls


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_planned_kernels_compile_for_v5e_within_modeled_vmem(
    cell, one_chip, no_compile_cache
):
    calls = _planned_calls(*CELLS[cell])
    assert calls, cell
    refused = []
    for key, (index, fn, args, model) in calls.items():
        assert model <= V5E.vmem_bytes, (cell, index, key, model)
        try:
            compiled = compile_with_limit(fn, args, one_chip, model)
        except Exception as e:  # noqa: BLE001 - collected and reported below
            refused.append(f"layer {index} {key} at {model} B: "
                           f"{str(e).splitlines()[0][:300]}")
            continue
        assert "tpu_custom_call" in compiled.as_text(), (cell, index, key)
    assert not refused, f"{cell}: refused within the modeled VMEM:\n" + \
        "\n".join(refused)
