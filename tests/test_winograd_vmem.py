"""Winograd tiled in VMEM: the fused kernel on NHWC row windows.

  - interpret mode: the NHWC kernel (tiles cut with loads strided by 6,
    written back with stores strided by 6) against the lax reference and, far
    tighter, against itself at another block decomposition (one tile row
    per block, or every tile in one block) — on the awkward shapes, and on
    the shape of each other Winograd test;
  - the wrapper runs the fused kernel with no tile gather around it
    (traced, not run);
  - the layer table says how many tiles the Winograd layers of the
    benchmark networks compute over the real ones, also with the batch
    split over four devices.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs import resnet50, vgg16, yolov3
from repro.core.conv_spec import ConvSpec, Epilogue, apply_epilogue
from repro.core.conv2d import conv2d_reference
from repro.core.netplan import layer_table, plan_network
from repro.core.winograd import transform_weights
from repro.kernels.winograd.ops import (
    conv2d_winograd_padded_call,
    winograd_tiling,
    winograd_vmem_tiled_call,
)
from repro.util import ceil_to, pad_bias_row


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _case(b, h, w, c, o, nhwc, bc=128, bo=128, bias=False,
          activation="linear"):
    return dict(b=b, h=h, w=w, c=c, o=o, nhwc=nhwc, bc=bc, bo=bo, bias=bias,
                activation=activation)


CASES = {
    # oh = 16 (not a multiple of 6), nTW = 7 tile columns (padded to 8),
    # nTH = 3 tile rows in blocks of 2, a 3-channel stem padded to one
    # 128-lane block, bias + leaky.
    "edges_stem_leaky": _case(1, 16, 40, 3, 32, nhwc=(1, 2, 8), bias=True,
                              activation="leaky"),
    # Two whole images per block, bias + relu.
    "multi_image_relu": _case(4, 6, 6, 8, 8, nhwc=(2, 1, 8), bias=True,
                              activation="relu"),
    # Two Cin blocks (the reduction axis), two Cout blocks, two tile-column
    # blocks per row.
    "multi_block": _case(1, 12, 90, 200, 130, nhwc=(1, 1, 8)),
    # The shape of each other Winograd test, one tile row per block.
    "end_to_end": _case(2, 12, 14, 5, 7, nhwc=(1, 1, 8)),
    "pretransformed_weights": _case(1, 12, 12, 4, 6, nhwc=(1, 1, 8)),
    "crop_path": _case(2, 11, 23, 4, 8, nhwc=(1, 1, 8)),
    "block_padding_path": _case(2, 12, 12, 5, 7, nhwc=(1, 1, 8), bc=8, bo=8),
    "pretransformed_weights_13x17": _case(1, 13, 17, 4, 6, nhwc=(1, 1, 8)),
    "fused_epilogue": _case(2, 10, 13, 5, 9, nhwc=(1, 1, 8), bias=True,
                            activation="leaky"),
}


@pytest.mark.parametrize("name", list(CASES))
def test_winograd_vmem_tiling(name):
    cs = CASES[name]
    b, h, w, c, o = cs["b"], cs["h"], cs["w"], cs["c"], cs["o"]
    bc, bo = cs["bc"], cs["bo"]
    spec = ConvSpec(c, o, (3, 3), (1, 1), (1, 1))
    x = _rand((b, h, w, c), 71)
    wt = _rand((3, 3, c, o), 72) / np.sqrt(9 * c)   # outputs of order 1
    bias = _rand((o,), 73) if cs["bias"] else None
    cp, op = ceil_to(c, bc), ceil_to(o, bo)
    x_sp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, cp - c)))
    u = jnp.pad(transform_weights(wt), ((0, 0), (0, 0), (0, cp - c),
                                        (0, op - o)))
    bias_p = pad_bias_row(bias, op)
    nhwc = cs["nhwc"]
    # Each tile's arithmetic does not depend on the block it falls in.
    other = ((1, 1, 8) if nhwc != (1, 1, 8)
             else (b, -(-h // 6), ceil_to(-(-w // 6), 8)))
    got, alt = (
        winograd_vmem_tiled_call(
            x_sp, u, h, w, blocks, (bc, bo), interpret=True, bias_p=bias_p,
            activation=cs["activation"],
        )
        for blocks in (nhwc, other)
    )
    assert got.shape == alt.shape == (b, h, w, op)
    np.testing.assert_allclose(got, alt, rtol=1e-5, atol=1e-5)
    ref = apply_epilogue(conv2d_reference(x, wt, spec),
                         Epilogue(bias=bias, activation=cs["activation"]))
    np.testing.assert_allclose(got[..., :o], ref, rtol=5e-4, atol=5e-4)
    np.testing.assert_array_equal(got[..., o:], 0.0)


def _pallas_names(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [
        str(e.params.get("name") or e.params["jaxpr"].debug_info.func_name)
        for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"
    ], {e.primitive.name for e in jaxpr.jaxpr.eqns}


@pytest.mark.parametrize("b,hw,c,o,nhwc", [
    # VGG-16's first layers: 38 -> 40 tile columns, one row per block.
    (1, 224, 128, 128, (1, 1, 40)),
    # 5 -> 8 tile columns on 512-wide channels: one whole image per block
    # (two overrun the 16 MiB VMEM).
    (8, 28, 512, 512, (1, 5, 8)),
])
def test_padded_call_dispatches_by_shape(b, hw, c, o, nhwc):
    """The fused kernel cuts its tiles in VMEM at the blocks
    ``winograd_tiling`` names, with no gather or transpose around it."""
    blocks = (32, 128, 128)
    assert winograd_tiling(b, hw, hw, blocks).nhwc == nhwc
    x = jax.ShapeDtypeStruct((b, hw + 2, hw + 2, c), jnp.float32)
    u = jax.ShapeDtypeStruct((8, 8, c, o), jnp.float32)
    bias = jax.ShapeDtypeStruct((1, o), jnp.float32)
    names, prims = _pallas_names(
        lambda x, u, bias: conv2d_winograd_padded_call(
            x, u, hw, hw, blocks, interpret=True, bias_p=bias,
            activation="relu"),
        x, u, bias,
    )
    assert names == ["_fused_winograd_nhwc_bias_kernel"]
    assert "gather" not in prims and "transpose" not in prims


def _table(model, batch, shards=1):
    opts = repro.ExecutionOptions(impl="pallas", interpret=False, batch=batch,
                                  dtype="float32", cache_path=None)
    netplan = plan_network(model.layers, *model.input_hw, opts.make_planner(),
                           in_channels=model.in_channels, batch=batch,
                           dtype="float32")
    return netplan, layer_table(netplan, shards)["layers"]


@pytest.mark.parametrize("model,batch,wino", [
    # VGG-16@224 b8: 224² to 28², 3 of 4 tile columns real at 112², 5 of 8
    # at 56² and 28².  Layer 0 (3 channels) folds its taps into one GEMM.
    (vgg16.MODEL, 8, (1, 3, 4, 6, 7, 8, 10, 11, 12)),
    # YOLOv3-20@608 b1; layer 0 folds its taps too.
    (yolov3.MODEL_20, 1, (3, 7, 10, 14, 17)),
    # ResNet-50@224 b64: every 3x3 stride-1 layer, from 56² to 7²; the
    # 3x3/2s that open stages 2 to 4 (17, 35, 61) run im2col.
    pytest.param(resnet50.MODEL, 64, (3, 9, 13, 23, 27, 31, 41, 45, 49, 53,
                                      57, 67, 71), id="resnet50"),
])
def test_layer_table_records_tiling(model, batch, wino):
    netplan, layers = _table(model, batch)
    tiled = {l["index"]: l for l in layers if l["algorithm"] == "winograd"}
    assert sorted(tiled) == list(wino)
    for l in layers:
        assert "tiling" not in l
        if l["algorithm"] != "winograd":
            assert l["tile_ratio"] is None
    for i, l in tiled.items():
        assert l["tile_ratio"] >= 1.0, (i, l)
        if netplan.steps[i].out_hw[0] in (224, 608):
            assert l["tile_ratio"] <= 1.10, (i, l)


def test_sharded_executor_tiles_at_per_device_batch():
    """Batch 4 over four CPU devices: each kernel sees one image.  The
    executor's layer table and the analysis descriptors describe the
    Winograd kernels at that batch, and the sharded forward's kernels have
    exactly the descriptors' grids: the fused kernel's blocks at 32² hold
    every tile of as many images as fit (6 x 8 tiles each)."""
    from conftest import run_with_devices

    out = run_with_devices(4, """
        import json
        import jax, jax.numpy as jnp
        from repro import spans
        from repro.analysis.descriptors import step_descriptors
        from repro.analysis.trace import trace_forward
        from repro.configs import vgg16
        from repro.core.netplan import (NetworkExecutor, layer_table,
                                        plan_network)
        from repro.core.planner import Planner
        from repro.models.cnn import init_cnn

        layers = vgg16.LAYERS
        params = init_cnn(jax.random.PRNGKey(0), layers)
        x = jnp.zeros((4, 32, 32, 3), jnp.float32)
        netplan = plan_network(layers, 32, 32,
                               Planner(impl="pallas", cache_path=None),
                               batch=4)
        ex = NetworkExecutor(netplan, params, interpret=True)
        assert ex.mesh is not None and len(jax.devices()) == 4
        table = spans.RECORD.layer_tables()[-1]["layers"]
        _, recs = trace_forward(ex._fn, ex.params, x)
        want = [tuple(d["grid"]) for s in netplan.steps
                for d in step_descriptors(netplan, s, batch=1)]
        whole = [tuple(d["grid"]) for s in netplan.steps
                 for d in step_descriptors(netplan, s)]
        print(json.dumps({
            "table": table == layer_table(netplan, 4)["layers"],
            "grids": [r.grid for r in recs] == want,
            "grids_differ": want != whole,
            "ratios": [l["tile_ratio"] for l in table
                       if l["algorithm"] == "winograd"],
        }))
    """)
    got = json.loads(out.strip().splitlines()[-1])
    assert got["table"] and got["grids"] and got["grids_differ"], got
    assert got["ratios"] and all(r >= 1.0 for r in got["ratios"]), got
