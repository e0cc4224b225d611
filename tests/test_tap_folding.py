"""Tap folding: narrow-input im2col convs as one patch-matrix GEMM.

A conv whose input channels are narrower than a lane block (the RGB stems,
YOLOv3's 32-channel stride-2 layer) can run im2col+GEMM two ways: the fused
kernel multiplies every tap over channels padded to 128 lanes, or the
wrapper builds the (B*OH*OW, kh*kw*C) patch matrix once from the logical
channels and runs one GEMM over K padded once (``ConvPlan.taps_folded``).
These tests hold:

  - the folded realization equal to the tap-wise kernel and the XLA oracle,
    with fused bias + activation, batch > 1 and odd maps, on both the
    public wrapper path and the network executor;
  - the cost model folding exactly the narrow stems of the benchmark's
    networks, and leaving every other step's plan as it was before tap
    folding existed;
  - a plan cache written before tap folding existed re-tuned, not read
    back as a tap-wise plan.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.conv2d import conv2d_reference
from repro.core.conv_spec import (
    ConvAlgorithm,
    ConvSpec,
    Epilogue,
    apply_activation,
)
from repro.core.netplan import (
    NetworkExecutor,
    expected_channel_ops,
    layer_table,
    plan_network,
)
from repro.core.planner import PLAN_CACHE_VERSION, ConvPlan, Planner, plan_key
from repro.core.vmem_model import BlockConfig
from repro.kernels.conv_ops import conv2d_pallas
from repro.kernels.im2col_gemm.ops import pick_blocks
from repro.models.cnn import CNNLayer, cnn_forward, init_cnn


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# (name, batch, H, W, cin, cout, kernel, stride): the ResNet-50 stem at a
# reduced map, a 3x3 RGB stem (VGG-16, YOLOv3) and YOLOv3's 3x3/2 layer on
# 32 channels; odd maps throughout.
SHAPES = [
    ("stem7x7s2", 2, 23, 21, 3, 64, 7, 2),
    ("rgb3x3s1", 2, 17, 15, 3, 32, 3, 1),
    ("c32_3x3s2", 3, 19, 19, 32, 64, 3, 2),
]


@pytest.mark.parametrize("activation", ["relu", "leaky"])
@pytest.mark.parametrize("shape", SHAPES, ids=[s[0] for s in SHAPES])
def test_folded_matches_tapwise_and_oracle(shape, activation):
    _, b, h, w, cin, cout, k, s = shape
    spec = ConvSpec(cin, cout, (k, k), (s, s), (k // 2, k // 2))
    oh, ow = spec.out_hw(h, w)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(b, h, w, cin)), jnp.float32)
    wt = jnp.asarray(rng.normal(size=(k, k, cin, cout)) / np.sqrt(k * k * cin),
                     jnp.float32)
    bias = jnp.asarray(rng.normal(size=(cout,)), jnp.float32)
    epi = Epilogue(bias=bias, activation=activation)
    ref = apply_activation(
        conv2d_reference(x, wt, spec).astype(jnp.float32) + bias, activation)

    tapwise = ConvPlan(
        ConvAlgorithm.IM2COL_GEMM, "pallas", BlockConfig(128, 128, 128),
        pick_blocks(oh, ow, cin, cout, k, k, s, s), 0.0)
    # K in one full-dimension block where it fits (27), else padded to
    # the lane width and cut in 128-deep blocks (147 -> 256, 288 -> 384).
    k_taps = k * k * cin
    folded = ConvPlan(
        ConvAlgorithm.IM2COL_GEMM, "pallas", BlockConfig(128, 128, 128),
        (64, 128, k_taps if k_taps <= 128 else 128), 0.0, taps_folded=True)
    got_t = conv2d_pallas(x, wt, spec, ConvAlgorithm.IM2COL_GEMM,
                          interpret=True, plan=tapwise, epilogue=epi)
    got_f = conv2d_pallas(x, wt, spec, ConvAlgorithm.IM2COL_GEMM,
                          interpret=True, plan=folded, epilogue=epi)
    assert got_f.shape == ref.shape == (b, oh, ow, cout)
    assert _rel_l2(got_f, ref) < 2e-6
    assert _rel_l2(got_f, got_t) < 2e-6


def _folded_net():
    """A YOLOv3-like head: a 3x3 RGB stem and a 3x3/2 layer on its 8
    channels (both fold), a 1x1 and a 3x3 on 32 channels (tap-wise), and a
    shortcut."""
    C = CNNLayer
    return (
        C("conv", out_channels=8, kernel=3, activation="leaky"),
        C("conv", out_channels=64, kernel=3, stride=2, activation="leaky"),
        C("conv", out_channels=32, kernel=1, activation="leaky"),
        C("conv", out_channels=64, kernel=3, activation="leaky"),
        C("shortcut", from_layers=(1,)),
    )


def test_executor_runs_folded_layers_0_and_1():
    layers = _folded_net()
    params = init_cnn(jax.random.PRNGKey(0), layers)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 19, 19, 3))
    planner = Planner(impl="pallas", cache_path=None, fuse_epilogue=True)
    netplan = plan_network(layers, 19, 19, planner, batch=2)
    folded = [s.index for s in netplan.steps
              if s.plan is not None and s.plan.taps_folded]
    assert folded == [0, 1]
    # A folded step takes logical channels: the image is not padded to a
    # lane block, and layer 0 crops its output for layer 1.
    assert netplan.steps[0].in_layout.phys_c == 3
    assert netplan.steps[0].out_layout.trivial
    assert netplan.steps[1].in_layout.phys_c == 8
    got = NetworkExecutor(netplan, params, interpret=True)(x)
    ref = cnn_forward(params, layers, x, impl="xla")
    assert _rel_l2(got, ref) < 2e-6

    table = {l["index"]: l for l in layer_table(netplan)["layers"]}
    assert table[0]["scope"] == "L000.conv.im2col_gemm"
    assert (table[0]["taps_folded"], table[0]["folded_k"]) == (True, 27)
    assert (table[1]["taps_folded"], table[1]["folded_k"]) == (True, 72)
    assert table[2]["taps_folded"] is None      # direct
    assert table[3]["taps_folded"] is False and table[3]["folded_k"] is None
    # Both patch matrices are one K block, so neither pads its K.
    pads = [o["step"] for o in expected_channel_ops(netplan)
            if o["kind"] == "pad"]
    assert 0 not in pads and 1 not in pads


# ---------------------------------------------------------------------------
# Plans of the benchmark's networks


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cells():
    """Each cell's layer table, input size and batch, from the benchmark's
    configuration files."""
    def config(name):
        with open(os.path.join(ROOT, "perfbench", "configs",
                               f"{name}.json")) as f:
            cfg = json.load(f)
        layers = tuple(
            CNNLayer(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in l.items()})
            for l in cfg["layers"])
        return layers, tuple(cfg["input_hw"])

    return {
        "vgg16_b8": (*config("vgg16"), 8),
        "vgg16_b32": (*config("vgg16"), 32),
        "yolov3-20_b1": (*config("yolov3-20"), 1),
        "resnet50_b64": (*config("resnet50"), 64),
    }


W = ("winograd", (32, 128, 128))


def D(bn, bk):
    return ("direct", (512, bn, bk))


def I(toh, bo):
    return ("im2col_gemm", (toh, 128, bo))


# (algorithm, kernel blocks) of every conv step that does
# not fold, as the planner chose them before tap folding existed.
UNFOLDED = {
    "vgg16_b8": {
        1: W, 3: W, 4: W, 6: W, 7: W, 8: W, 10: W, 11: W, 12: W,
        14: I(14, 256), 15: I(14, 256), 16: I(14, 256), 18: I(1, 256),
    },
    "vgg16_b32": {
        1: W, 3: W, 4: W, 6: W, 7: W, 8: W, 10: W, 11: W, 12: W, 14: W, 15: W,
        16: W, 18: I(1, 256),
    },
    "yolov3-20_b1": {
        1: I(4, 128), 2: D(128, 128), 3: W, 5: I(8, 128), 6: D(128, 128), 7: W,
        9: D(128, 128), 10: W, 12: I(16, 128), 13: D(128, 256), 14: W,
        16: D(128, 256), 17: W, 19: D(128, 256),
    },
    "resnet50_b64": {
        2: D(128, 128), 3: W, 4: D(256, 128), 6: D(256, 128), 8: D(128, 256),
        9: W, 10: D(256, 128), 12: D(128, 256), 13: W, 14: D(256, 128),
        16: D(128, 256), 17: I(28, 128), 18: D(512, 128), 20: I(28, 256),
        22: D(128, 512), 23: W, 24: D(512, 128), 26: D(128, 512), 27: W,
        28: D(512, 128), 30: D(128, 512), 31: W, 32: D(512, 128),
        34: D(256, 512), 35: I(14, 256), 36: D(1024, 256), 38: I(14, 256),
        40: D(256, 1024), 41: W, 42: D(1024, 256), 44: D(256, 1024), 45: W,
        46: D(1024, 256), 48: D(256, 1024), 49: W, 50: D(1024, 256),
        52: D(256, 1024), 53: W, 54: D(1024, 256), 56: D(256, 1024), 57: W,
        58: D(1024, 256), 60: D(512, 512), 61: I(7, 256), 62: D(1024, 256),
        64: I(7, 256), 66: D(512, 512), 67: W, 68: D(1024, 256),
        70: D(512, 512), 71: W, 72: D(1024, 256),
    },
}

# (index, folded K, GEMM blocks) of every step that folds: K is one
# full-dimension block.
FOLDED = {
    "vgg16_b8": {0: (27, (512, 128, 27))},
    "vgg16_b32": {0: (27, (512, 128, 27))},
    "yolov3-20_b1": {0: (27, (512, 128, 27))},
    "resnet50_b64": {0: (147, (512, 128, 147))},
}


@pytest.mark.parametrize("cell", sorted(UNFOLDED))
def test_cost_model_folds_exactly_the_narrow_stems(cell):
    layers, (h, w), batch = _cells()[cell]
    planner = Planner(impl="pallas", cache_path=None, fuse_epilogue=True)
    netplan = plan_network(layers, h, w, planner, batch=batch)
    table = {l["index"]: l for l in layer_table(netplan)["layers"]}
    unfolded, folded = {}, {}
    for s in netplan.steps:
        if s.plan is None:
            continue
        if s.plan.taps_folded:
            assert s.plan.algorithm is ConvAlgorithm.IM2COL_GEMM
            assert s.in_layout.trivial          # logical channels in
            folded[s.index] = (table[s.index]["folded_k"],
                               tuple(s.plan.kernel_blocks))
        else:
            unfolded[s.index] = (s.plan.algorithm.value,
                                 tuple(s.plan.kernel_blocks))
    assert folded == FOLDED[cell]
    assert unfolded == UNFOLDED[cell]


def test_plan_cache_from_before_folding_is_tuned_again(tmp_path):
    """A cache written before tap folding existed (version 7, no
    ``taps_folded``) holds the ResNet-50 stem as a tap-wise plan; a planner
    must tune the stem again rather than read it back."""
    spec = ConvSpec(3, 64, (7, 7), (2, 2), (3, 3))
    key = plan_key(spec, 224, 224, 64, "tpu_v5e", "float32", "pallas",
                   "cost", 16 * 1024 * 1024, True)
    old = {
        "algorithm": "im2col_gemm", "impl": "pallas",
        "block": [512, 128, 256], "kernel_blocks": [8, 128, 128],
        "predicted_s": 0.0131, "source": "cost_model",
        "fused_epilogue": True, "dtype": "float32",
    }
    cache = os.path.join(tmp_path, "plans.json")
    with open(cache, "w") as f:
        json.dump({"version": 7, "chip": "tpu_v5e", "plans": {key: old},
                   "networks": {}, "pipelines": {}}, f)
    assert PLAN_CACHE_VERSION > 7
    planner = Planner(impl="pallas", cache_path=cache, fuse_epilogue=True)
    plan = planner.plan(spec, 224, 224, batch=64)
    assert planner.stats == {"hits": 0, "tunes": 1}
    assert plan.taps_folded and plan.kernel_blocks == (512, 128, 147)
    # The re-tuned plan persists and is read back as folded.
    again = Planner(impl="pallas", cache_path=cache, fuse_epilogue=True)
    assert again.plan(spec, 224, 224, batch=64) == plan
    assert again.stats == {"hits": 1, "tunes": 0}
