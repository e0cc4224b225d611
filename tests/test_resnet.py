"""ResNet-50 v1.5 on the normal path, and the two layer semantics it needs:
a max pool with symmetric -inf padding, and a shortcut that applies its
activation after the add (linear when the table names none).

The plain forward here is written from the published semantics with
``lax`` at HIGHEST and slicing, and shares nothing with the program's
layer walks.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import repro
from repro.configs import resnet50, yolov3
from repro.api.model import CNNModel
from repro.core.netplan import (
    layer_table,
    plan_network,
    prepare_net_params,
    run_network,
)
from repro.core.planner import Planner
from repro.core.quant import calibrate_activation_scales
from repro.models.cnn import (
    CNNLayer,
    activate_array,
    add_bias,
    batchnorm_inference,
    cnn_forward,
    conv_layer_dims,
    fold_batchnorm,
    init_cnn,
    max_pool,
    max_pool_out_hw,
)

C = CNNLayer
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Stem, padded pool, a projection block at stride 1, one at stride 2, an
#: identity block and fc; narrow widths.
SMALL = resnet50.layers(stem=8, stages=((8, 1, 1), (16, 2, 2)), classes=10)


def _params(layers, seed=0):
    """Seeded weights with random batchnorm statistics, so that folding
    them is exercised."""
    params = init_cnn(jax.random.PRNGKey(seed), layers)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4 * len(layers)))
    for p in params:
        if "bn" in p:
            n = p["bn"]["gamma"].shape
            p["bn"] = {
                "gamma": 1.0 + 0.1 * jax.random.normal(next(keys), n),
                "beta": 0.1 * jax.random.normal(next(keys), n),
                "mean": 0.1 * jax.random.normal(next(keys), n),
                "var": jax.random.uniform(next(keys), n, minval=0.5, maxval=1.5),
            }
    return params


def _pool_by_slices(x, size, stride, pad):
    """Max over the ``size`` x ``size`` strided slices of the -inf padded
    map: PyTorch's ``MaxPool2d(size, stride, pad)``."""
    x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                constant_values=-jnp.inf)
    oh = (x.shape[1] - size) // stride + 1
    ow = (x.shape[2] - size) // stride + 1
    return jnp.max(jnp.stack([
        x[:, i:i + stride * (oh - 1) + 1:stride, j:j + stride * (ow - 1) + 1:stride]
        for i in range(size) for j in range(size)]), axis=0)


def _act(x, kind):
    return {"relu": lambda v: jnp.maximum(v, 0.0), "linear": lambda v: v,
            "leaky": lambda v: jnp.where(v > 0, v, 0.1 * v)}[kind](x)


def plain_forward(params, layers, x):
    """The table's function in float32 at HIGHEST (ResNet's kinds only)."""
    outs = []
    for l, p in zip(layers, params):
        if l.kind == "conv":
            k = l.kernel // 2
            x = lax.conv_general_dilated(
                x, p["w"], (l.stride, l.stride), [(k, k), (k, k)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=lax.Precision.HIGHEST)
            bn = p["bn"]
            x = (x - bn["mean"]) / jnp.sqrt(bn["var"] + 1e-5) * bn["gamma"] \
                + bn["beta"]
            x = _act(x, l.activation)
        elif l.kind == "maxpool":
            x = _pool_by_slices(x, l.size, l.stride, l.pad)
        elif l.kind == "route":
            (j,) = l.from_layers
            x = outs[j]
        elif l.kind == "shortcut":
            x = _act(x + outs[l.from_layers[0]], l.activation)
        elif l.kind == "fc":
            x = jnp.dot(x.mean(axis=(1, 2)), p["w"],
                        precision=lax.Precision.HIGHEST) + p["b"]
        else:
            raise ValueError(l.kind)
        outs.append(x)
    return x


def _rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# The table


def test_resnet50_table_counts():
    kinds = [l.kind for l in resnet50.LAYERS]
    assert len(kinds) == 75
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "conv": 53, "route": 4, "shortcut": 16, "maxpool": 1, "fc": 1}
    dims = conv_layer_dims(resnet50.LAYERS, 224, 224)
    weights = sum(d["kernel"] ** 2 * d["cin"] * d["cout"] for d in dims)
    # torchvision counts 25.557 M with batchnorm and the fc bias.
    assert weights + 2048 * 1000 == 25_502_912
    # v1.5: each downsampling block strides on its 3x3 and its projection.
    strided = [(d["kernel"], d["h"]) for d in dims if d["stride"] == 2]
    assert strided == [(7, 224), (3, 56), (1, 56), (3, 28), (1, 28),
                       (3, 14), (1, 14)]
    assert (dims[-1]["h"], dims[-1]["cout"]) == (7, 2048)


def test_perfbench_config_matches_the_table():
    """The benchmark's configuration file runs this very table."""
    with open(os.path.join(REPO, "perfbench", "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    got = tuple(C(**{k: tuple(v) if isinstance(v, list) else v
                     for k, v in l.items()}) for l in cfg["layers"])
    assert got == resnet50.LAYERS
    assert tuple(cfg["input_hw"]) == resnet50.INPUT_HW


def test_layer_table_marks_the_residual_path():
    planner = Planner(impl="jax", cache_path=None)
    netplan = plan_network(SMALL, 32, 32, planner, batch=2)
    rows = layer_table(netplan)["layers"]
    marks = {r["index"]: r["residual"] for r in rows if r["residual"]}
    assert marks == {1: "source", 4: "source", 5: "branch", 7: "add",
                     10: "source", 11: "branch", 13: "add", 17: "add"}


# ---------------------------------------------------------------------------
# Layer semantics


@pytest.mark.parametrize("hw", [(7, 8), (112, 112)])
def test_padded_max_pool(hw):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, *hw, 3))
    x = x.at[..., 2].set(0.0)
    layer = C("maxpool", size=3, stride=2, pad=1)
    got = max_pool(x, layer)
    want = _pool_by_slices(x, 3, 2, 1)
    assert got.shape[1:3] == max_pool_out_hw(layer, *hw)
    np.testing.assert_array_equal(got, want)
    # An all-zero channel stays zero: no window is all padding.
    assert not np.any(np.asarray(got[..., 2]))
    # Without a pad, Darknet's "SAME" windows, as before.
    same = C("maxpool", size=2, stride=2)
    np.testing.assert_array_equal(
        max_pool(x, same),
        lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                          "SAME"))
    with pytest.raises(ValueError, match="pad"):
        C("maxpool", size=3, stride=2, pad=2)


def test_shortcut_activation_defaults():
    assert C("shortcut", from_layers=(0,)).activation == "linear"
    assert C("conv", out_channels=4).activation == "leaky"
    assert C("fc", out_channels=4).activation == "leaky"
    assert all(l.activation == "linear" for l in yolov3.LAYERS_20
               if l.kind == "shortcut")


#: conv -> padded pool -> conv -> shortcut (activation as named) -> conv.
def _semantics_table(activation):
    kw = {} if activation is None else {"activation": activation}
    return (
        C("conv", out_channels=4, kernel=3, activation="leaky"),
        C("maxpool", size=3, stride=2, pad=1),
        C("conv", out_channels=4, kernel=1, activation="linear"),
        C("shortcut", from_layers=(1,), **kw),
        C("conv", out_channels=4, kernel=1, activation="linear"),
    )


def _walk_cnn_forward(params, layers, x):
    return cnn_forward(params[:4], layers[:4], x, impl="jax")


def _walk_run_network(params, layers, x):
    netplan = plan_network(layers, 9, 9, Planner(impl="jax", cache_path=None),
                           batch=2)
    return run_network(netplan, prepare_net_params(netplan, params), x, stop=4)


def _walk_calibration(params, layers, x):
    """The int8 calibration walk's scale at the conv after the shortcut is
    the shortcut's per-channel max-abs over 127."""
    netplan = plan_network(layers, 9, 9, Planner(impl="jax", cache_path=None),
                           batch=2)
    scales = calibrate_activation_scales(
        netplan, fold_batchnorm(params, layers), x)
    return scales[4] * 127.0


WALKS = {"cnn_forward": _walk_cnn_forward, "run_network": _walk_run_network,
         "calibration": _walk_calibration}


@pytest.mark.parametrize("activation", [None, "relu"])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_every_walk_applies_the_shortcut_activation(walk, activation):
    """``cnn_forward``, ``run_network`` and the int8 calibration walk run
    the padded pool and the shortcut's activation alike."""
    layers = _semantics_table(activation)
    params = _params(layers, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 9, 3))
    pooled = plain_forward(params[:2], layers[:2], x)
    branch = plain_forward(params[:3], layers[:3], x)
    want = _act(branch + pooled, activation or "linear")
    got = WALKS[walk](params, layers, x)
    if walk == "calibration":
        want = jnp.max(jnp.abs(want), axis=(0, 1, 2))
    # fp32 convs and a folded batchnorm differ from the unfolded one in the
    # last bits; a missing ReLU would differ at O(1).
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if activation == "relu" and walk != "calibration":
        assert float(jnp.min(got)) == 0.0


def _legacy_forward(params, layers, x):
    """``cnn_forward`` as it was before the padded pool and the shortcut
    activation: "SAME" windows, a bare add."""
    outputs = []
    cur = x
    for l, p in zip(layers, params):
        if l.kind == "conv":
            from repro.core.conv2d import conv2d_reference
            from repro.models.cnn import _conv_spec

            cur = conv2d_reference(cur, p["w"], _conv_spec(l, cur.shape[-1]))
            cur = batchnorm_inference(cur, p["bn"]) if "bn" in p \
                else add_bias(cur, p["b"])
            cur = activate_array(cur, l.activation)
        elif l.kind == "maxpool":
            cur = lax.reduce_window(cur, -jnp.inf, lax.max,
                                    (1, l.size, l.size, 1),
                                    (1, l.stride, l.stride, 1), "SAME")
        elif l.kind == "upsample":
            cur = jnp.repeat(jnp.repeat(cur, l.size, axis=1), l.size, axis=2)
        elif l.kind == "shortcut":
            cur = cur + outputs[l.from_layers[0]]
        elif l.kind == "route":
            cur = jnp.concatenate([outputs[j] for j in l.from_layers], axis=-1)
        outputs.append(cur)
    return cur


@pytest.mark.parametrize("name", ["yolov3-20", "yolov3-tiny"])
def test_darknet_tables_keep_their_outputs(name):
    """Tables that name no shortcut activation and no pool pad give
    bit-identical outputs to the semantics before either existed."""
    layers = {"yolov3-20": yolov3.LAYERS_20, "yolov3-tiny": yolov3.TINY_LAYERS}[name]
    params = _params(layers, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 32, 3))
    fwd = jax.jit(lambda p, xx: cnn_forward(p, layers, xx, impl="xla"))
    old = jax.jit(lambda p, xx: _legacy_forward(p, layers, xx))
    np.testing.assert_array_equal(fwd(params, x), old(params, x))


# ---------------------------------------------------------------------------
# Whole networks through repro.compile


def test_small_resnet_pallas_matches_plain_forward():
    """Stem (7x7/2 im2col), padded pool, both kinds of bottleneck and fc on
    the Pallas kernels (interpret mode) against the plain forward."""
    params = _params(SMALL, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 32, 3))
    model = CNNModel(SMALL, (32, 32), name="resnet-small")
    compiled = repro.compile(model, params, repro.ExecutionOptions(
        impl="pallas", interpret=True, batch=2, cache_path=None))
    got = compiled.run(x)
    want = plain_forward(params, SMALL, x)
    assert got.shape == (2, 10)
    # Both sides are fp32 at HIGHEST; they differ by summation order,
    # folded batchnorm and the Winograd F(6,3) transforms' rounding: 1.98e-7
    # here (CPU).  The limit leaves 10x; a wrong pool window or a missing
    # ReLU reads O(1e-1).
    assert _rel_l2(got, want) < 2e-6


def test_resnet50_jax_impl_at_64():
    """The full 75-entry table through the jax path, at 64 x 64."""
    params = _params(resnet50.LAYERS, seed=9)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 64, 64, 3))
    compiled = repro.compile(resnet50.MODEL.with_input_hw((64, 64)), params,
                             repro.ExecutionOptions(impl="jax", batch=1,
                                                    cache_path=None))
    got = compiled.run(x)
    want = jax.jit(lambda p, xx: plain_forward(p, resnet50.LAYERS, xx))(params, x)
    assert got.shape == (1, 1000)
    # 53 fp32 convs, some as Winograd F(6,3) or im2col on the jax path,
    # round differently from direct convs: 8.4e-7 here (CPU), 53 layers
    # deep.  The limit leaves 12x.
    assert _rel_l2(got, want) < 1e-5
