"""Network-level executor: conformance, layout elision, sharding, serving.

The acceptance surface of the NetworkPlan/NetworkExecutor subsystem
(core/netplan.py):

  - executor output == the per-layer ``cnn_forward`` path == the XLA oracle
    for VGG-16 and YOLOv3-tiny at batch 1/4/8 (spatial dims scaled down so
    the suite stays fast — the layer-boundary math is resolution-free);
  - the jaxpr of a planned 2-conv chain contains **no** interior pad/slice
    ops once layouts are compatible (the crop+re-pad pair is elided);
  - elision is *numerically* invisible on the pallas interpret path;
  - shard_map data parallelism over the batch axis matches single-device;
  - the CNN serving engine's bucket dispatch returns per-request outputs
    identical to direct inference, and re-opens warm from the v4 cache.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.netplan import (
    Layout,
    NetworkExecutor,
    plan_network,
    prepare_net_params,
    run_network,
)
from repro.core.planner import Planner
from repro.models.cnn import CNNLayer, cnn_forward, init_cnn

C = CNNLayer


def _models():
    from repro.configs import vgg16, yolov3

    return {"vgg16": vgg16.LAYERS, "yolov3-tiny": yolov3.TINY_LAYERS}


def _tol(ref):
    scale = float(jnp.max(jnp.abs(ref)))
    return dict(rtol=1e-4, atol=1e-4 * max(scale, 1.0))


# ---------------------------------------------------------------------------
# Conformance: executor vs per-layer forward vs XLA oracle


@pytest.mark.parametrize("model", ["vgg16", "yolov3-tiny"])
@pytest.mark.parametrize("batch", [1, 4, 8])
def test_executor_matches_per_layer_and_oracle(model, batch):
    """Acceptance: the planned executor run is numerically equal (fp32
    tolerance) to the per-layer cnn_forward path and the XLA oracle."""
    layers = _models()[model]
    params = init_cnn(jax.random.PRNGKey(0), layers)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 32, 32, 3))

    planner = Planner(impl="jax", cache_path=None)
    netplan = plan_network(layers, 32, 32, planner, batch=batch)
    executor = NetworkExecutor(netplan, params)
    got = executor(x)

    oracle = cnn_forward(params, layers, x, impl="xla")
    plans = [s.plan for s in netplan.steps]
    perlayer = cnn_forward(params, layers, x, impl="jax", plans=plans)
    np.testing.assert_allclose(got, oracle, **_tol(oracle))
    np.testing.assert_allclose(got, perlayer, **_tol(perlayer))


def test_executor_pallas_elision_matches_reference():
    """Layout persistence on the pallas interpret path: a mixed net whose
    channel pads genuinely flow (conv -> pool -> conv -> conv) matches the
    trivially-laid-out jax reference."""
    layers = (
        C("conv", out_channels=24, kernel=3, activation="relu"),
        C("maxpool", size=2, stride=2),
        C("conv", out_channels=40, kernel=1, pad=0, batch_norm=False,
          activation="leaky"),
        C("conv", out_channels=17, kernel=3, activation="leaky"),
    )
    params = init_cnn(jax.random.PRNGKey(2), layers)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 12, 3))
    ref = cnn_forward(params, layers, x, impl="xla")

    planner = Planner(impl="pallas", cache_path=None)
    netplan = plan_network(layers, 12, 12, planner, batch=2)
    assert netplan.elided_boundaries >= 1, "expected at least one elision"
    executor = NetworkExecutor(netplan, params, interpret=True)
    got = executor(x)
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_executor_batch_keyed_network_cache(tmp_path):
    """Plans are batch-keyed and the network entry persists: a fresh
    planner on the same cache rebuilds each batch's NetworkPlan with zero
    tunes and a network-entry hit per batch."""
    layers = _models()["vgg16"]
    cache = os.path.join(tmp_path, "plans.json")
    p1 = Planner(impl="jax", cache_path=cache, autosave=False)
    np1 = plan_network(layers, 32, 32, p1, batch=1)
    np4 = plan_network(layers, 32, 32, p1, batch=4)
    p1.save()
    assert p1.stats["tunes"] > 0 and p1.network_hits == 0

    p2 = Planner(impl="jax", cache_path=cache)
    np1b = plan_network(layers, 32, 32, p2, batch=1)
    np4b = plan_network(layers, 32, 32, p2, batch=4)
    assert p2.stats["tunes"] == 0 and p2.network_hits == 2
    assert np1b == np1 and np4b == np4


# ---------------------------------------------------------------------------
# Layout elision: the jaxpr has no interior pad/slice ops


# The walker now lives in the static-analysis subsystem (it is the elision
# pass's foundation); the test keeps its old local name.
from repro.analysis import boundary_ops as _boundary_ops  # noqa: E402


def test_two_conv_chain_jaxpr_has_no_interior_pad_or_slice():
    """Acceptance: a planned 2-conv chain with compatible layouts compiles
    to a jaxpr with zero pad/slice ops outside the kernels — entry needs no
    pad (channels lane-aligned), the boundary is elided, exit needs no crop."""
    layers = (
        C("conv", out_channels=256, kernel=1, pad=0, batch_norm=False,
          activation="relu"),
        C("conv", out_channels=128, kernel=1, pad=0, batch_norm=False,
          activation="linear"),
    )
    params = init_cnn(jax.random.PRNGKey(0), layers, in_channels=128)
    planner = Planner(impl="pallas", cache_path=None)
    netplan = plan_network(layers, 8, 8, planner, in_channels=128, batch=2)
    prepared = prepare_net_params(netplan, params, pretransform=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 128))

    bad = _boundary_ops(
        lambda p, xx: run_network(netplan, p, xx, interpret=True),
        prepared, x,
    )
    assert not bad, f"interior pad/slice ops survived elision: {bad}"

    # And the chain still computes the right thing.
    got = run_network(netplan, prepared, x, interpret=True)
    ref = cnn_forward(params, layers, x, impl="xla")
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_padded_chain_pads_once_and_crops_once():
    """With unaligned channels the executor still owns the boundaries: one
    entry pad, one exit crop, nothing in between (the 24->40 boundary's
    crop+re-pad pair is elided)."""
    layers = (
        C("conv", out_channels=40, kernel=1, pad=0, batch_norm=False,
          activation="relu"),
        C("conv", out_channels=24, kernel=1, pad=0, batch_norm=False,
          activation="linear"),
    )
    params = init_cnn(jax.random.PRNGKey(0), layers, in_channels=24)
    planner = Planner(impl="pallas", cache_path=None)
    netplan = plan_network(layers, 8, 8, planner, in_channels=24, batch=2)
    assert not netplan.steps[0].out_layout.trivial, "boundary should elide"
    prepared = prepare_net_params(netplan, params, pretransform=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 24))
    ops = _boundary_ops(
        lambda p, xx: run_network(netplan, p, xx, interpret=True),
        prepared, x,
    )
    assert ops.count("pad") == 1 and ops.count("slice") == 1, ops


def test_row_tile_snapped_to_divisor_of_oh():
    """Network-level adjustment: the im2col row tile toh divides OH, so the
    kernel's row-block pad/crop pair vanishes identically."""
    from repro.core.conv_spec import ConvAlgorithm

    layers = (
        C("conv", out_channels=32, kernel=3, stride=2, activation="leaky"),
    )
    planner = Planner(impl="pallas", cache_path=None)
    # 28x28 stride-2 -> OH = 14; an autotuned toh of e.g. 8 would emit 16
    # rows; the plan must land on a divisor of 14.  The input is a lane
    # block wide: a narrower one folds its taps into one GEMM, which has
    # no row tile.
    netplan = plan_network(layers, 28, 28, planner, in_channels=128,
                           batch=1)
    step = netplan.steps[0]
    assert step.plan.algorithm is ConvAlgorithm.IM2COL_GEMM
    assert not step.plan.taps_folded
    toh = step.plan.kernel_blocks[0]
    assert step.out_hw[0] % toh == 0, (toh, step.out_hw)

    # Prime OH (149): the best divisor is 1 — the snap must NOT take it
    # (one program per output row); the tuned tile stays and the executor
    # crops the row tail instead.
    prime = (
        C("conv", out_channels=128, kernel=3, stride=2, activation="leaky"),
        C("conv", out_channels=32, kernel=5, stride=1, pad=2,
          activation="leaky"),
    )
    netplan_p = plan_network(prime, 297, 297, Planner(impl="pallas",
                                                      cache_path=None),
                             batch=1)
    step_p = netplan_p.steps[1]        # 149x149 input, 5x5 -> im2col
    assert step_p.plan.algorithm is ConvAlgorithm.IM2COL_GEMM
    assert not step_p.plan.taps_folded
    assert step_p.out_hw[0] == 149
    assert step_p.plan.kernel_blocks[0] > 1


def test_layout_invariants():
    lo = Layout(24, 104)
    assert lo.phys_c == 128 and not lo.trivial
    assert Layout.from_json(lo.to_json()) == lo
    assert Layout(24).trivial


# ---------------------------------------------------------------------------
# Data-parallel batch execution (shard_map over the batch axis)


def test_executor_shard_map_matches_single_device():
    from conftest import run_with_devices

    out = run_with_devices(2, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.netplan import NetworkExecutor, plan_network
        from repro.core.planner import Planner
        from repro.models.cnn import CNNLayer, init_cnn

        C = CNNLayer
        layers = (
            C("conv", out_channels=16, kernel=3, activation="relu"),
            C("maxpool", size=2, stride=2),
            C("conv", out_channels=8, kernel=1, pad=0, batch_norm=False,
              activation="linear"),
        )
        params = init_cnn(jax.random.PRNGKey(0), layers)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))
        planner = Planner(impl="jax", cache_path=None)
        netplan = plan_network(layers, 8, 8, planner, batch=4)
        sharded = NetworkExecutor(netplan, params)          # 2 devices
        single = NetworkExecutor(netplan, params,
                                 devices=jax.devices()[:1])  # fallback
        assert sharded.mesh is not None and single.mesh is None
        np.testing.assert_allclose(np.asarray(sharded(x)),
                                   np.asarray(single(x)),
                                   rtol=1e-5, atol=1e-5)
        print("SHARDED_OK", sharded(x).shape)
    """)
    assert "SHARDED_OK" in out


# ---------------------------------------------------------------------------
# CNN serving engine: bucket dispatch + warm plan-per-bucket cache


def _tiny_net():
    layers = (
        C("conv", out_channels=16, kernel=3, activation="relu"),
        C("maxpool", size=2, stride=2),
        C("conv", out_channels=8, kernel=1, pad=0, batch_norm=False,
          activation="linear"),
    )
    params = init_cnn(jax.random.PRNGKey(0), layers)
    return layers, params


def _facade_engine(layers, params, buckets, cache_path, **kw):
    """Engine over the tiny net via the facade (direct construction of
    ``CNNServingEngine`` was a one-release shim and is gone)."""
    import repro

    compiled = repro.compile(
        repro.CNNModel(layers, (8, 8), name="tiny-netplan"), params,
        repro.ExecutionOptions(impl="jax", cache_path=cache_path,
                               buckets=tuple(buckets)),
    )
    return compiled.serve(**kw)


def test_cnn_engine_bucket_dispatch_and_results(tmp_path):
    layers, params = _tiny_net()
    cache = os.path.join(tmp_path, "plans.json")
    eng = _facade_engine(layers, params, (1, 2, 4), cache)
    rng = np.random.default_rng(0)
    imgs = rng.normal(size=(5, 8, 8, 3)).astype(np.float32)
    uids = [eng.submit(im) for im in imgs]
    results = eng.run()
    assert set(results) == set(uids)
    # 5 pending -> one full 4-bucket, then the 1-bucket; nothing padded.
    assert eng.stats["batches"] == {1: 1, 2: 0, 4: 1}
    assert eng.stats["padded_slots"] == 0

    # Per-request outputs equal direct single-image inference.
    ref = np.asarray(
        cnn_forward(params, layers, jnp.asarray(imgs), impl="xla")
    )
    for i, u in enumerate(uids):
        np.testing.assert_allclose(results[u], ref[i], rtol=1e-4, atol=1e-4)


def test_cnn_engine_pads_tail_bucket(tmp_path):
    layers, params = _tiny_net()
    eng = _facade_engine(layers, params, (4,),
                         os.path.join(tmp_path, "p.json"))
    rng = np.random.default_rng(1)
    imgs = rng.normal(size=(3, 8, 8, 3)).astype(np.float32)
    out = eng.infer(imgs)
    assert out.shape[0] == 3
    assert eng.stats["padded_slots"] == 1
    assert eng.stats["batches"][4] == 1


def test_cnn_engine_rejects_bad_shapes_and_buckets(tmp_path):
    from repro.serving import CNNServingEngine

    layers, params = _tiny_net()
    # Bucket validation still fires before the constructed-from-compilation
    # check, so an empty ladder is a ValueError, not the shim TypeError.
    with pytest.raises(ValueError):
        CNNServingEngine(layers, params, (8, 8), buckets=(),
                         cache_path=None)
    eng = _facade_engine(layers, params, (1,), None)
    with pytest.raises(ValueError):
        eng.submit(np.zeros((4, 4, 3), np.float32))


def test_cnn_engine_warm_cache_per_bucket(tmp_path):
    layers, params = _tiny_net()
    cache = os.path.join(tmp_path, "plans.json")
    cold = _facade_engine(layers, params, (1, 2), cache)
    assert cold.planner.stats["tunes"] > 0
    warm = _facade_engine(layers, params, (1, 2), cache)
    assert warm.warm and warm.planner.network_hits == 2


# ---------------------------------------------------------------------------
# CI smoke: tiny interpret-mode executor chain + one engine round-trip


def test_ci_smoke_two_layer_chain_interpret():
    """CI executor smoke: a 2-layer planned chain through the pallas
    kernels in interpret mode."""
    layers = (
        C("conv", out_channels=16, kernel=3, activation="relu"),
        C("conv", out_channels=8, kernel=1, pad=0, batch_norm=False,
          activation="linear"),
    )
    params = init_cnn(jax.random.PRNGKey(0), layers)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 3))
    planner = Planner(impl="pallas", cache_path=None)
    netplan = plan_network(layers, 8, 8, planner, batch=1)
    got = NetworkExecutor(netplan, params, interpret=True)(x)
    ref = cnn_forward(params, layers, x, impl="xla")
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


def test_ci_smoke_engine_bucket_roundtrip(tmp_path):
    """CI serving smoke: one bucket round-trip through the engine."""
    layers, params = _tiny_net()
    eng = _facade_engine(layers, params, (2,),
                         os.path.join(tmp_path, "p.json"))
    imgs = np.random.default_rng(2).normal(size=(2, 8, 8, 3)).astype(
        np.float32
    )
    out = eng.infer(imgs)
    assert out.shape[0] == 2 and np.isfinite(out).all()
    assert eng.stats["batches"][2] == 1
