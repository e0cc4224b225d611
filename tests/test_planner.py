"""Planner: persistent plan cache, cost-model routing parity, and planned
conv2d correctness against the XLA oracle."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.codesign import select_algorithm_by_cost
from repro.core.conv_spec import ConvAlgorithm, ConvSpec
from repro.core.conv2d import conv2d, conv2d_reference
from repro.core.planner import (
    PLAN_CACHE_VERSION,
    ConvPlan,
    Planner,
    plan_key,
)

# The three layer classes the selector distinguishes (paper §VII.A).
LAYER_CASES = [
    # (spec, h, w)
    (ConvSpec(8, 16, (1, 1), (1, 1), (0, 0)), 14, 14),        # direct 1x1
    (ConvSpec(8, 16, (3, 3), (1, 1), (1, 1)), 20, 20),        # 3x3 stride-1
    (ConvSpec(8, 16, (3, 3), (2, 2), (1, 1)), 20, 20),        # strided
    (ConvSpec(4, 6, (5, 5), (2, 2), (2, 2)), 17, 17),         # generic
]


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def test_plan_cache_round_trip(tmp_path):
    """write -> reload in a fresh Planner -> every lookup is a hit."""
    cache = os.path.join(tmp_path, "plans.json")
    p1 = Planner(cache_path=cache)
    plans = [p1.plan(s, h, w, batch=2) for s, h, w in LAYER_CASES]
    assert p1.stats == {"hits": 0, "tunes": len(LAYER_CASES)}
    assert os.path.exists(cache)

    p2 = Planner(cache_path=cache)
    replans = [p2.plan(s, h, w, batch=2) for s, h, w in LAYER_CASES]
    assert p2.stats == {"hits": len(LAYER_CASES), "tunes": 0}
    assert replans == plans  # identical decisions, not just same algorithms

    # The file itself is versioned JSON with round-trippable plan records.
    from repro.core.planner import PLAN_CACHE_VERSION

    data = json.load(open(cache))
    assert data["version"] == PLAN_CACHE_VERSION
    assert len(data["plans"]) == len(LAYER_CASES)
    for d in data["plans"].values():
        assert ConvPlan.from_json(d).to_json() == d


def test_cache_key_distinguishes_shape_dtype_batch():
    spec = ConvSpec(8, 16)
    k = lambda **kw: plan_key(spec, kw.get("h", 20), kw.get("w", 20),
                              kw.get("batch", 1), "tpu_v5e",
                              kw.get("dtype", "float32"), "jax")
    base = k()
    assert k(h=21) != base
    assert k(batch=2) != base
    assert k(dtype="bfloat16") != base
    # mode and VMEM budget change the decision, so they change the key:
    # a measure-mode planner must never reuse a cost-model plan.
    assert plan_key(spec, 20, 20, 1, "tpu_v5e", "float32", "jax",
                    mode="measure") != base
    assert plan_key(spec, 20, 20, 1, "tpu_v5e", "float32", "jax",
                    vmem_budget=2 * 1024 * 1024) != base


def test_corrupt_cache_is_cold_start(tmp_path):
    cache = os.path.join(tmp_path, "plans.json")
    with open(cache, "w") as f:
        f.write("{not json")
    p = Planner(cache_path=cache)           # must not raise
    spec, h, w = LAYER_CASES[0]
    p.plan(spec, h, w)
    assert p.stats["tunes"] == 1
    json.load(open(cache))                  # overwritten with a valid cache


def test_cost_plan_matches_cost_selector_routing():
    """Cost-mode plans route exactly like select_algorithm_by_cost."""
    planner = Planner(cache_path=None)
    shapes = [(ConvSpec(c, o, (3, 3), (1, 1), (1, 1)), h, h)
              for c, o, h in [(16, 32, 104), (256, 512, 13), (64, 128, 52)]]
    for spec, h, w in shapes + LAYER_CASES:
        plan = planner.plan(spec, h, w)
        assert plan.algorithm is select_algorithm_by_cost(spec, h, w)
        assert plan.source == "cost_model"
        assert plan.predicted_s > 0
        assert plan.block.vmem_bytes() <= planner.vmem_budget


def test_forced_algorithm_is_respected():
    spec = ConvSpec(8, 16, (3, 3), (1, 1), (1, 1),
                    algorithm=ConvAlgorithm.IM2COL_GEMM)
    plan = Planner(cache_path=None).plan(spec, 20, 20)
    assert plan.algorithm is ConvAlgorithm.IM2COL_GEMM


@pytest.mark.parametrize("case", range(len(LAYER_CASES)))
def test_planned_conv2d_matches_reference(case):
    """conv2d driven by a plan == XLA oracle for 1x1 / 3x3-s1 / strided."""
    spec, h, w = LAYER_CASES[case]
    planner = Planner(cache_path=None)
    plan = planner.plan(spec, h, w, batch=2)
    x = _rand((2, h, w, spec.in_channels), case)
    wt = _rand((spec.kh, spec.kw, spec.in_channels, spec.out_channels), case + 10)
    got = conv2d(x, wt, spec, plan=plan)
    ref = conv2d_reference(x, wt, spec)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_planned_conv2d_pallas_uses_plan_blocks():
    """A pallas-impl plan threads its block sizes into the kernels and still
    matches the oracle (interpret mode on CPU)."""
    planner = Planner(cache_path=None, impl="pallas")
    for spec, h, w in LAYER_CASES[:3]:
        plan = planner.plan(spec, h, w)
        assert plan.impl == "pallas"
        x = _rand((1, h, w, spec.in_channels), 3)
        wt = _rand((spec.kh, spec.kw, spec.in_channels, spec.out_channels), 4)
        got = conv2d(x, wt, spec, plan=plan, interpret=True)
        ref = conv2d_reference(x, wt, spec)
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


def test_measure_mode_smoke():
    """Measure mode times real candidates and its winner is numerically right."""
    planner = Planner(cache_path=None, mode="measure", measure_reps=1)
    spec = ConvSpec(4, 8, (3, 3), (1, 1), (1, 1))
    plan = planner.plan(spec, 12, 12)
    assert plan.source == "measured"
    assert plan.algorithm in (ConvAlgorithm.WINOGRAD, ConvAlgorithm.IM2COL_GEMM)
    assert plan.predicted_s > 0
    x, wt = _rand((1, 12, 12, 4), 5), _rand((3, 3, 4, 8), 6)
    np.testing.assert_allclose(
        conv2d(x, wt, spec, plan=plan), conv2d_reference(x, wt, spec),
        rtol=2e-4, atol=2e-4,
    )


def test_planner_threads_through_cnn_forward(tmp_path):
    """_plan_layers + cnn_forward(plans=...) == unplanned forward, and the
    whole network's plans persist."""
    import jax

    from repro.models.cnn import CNNLayer, _plan_layers, cnn_forward, init_cnn

    layers = (
        CNNLayer("conv", out_channels=8, kernel=3, stride=1),
        CNNLayer("maxpool", size=2, stride=2),
        CNNLayer("conv", out_channels=12, kernel=1, stride=1, pad=0),
        CNNLayer("conv", out_channels=12, kernel=3, stride=2),
    )
    cache = os.path.join(tmp_path, "net.json")
    planner = Planner(cache_path=cache)
    plans = _plan_layers(layers, 16, 16, planner, in_channels=3)
    assert [p is not None for p in plans] == [True, False, True, True]

    params = init_cnn(jax.random.PRNGKey(0), layers)
    x = _rand((2, 16, 16, 3), 9)
    planned = cnn_forward(params, layers, x, plans=plans)
    unplanned = cnn_forward(params, layers, x)
    np.testing.assert_allclose(planned, unplanned, rtol=2e-4, atol=2e-4)

    warm = Planner(cache_path=cache)
    _plan_layers(layers, 16, 16, warm, in_channels=3)
    assert warm.stats["tunes"] == 0


def test_plan_records_fused_epilogue(tmp_path):
    """Planner(fuse_epilogue=True) stamps plans, keys them separately from
    unfused plans, and round-trips the flag through the JSON cache."""
    cache = os.path.join(tmp_path, "fused.json")
    spec = ConvSpec(8, 16)
    fused = Planner(cache_path=cache, fuse_epilogue=True)
    plain = Planner(cache_path=cache)
    pf = fused.plan(spec, 20, 20)
    pu = plain.plan(spec, 20, 20)
    assert pf.fused_epilogue and not pu.fused_epilogue
    assert plan_key(spec, 20, 20, 1, "tpu_v5e", "float32", "jax",
                    fuse_epilogue=True) != plan_key(
        spec, 20, 20, 1, "tpu_v5e", "float32", "jax")
    # Both live in the same cache file; a warm fused planner re-tunes nothing.
    warm = Planner(cache_path=cache, fuse_epilogue=True)
    assert warm.plan(spec, 20, 20).fused_epilogue
    assert warm.stats["tunes"] == 0


def test_winograd_plan_records_fused_megakernel(tmp_path):
    """Winograd plans autotune (bt, bc, bo) against the megakernel's
    footprint and persist them, and a warm planner re-tunes nothing."""
    from repro.core.vmem_model import winograd_kernel_vmem_bytes

    cache = os.path.join(tmp_path, "wino.json")
    spec = ConvSpec(64, 128, (3, 3), (1, 1), (1, 1))
    planner = Planner(cache_path=cache)
    plan = planner.plan(spec, 152, 152)
    assert plan.algorithm is ConvAlgorithm.WINOGRAD
    bt, bc, bo = plan.kernel_blocks
    assert winograd_kernel_vmem_bytes(bt, bc, bo) <= planner.vmem_budget

    # Round-trips through the JSON cache, zero re-tunes on a warm planner.
    warm = Planner(cache_path=cache)
    replan = warm.plan(spec, 152, 152)
    assert warm.stats == {"hits": 1, "tunes": 0}
    assert replan == plan

    data = json.load(open(cache))
    assert data["version"] == 9   # v9: one Winograd kernel (v8: tap folding)
    (record,) = data["plans"].values()
    assert set(record) == {"algorithm", "impl", "block", "kernel_blocks",
                           "predicted_s", "source", "fused_epilogue", "dtype",
                           "taps_folded"}


def test_plan_cache_from_before_one_winograd_kernel_is_planned_again(
        tmp_path):
    """A version-8 cache keys its plans with the Winograd policy token
    (``wfa``) and records the realization on each plan.  It loads without
    error, the layer is planned again to the same plan, and the rewritten
    cache holds neither the old key nor the field."""
    spec = ConvSpec(64, 128, (3, 3), (1, 1), (1, 1))
    plan = Planner(cache_path=None).plan(spec, 152, 152)
    assert plan.algorithm is ConvAlgorithm.WINOGRAD
    key = plan_key(spec, 152, 152, 1, "tpu_v5e", "float32", "jax", "cost",
                   Planner(cache_path=None).vmem_budget)
    old_key = key.replace("|e0|", "|e0|wfa|")
    assert old_key != key
    record = dict(plan.to_json(), winograd_fused=True)
    assert ConvPlan.from_json(record) == plan
    cache = os.path.join(tmp_path, "plans.json")
    with open(cache, "w") as f:
        json.dump({"version": 8, "chip": "tpu_v5e",
                   "plans": {old_key: record}, "networks": {},
                   "pipelines": {}}, f)
    planner = Planner(cache_path=cache)
    assert planner.plan(spec, 152, 152) == plan
    assert planner.stats == {"hits": 0, "tunes": 1}
    data = json.load(open(cache))
    assert data["version"] == PLAN_CACHE_VERSION
    assert list(data["plans"]) == [key]
    assert "winograd_fused" not in data["plans"][key]


def test_measure_mode_times_each_algorithm_once(monkeypatch):
    """Measure mode on the Pallas path times each eligible realization of a
    narrow 3x3 layer exactly once (Winograd, im2col, im2col with its taps
    folded) and keeps the fastest, which then runs correctly."""
    import importlib
    import time

    conv2d_mod = importlib.import_module("repro.core.conv2d")

    # Seconds per run on a fake clock; the fastest is not the first timed.
    costs = {(ConvAlgorithm.WINOGRAD, False): 2.0,
             (ConvAlgorithm.IM2COL_GEMM, False): 3.0,
             (ConvAlgorithm.IM2COL_GEMM, True): 1.0}

    real_conv2d = conv2d_mod.conv2d
    traced = []

    def traced_conv2d(x, w, spec, plan=None, **kw):
        # Runs at trace time, once per jitted candidate.
        traced.append((plan.algorithm, plan.taps_folded))
        b, h, ww, _ = x.shape
        return jnp.zeros((b, *spec.out_hw(h, ww), spec.out_channels),
                         x.dtype)

    clock = [0.0]

    def perf_counter():
        clock[0] += costs[traced[-1]] if traced else 0.0
        return clock[0]

    monkeypatch.setattr(conv2d_mod, "conv2d", traced_conv2d)
    monkeypatch.setattr(time, "perf_counter", perf_counter)
    spec = ConvSpec(4, 8, (3, 3), (1, 1), (1, 1))
    planner = Planner(cache_path=None, mode="measure", impl="pallas",
                      measure_reps=3)
    plan = planner.plan(spec, 12, 12)
    monkeypatch.undo()
    assert sorted(traced, key=str) == sorted(costs, key=str)
    best = min(costs, key=costs.get)
    assert (plan.algorithm, plan.taps_folded) == best
    assert plan.source == "measured" and plan.predicted_s == costs[best]
    x, wt = _rand((1, 12, 12, 4), 15), _rand((3, 3, 4, 8), 16)
    np.testing.assert_allclose(
        real_conv2d(x, wt, spec, plan=plan, interpret=True),
        conv2d_reference(x, wt, spec),
        rtol=5e-4, atol=5e-4,
    )


def test_options_from_before_one_winograd_kernel_load():
    """A model saved with the removed ``winograd_fused`` option loads with
    the same options as one saved without it."""
    from repro import ExecutionOptions

    opts = ExecutionOptions(impl="pallas", batch=8)
    saved = opts.to_json()
    assert "winograd_fused" not in saved
    legacy = dict(saved, winograd_fused=None)
    assert ExecutionOptions.from_json(legacy) == opts
    assert ExecutionOptions.from_json(dict(saved, winograd_fused=True)) \
        == ExecutionOptions.from_json(saved)


def test_fused_plan_drives_cnn_forward_fusion():
    """A fused_epilogue plan opts its layer into in-kernel fusion even when
    cnn_forward isn't asked to fuse globally — outputs must match the
    unfused path (on bn-folded params)."""
    import jax

    from repro.models.cnn import (
        CNNLayer, _plan_layers, cnn_forward, fold_batchnorm, init_cnn,
    )

    layers = (
        CNNLayer("conv", out_channels=8, kernel=3, stride=1),
        CNNLayer("conv", out_channels=12, kernel=1, stride=1, pad=0,
                 batch_norm=False),
    )
    planner = Planner(cache_path=None, fuse_epilogue=True)
    plans = _plan_layers(layers, 16, 16, planner, in_channels=3)
    assert all(p.fused_epilogue for p in plans)

    params = fold_batchnorm(init_cnn(jax.random.PRNGKey(0), layers), layers)
    x = _rand((1, 16, 16, 3), 11)
    fused = cnn_forward(params, layers, x, plans=plans)
    unfused = cnn_forward(params, layers, x)
    np.testing.assert_allclose(fused, unfused, rtol=2e-4, atol=2e-4)
