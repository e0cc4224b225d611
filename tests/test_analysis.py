"""Compile-time plan verifier: clean-plan proofs and mutation coverage.

The acceptance surface of the static-analysis subsystem (repro.analysis):

  - a cleanly planned VGG-16 / YOLOv3-tiny verifies with **zero** findings
    at fp32 and int8, full level (trace + all five passes) and plan level;
  - each analysis pass catches exactly its injected NetworkPlan corruption:
      oversized kernel block            -> vmem (budget proof)
      wrong declared accumulator dtype  -> dtype (int8 legality lint)
      forced un-elided boundary         -> elision (layout-contract proof)
      bogus Layout (inflated phys_c)    -> traffic (HBM byte audit)
    ... and *only* that pass fires, so a red verifier report names the
    defect rather than burying it in cascading noise;
  - the promoted jaxpr boundary walker descends into pjit and cond call
    params (the old test-local walker silently skipped tuple-valued
    sub-jaxprs);
  - the facade gate: ``ExecutionOptions(validate=...)`` is validated, and
    ``CompiledModel.verify_report()`` returns a clean report for a planned
    model.

Everything here is trace-only (``jax.make_jaxpr``): no kernel runs, no
device execution, so the whole file stays fast enough for tier-1.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (
    PlanVerificationError,
    boundary_ops,
    verify_network,
)
from repro.core.conv_spec import ConvAlgorithm
from repro.core.netplan import (
    Layout,
    plan_network,
    prepare_net_params,
    resolve_algorithm,
)
from repro.core.planner import Planner
from repro.models.cnn import init_cnn

# Reduced geometries matching the CLI smoke runs: the layer-boundary and
# block math the verifier proves is resolution-free.
CASES = {
    "vgg16": dict(hw=(64, 64)),
    "yolov3-tiny": dict(hw=(128, 128)),
}


def _layers(model):
    from repro.configs import vgg16, yolov3

    return {"vgg16": vgg16.LAYERS, "yolov3-tiny": yolov3.TINY_LAYERS}[model]


def _plan(model, dtype="float32", batch=1):
    h, w = CASES[model]["hw"]
    planner = Planner(impl="pallas", cache_path=None)
    return plan_network(
        _layers(model), h, w, planner, in_channels=3, batch=batch,
        dtype=dtype,
    )


def _verify(netplan, params=None):
    layers = tuple(s.layer for s in netplan.steps)
    if params is None:
        params = init_cnn(jax.random.PRNGKey(0), layers)
    prepared = prepare_net_params(netplan, params, pretransform=True)
    return verify_network(netplan, prepared)


def _with_mutated_plan(netplan, idx, **plan_changes):
    """Rebuild the netplan with one step's ConvPlan corrupted.

    Rebuilding (rather than patching the step in place) keeps the stored
    layouts self-consistent with the mutated plan, so the *only* defect the
    verifier can find is the one the mutation injects."""
    from repro.core.netplan import build_network_plan

    plans = [
        dataclasses.replace(s.plan, **plan_changes)
        if s.index == idx and s.plan is not None else s.plan
        for s in netplan.steps
    ]
    return build_network_plan(
        [s.layer for s in netplan.steps], *netplan.input_hw,
        in_channels=netplan.in_channels, batch=netplan.batch,
        plans=plans, impl=netplan.impl, dtype=netplan.dtype_name,
    )


def _replace_step(netplan, idx, **changes):
    steps = list(netplan.steps)
    steps[idx] = dataclasses.replace(steps[idx], **changes)
    return dataclasses.replace(netplan, steps=tuple(steps))


def _only_pass(report, pass_name):
    """The report is red, and every finding belongs to ``pass_name``."""
    assert not report.ok
    assert report.by_pass(pass_name), report.findings
    others = [f for f in report.findings if f.pass_name != pass_name]
    assert not others, others


def _algo(step):
    return resolve_algorithm(step.spec, step.plan, *step.in_hw)


# ---------------------------------------------------------------------------
# Clean plans verify with zero findings


@pytest.mark.parametrize("model", list(CASES))
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_clean_plan_zero_findings(model, dtype):
    """Acceptance: full-level verification of a cleanly planned network is
    green — the five byte passes plus the four kernel-interior passes all
    run, no findings, per-kernel metrics present."""
    report = _verify(_plan(model, dtype=dtype))
    assert report.ok and not report.findings, report.findings
    assert set(report.passes_run) == {
        "structure", "vmem", "traffic", "elision", "dtype",
        "race", "bounds", "accum", "overflow",
    }
    assert report.kernels
    for row in report.kernels:
        assert row["vmem_bytes"] <= row["vmem_budget"]


@pytest.mark.parametrize("model", list(CASES))
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_clean_plan_kernel_level_zero_findings(model, dtype):
    """The kernel rung alone (structure + race/bounds/accum/overflow) also
    certifies the zoo clean, and its metric rows carry the interior facts
    (recovered reduction axes, Mosaic schedule, corner count; the int8
    accumulator bound on q8 kernels)."""
    layers = tuple(_layers(model))
    netplan = _plan(model, dtype=dtype)
    params = init_cnn(jax.random.PRNGKey(0), layers)
    prepared = prepare_net_params(netplan, params, pretransform=True)
    report = verify_network(netplan, prepared, level="kernel")
    assert report.ok and not report.findings, report.findings
    assert set(report.passes_run) == {
        "structure", "race", "bounds", "accum", "overflow"
    }
    for row in report.kernels:
        assert "reduction_axes" in row and "bounds_points_checked" in row
        assert row["dimension_semantics"] is not None
    if dtype == "int8":
        q8 = [r for r in report.kernels if "_q8" in r["kernel"]]
        assert q8 and all(
            0 < r["acc_bound"] <= 2**31 - 1 and r["acc_headroom"] >= 1.0
            for r in q8
        )


def test_folded_stem_descriptor_names_the_gemm_call():
    """ResNet-50's 7x7/2 stem folds its taps: its expected-call descriptor
    is the blocked GEMM over the (B*OH*OW, 147) patch matrix, K in one
    block — name, grid, VMEM and traffic — and the traced forward verifies
    against it clean."""
    from repro.analysis.descriptors import step_descriptors
    from repro.configs import resnet50

    planner = Planner(impl="pallas", cache_path=None)
    netplan = plan_network(resnet50.LAYERS[:2], 64, 64, planner, batch=2)
    stem = netplan.steps[0]
    assert stem.plan.taps_folded and stem.in_layout.phys_c == 3
    bm, bn, bk = stem.plan.kernel_blocks
    (desc,) = step_descriptors(netplan, stem)
    assert desc["family"] == "gemm"
    assert desc["name"] == "_matmul_bias_kernel_6loop"
    assert bk == 147
    assert desc["grid"] == (-(-2 * 32 * 32 // bm), 128 // bn, 1)
    assert desc["k_elems"] == 147
    report = _verify(netplan)
    assert report.ok and not report.findings, report.findings
    assert [tuple(r["grid"]) for r in report.kernels] == [desc["grid"]]


def test_plan_level_zero_findings():
    """Plan-level (no trace) verification is also green, and cheap enough
    that it never needs prepared parameters."""
    report = verify_network(_plan("vgg16"), level="plan")
    assert report.ok and not report.findings
    assert set(report.passes_run) == {"vmem", "elision"}


# ---------------------------------------------------------------------------
# Mutation coverage: each pass flags exactly its defect


@pytest.mark.parametrize("model", list(CASES))
def test_oversized_block_flags_vmem_only(model):
    """An im2col output block inflated to 2048 lanes pushes the weight slab
    past the 16 MiB budget; the vmem pass (and only it) goes red."""
    netplan = _plan(model)
    idx = max(
        s.index for s in netplan.steps
        if s.layer.kind == "conv" and s.plan is not None
        and _algo(s) is ConvAlgorithm.IM2COL_GEMM
    )
    toh, bc, _ = netplan.steps[idx].plan.kernel_blocks
    report = _verify(
        _with_mutated_plan(netplan, idx, kernel_blocks=(toh, bc, 2048))
    )
    _only_pass(report, "vmem")
    assert any(
        f.step == idx and "budget" in f.message
        for f in report.by_pass("vmem")
    )


@pytest.mark.parametrize("model", list(CASES))
def test_wrong_dtype_flags_dtype_only(model):
    """Flipping a quantized step's declared dtype to fp32 *after* the
    parameters were prepared leaves an int8 kernel running under an
    fp32-claiming plan — the dtype pass pins it to the step; the byte-level
    passes stay quiet rather than cascading itemsize noise."""
    netplan = _plan(model, dtype="int8")
    idx = min(
        s.index for s in netplan.steps
        if s.layer.kind == "conv" and s.plan is not None
        and s.plan.dtype == "int8"
    )
    layers = tuple(s.layer for s in netplan.steps)
    params = init_cnn(jax.random.PRNGKey(0), layers)
    prepared = prepare_net_params(netplan, params, pretransform=True)
    step = netplan.steps[idx]
    bad = dataclasses.replace(step.plan, dtype="float32")
    mutated = _replace_step(netplan, idx, plan=bad)
    report = verify_network(mutated, prepared)
    _only_pass(report, "dtype")
    assert any(f.step == idx for f in report.by_pass("dtype"))


@pytest.mark.parametrize("model", list(CASES))
def test_forced_unelided_boundary_flags_elision_only(model):
    """Forcing a trivial out_layout where the layout rules elide the
    boundary is a planning-contract violation: the executor faithfully runs
    the cropped boundary (so structure/vmem/traffic/dtype stay green), but
    the elision decision check goes red against the re-derived reference."""
    netplan = _plan(model)
    idx = min(
        s.index for s in netplan.steps
        if s.layer.kind == "conv" and s.plan is not None
        and s.out_layout.pad_c > 0
    )
    oc = netplan.steps[idx].spec.out_channels
    report = _verify(_replace_step(netplan, idx, out_layout=Layout(oc)))
    _only_pass(report, "elision")
    assert any(f.step == idx for f in report.by_pass("elision"))


@pytest.mark.parametrize("model", list(CASES))
def test_bogus_layout_flags_traffic_only(model):
    """Doubling a boundary's physical channel count (producer out_layout +
    consumer in_layout, so the plan stays self-consistent and executable)
    moves real HBM bytes the reference layouts never asked for — the
    traffic audit flags it; footprints and decisions are unchanged."""
    netplan = _plan(model)
    pairs = []
    convs = [
        s for s in netplan.steps
        if s.layer.kind == "conv" and s.plan is not None
    ]
    for s, t in zip(convs, convs[1:]):
        if s.out_layout.pad_c > 0 and t.in_layout.phys_c == s.out_layout.phys_c:
            pairs.append((s.index, t.index))
    src, dst = pairs[0]
    oc = netplan.steps[src].spec.out_channels
    phys = netplan.steps[src].out_layout.phys_c
    fat = Layout(oc, 2 * phys - oc)         # doubled, still block-divisible
    mutated = _replace_step(netplan, src, out_layout=fat)
    mutated = _replace_step(
        mutated, dst,
        in_layout=Layout(netplan.steps[dst].in_layout.c,
                         fat.phys_c - netplan.steps[dst].in_layout.c),
    )
    report = _verify(mutated)
    _only_pass(report, "traffic")
    assert any(f.step in (src, dst) for f in report.by_pass("traffic"))


# ---------------------------------------------------------------------------
# Kernel-interior mutation coverage: each injected kernel defect is caught
# by exactly one of the four interior passes (race / bounds / accum /
# overflow), so a red report names the defect class.


def _interior_report(pairs):
    from repro.analysis.passes import (
        accum_pass,
        bounds_pass,
        overflow_pass,
        race_pass,
    )
    from repro.analysis.report import VerifyReport

    report = VerifyReport(
        level="kernel", passes_run=("race", "bounds", "accum", "overflow")
    )
    race_pass(report, pairs)
    bounds_pass(report, pairs)
    accum_pass(report, pairs)
    overflow_pass(report, pairs)
    return report


def _records(fn, *args):
    from repro.analysis import pallas_calls

    recs = pallas_calls(jax.make_jaxpr(fn)(*args))
    assert recs, "no pallas_call recovered from the trace"
    return recs


def test_noninjective_index_map_flags_race_only():
    """Two grid programs mapped to the same output block: (i, j) -> (i+j,)
    collides at (0,1)/(1,0).  The race pass produces the concrete witness;
    bounds stays green (the map's range fits the operand), accum/overflow
    have nothing to say (no scratch, no q8)."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(2, 2),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i + j, j))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j: (i + j, 0)),
            out_shape=jax.ShapeDtypeStruct((24, 128), jnp.float32),
            interpret=True,
        )(x)

    (rec,) = _records(fn, jnp.ones((24, 256), jnp.float32))
    report = _interior_report([(rec, {"step": 0, "reduction_axes": ()})])
    _only_pass(report, "race")
    assert any("not injective" in f.message for f in report.by_pass("race"))


def test_oob_block_window_flags_bounds_only():
    """An index map shifted by one block ((i, j) -> (i+1, j)) drives the
    last grid row's window past the operand extent.  Bounds flags it with
    the offending corner; the shifted map is still injective, so race stays
    green."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(2, 2),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j: (i + 1, j)),
            out_shape=jax.ShapeDtypeStruct((16, 256), jnp.float32),
            interpret=True,
        )(x)

    (rec,) = _records(fn, jnp.ones((16, 256), jnp.float32))
    report = _interior_report([(rec, {"step": 0, "reduction_axes": ()})])
    _only_pass(report, "bounds")
    f = report.by_pass("bounds")[0]
    assert "escapes" in f.message and f.actual > f.expected


def test_partial_output_edge_block_is_in_bounds():
    """An output whose extent is not a block multiple: the last grid row's
    block starts inside the operand and overhangs its end, which Pallas
    writes back clipped (the Winograd kernel tiled in VMEM writes its
    (B, OH, OW, O) output so).  Bounds stays green; the same overhang on
    the input is still flagged."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=(2, 2),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((13, 256), jnp.float32),
            interpret=True,
        )(x)

    (rec,) = _records(fn, jnp.ones((16, 256), jnp.float32))
    report = _interior_report([(rec, {"step": 0, "reduction_axes": ()})])
    assert not report.by_pass("bounds"), report.findings

    (rec,) = _records(fn, jnp.ones((13, 256), jnp.float32))
    report = _interior_report([(rec, {"step": 0, "reduction_axes": ()})])
    (f,) = report.by_pass("bounds")
    assert "input operand 0" in f.message and f.actual == 16


def test_flipped_init_guard_flags_accum_only():
    """An accumulator initialized under the *last*-step guard instead of the
    first: every earlier reduction step reads stale VMEM.  The accum pass
    pins the flipped predicate; the flush guard is still correct, so the
    race pass (which owns the flush obligation) stays green."""
    from jax.experimental import pallas as pl

    def kernel(a_ref, b_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _init():                                    # wrong step!
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += a_ref[...] @ b_ref[...]

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _flush():
            o_ref[...] = acc_ref[...]

    def fn(a, b):
        from jax.experimental.pallas import tpu as pltpu

        return pl.pallas_call(
            kernel,
            grid=(1, 1, 2),
            in_specs=[
                pl.BlockSpec((8, 128), lambda i, j, k: (i, k)),
                pl.BlockSpec((128, 128), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((8, 128), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            interpret=True,
        )(a, b)

    (rec,) = _records(
        fn, jnp.ones((8, 256), jnp.float32), jnp.ones((256, 128), jnp.float32)
    )
    report = _interior_report([(rec, {"step": 0, "reduction_axes": (2,)})])
    _only_pass(report, "accum")
    assert any(
        "initializing write is guarded on step 1" in f.message
        for f in report.by_pass("accum")
    )


def test_overflow_shape_flags_overflow_only():
    """A q8 GEMM deep enough that K*127^2 exceeds int32: the real kernel
    (structurally sound — race/bounds/accum all green) is rejected purely
    by the interval certificate.  K = 133248 > floor((2^31-1)/127^2)."""
    from repro.kernels.gemm.ops import gemm_call_descriptor, matmul_padded_call

    kp = 133248                                     # 1041 K-blocks of 128
    block = (8, 128, 128)

    def fn(a, b, scale):
        return matmul_padded_call(
            a, b, block, variant="6loop", interpret=True, scale_p=scale,
        )

    (rec,) = _records(
        fn,
        jnp.ones((8, kp), jnp.int8),
        jnp.ones((kp, 128), jnp.int8),
        jnp.ones((1, 128), jnp.float32),
    )
    desc = gemm_call_descriptor(8, 128, kp, block, dtype_bytes=1, scale=True)
    desc["step"] = 0
    report = _interior_report([(rec, desc)])
    _only_pass(report, "overflow")
    f = report.by_pass("overflow")[0]
    assert f.actual == kp * 127 * 127 and f.actual > f.expected


def test_declared_k_drift_flags_overflow():
    """The descriptor's declared reduction depth must match the traced
    operand shapes — plan/trace drift is an overflow-pass error even when
    both depths are individually safe."""
    from repro.kernels.gemm.ops import gemm_call_descriptor, matmul_padded_call

    def fn(a, b, scale):
        return matmul_padded_call(
            a, b, (8, 128, 128), variant="6loop", interpret=True,
            scale_p=scale,
        )

    (rec,) = _records(
        fn,
        jnp.ones((8, 256), jnp.int8),
        jnp.ones((256, 128), jnp.int8),
        jnp.ones((1, 128), jnp.float32),
    )
    desc = gemm_call_descriptor(8, 128, 512, (8, 128, 128), dtype_bytes=1,
                                scale=True)        # lies: traced K is 256
    desc["step"] = 0
    report = _interior_report([(rec, desc)])
    _only_pass(report, "overflow")
    assert report.by_pass("overflow")[0].expected == 512


def test_multi_image_winograd_block_kernel_level_clean():
    """A Winograd step whose block holds several whole images (bb > 1, as at
    ResNet-50's 7x7 layers at batch 64): ResNet-50's first four layers at
    48x48, batch 4, put two 12x12 images (2 x 2 tiles, padded to 2 x 8)
    in each block of layer 3.  The kernel rung certifies it clean, and the
    traced grid is the descriptor's."""
    from repro.analysis.descriptors import step_descriptors
    from repro.configs import resnet50
    from repro.core.netplan import winograd_step_tiling

    layers = resnet50.LAYERS[:4]
    planner = Planner(impl="pallas", cache_path=None)
    netplan = plan_network(layers, 48, 48, planner, batch=4)
    step = netplan.steps[3]
    assert _algo(step) is ConvAlgorithm.WINOGRAD
    assert winograd_step_tiling(netplan, step).nhwc == (2, 2, 8)
    (desc,) = step_descriptors(netplan, step)
    assert desc["grid"][0] == 2                 # 4 images, 2 per block
    params = init_cnn(jax.random.PRNGKey(0), layers)
    prepared = prepare_net_params(netplan, params, pretransform=True)
    report = verify_network(netplan, prepared, level="kernel")
    assert report.ok and not report.findings, report.findings
    assert tuple(desc["grid"]) in [tuple(r["grid"]) for r in report.kernels]


# ---------------------------------------------------------------------------
# Boundary walker recursion (the promoted tests/test_netplan.py walker)


def test_boundary_walker_descends_into_pjit():
    @jax.jit
    def inner(x):
        return jnp.pad(x, ((0, 1), (0, 0)))

    def fn(x):
        return inner(x) * 2.0

    assert "pad" in boundary_ops(fn, jnp.ones((4, 4)))


def test_boundary_walker_descends_into_cond_branches():
    """cond branches arrive as a *tuple* of ClosedJaxprs in eqn params —
    exactly the shape the old test-local walker silently skipped."""

    def fn(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda v: jnp.pad(v, ((0, 1), (0, 0))),
            lambda v: jnp.concatenate([v, v[:1]]),
            x,
        )

    ops = boundary_ops(fn, jnp.ones((4, 4)))
    assert "pad" in ops


def test_channel_census_descends_switch_branches():
    """Regression (PR-7 gap): the channel-boundary census skipped cond_p
    sub-jaxprs because their invars omit the branch selector, so a pad on
    the tainted activation *inside* a ``lax.switch`` branch — exactly how
    PR-9 pipeline stage bodies appear in the traced jaxpr — was invisible
    to full-level verification."""
    from repro.analysis import channel_boundary_ops

    def fn(idx, x):
        return jax.lax.switch(
            idx,
            [
                lambda v: jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, 8))),
                lambda v: jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, 8))) * 2.0,
            ],
            x,
        )

    jaxpr = jax.make_jaxpr(fn)(0, jnp.ones((1, 4, 4, 8)))
    ops = channel_boundary_ops(jaxpr, taint_invar=-1)
    assert ops and all(op.kind == "pad" for op in ops), ops


def test_verify_pipeline_kernel_level():
    """verify_pipeline's kernel rung traces every stage slice at microbatch
    size and runs the interior passes over each stage's pallas_calls —
    requiring prepared params, and covering all plan steps exactly once."""
    from repro.analysis import verify_pipeline
    from repro.core.netplan import NetworkExecutor, plan_pipeline

    netplan = _plan("vgg16", batch=4)
    planner = Planner(impl="pallas", cache_path=None)
    pipeplan = plan_pipeline(
        _layers("vgg16"), *CASES["vgg16"]["hw"], planner, 2,
        in_channels=3, batch=4, netplan=netplan,
    )
    with pytest.raises(ValueError, match="parameter"):
        verify_pipeline(netplan, pipeplan, level="kernel")
    ex = NetworkExecutor(netplan, init_cnn(
        jax.random.PRNGKey(0), tuple(_layers("vgg16"))
    ), interpret=True, pretransform=True)
    report = verify_pipeline(
        netplan, pipeplan, name="vgg16", params=ex.params,
        pretransformed=ex.pretransformed, level="kernel",
    )
    assert report.ok and not report.findings, report.findings
    assert set(report.passes_run) == {
        "pipeline", "structure", "race", "bounds", "accum", "overflow"
    }
    planned = {
        s.index for s in netplan.steps
        if s.layer.kind == "conv" and s.plan is not None
    }
    assert {row["step"] for row in report.kernels} == planned


# ---------------------------------------------------------------------------
# Facade wiring


def test_execution_options_validate_is_checked():
    from repro.api import ExecutionOptions

    with pytest.raises(ValueError):
        ExecutionOptions(validate="bogus")
    assert ExecutionOptions(validate="plan").validate == "plan"


def test_facade_verify_report_clean():
    """repro.compile(...).verify_report() is green for a planned model and
    the validate='full' executor gate admits it."""
    import repro
    from repro.api import ExecutionOptions
    from repro.api.model import as_model
    from repro.models.cnn import CNNLayer

    model = as_model(
        (
            CNNLayer("conv", out_channels=32, kernel=3),
            CNNLayer("conv", out_channels=32, kernel=3),
        ),
        input_hw=(32, 32),
        name="chain2",
    )
    params = model.init_params(jax.random.PRNGKey(0))
    opts = ExecutionOptions(
        impl="pallas", mode="cost", interpret=True, cache_path=None,
        validate="full",
    )
    compiled = repro.compile(model, params, opts)
    report = compiled.verify_report()
    assert report.ok and not report.findings, report.findings
    assert report.level == "full"
    # the gate itself: executor construction under validate='full' passes
    assert compiled.executor(1) is not None


def test_plan_verification_error_carries_report():
    report = verify_network(_plan("vgg16"), level="plan")
    err = PlanVerificationError(report)
    assert err.report is report
