"""Expected-side kernel descriptors for a NetworkPlan.

For every planned pallas conv step this module predicts — without tracing
anything — exactly which pallas_call(s) the executor will emit: kernel body
name, grid, modeled VMEM footprint and modeled HBM traffic.  The math lives
next to each kernel family's wrapper (``gemm_call_descriptor`` /
``im2col_call_descriptor`` / ``winograd_call_descriptors``); this module
owns only the dispatch that mirrors ``kernels/conv_ops._conv2d_pallas_laidout``
(same algorithm routing, same block fallbacks, same physical channel
counts), so descriptor drift against the wrappers is a one-file diff.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from repro.core.conv_spec import ConvAlgorithm
from repro.core.netplan import (
    NetworkPlan,
    NetStep,
    resolve_algorithm,
    step_folded_k,
)
from repro.core.vmem_model import (
    GemmShape,
    im2col_gemm_traffic_bytes,
    itemsize,
    predict_gemm,
    winograd_traffic_bytes,
)
from repro.hw import V5E
from repro.util import ceil_to


def planned_pallas(step: NetStep) -> bool:
    """Does this step execute as pallas kernels under the network plan?"""
    return (
        step.layer.kind == "conv"
        and step.plan is not None
        and step.plan.impl == "pallas"
    )


def step_descriptors(
    netplan: NetworkPlan, step: NetStep, batch: Optional[int] = None
) -> List[Dict[str, Any]]:
    """The pallas_call descriptor list one conv step emits (program order).

    Empty for non-conv and non-pallas steps (fc layers run as plain XLA
    dots).  One descriptor for direct/im2col/Winograd; an im2col step that
    folds its taps names the GEMM call it runs on the (B*OH*OW, folded K)
    patch matrix.
    """
    if not planned_pallas(step):
        return []
    b = netplan.batch if batch is None else batch
    plan, spec = step.plan, step.spec
    algo = resolve_algorithm(spec, plan, *step.in_hw)
    # Per-step precision: under an int8 *network* request a layer the
    # quantization policy kept fp32 still runs fp32 kernels.
    quantized = plan.dtype == "int8"
    d = itemsize(plan.dtype)
    h, w = step.in_hw
    oh, ow = spec.out_hw(h, w)
    cp = step.in_layout.phys_c          # activation channels entering
    o_phys = step.out_layout.phys_c     # offline weight padding target
    blocks = plan.kernel_blocks

    if algo is ConvAlgorithm.DIRECT or plan.taps_folded:
        from repro.kernels.gemm.ops import gemm_call_descriptor

        bm, bn, bk = blocks
        m = b * oh * ow
        k = step_folded_k(step) if plan.taps_folded else ceil_to(cp, bk)
        desc = gemm_call_descriptor(
            ceil_to(m, bm), ceil_to(o_phys, bn), k, blocks,
            dtype_bytes=d, bias=True, scale=quantized,
        )
        return [dict(desc, step=step.index)]

    if algo is ConvAlgorithm.WINOGRAD:
        from repro.kernels.winograd.ops import winograd_call_descriptors

        descs = winograd_call_descriptors(
            b, oh, ow, cp, ceil_to(o_phys, blocks[2]), blocks,
            bias=True, vmem_budget=netplan.vmem_budget,
        )
        return [dict(x, step=step.index) for x in descs]

    from repro.kernels.im2col_gemm.ops import im2col_call_descriptor

    toh, bc, bo = blocks
    desc = im2col_call_descriptor(
        h, w, spec, blocks, cp, ceil_to(o_phys, bo), batch=b,
        dtype_bytes=d, bias=True, scale=quantized,
    )
    return [dict(desc, step=step.index)]


def ideal_traffic_bytes(netplan: NetworkPlan, step: NetStep) -> Optional[int]:
    """The cost model's *ideal-reuse* HBM bytes for one conv step.

    This is the quantity the planner prices layers with
    (``im2col_gemm_traffic_bytes`` / ``winograd_traffic_bytes`` / the
    direct-GEMM traffic term) on *logical* shapes.  The verifier reports
    actual/ideal as a per-kernel reuse-ratio metric but does not gate on it:
    block-padded physical channels (a 3-channel stem planned at a 128-wide
    block) legitimately inflate the ratio by an order of magnitude.
    """
    if not planned_pallas(step):
        return None
    plan, spec = step.plan, step.spec
    algo = resolve_algorithm(spec, plan, *step.in_hw)
    d = itemsize(plan.dtype)
    oh, ow = spec.out_hw(*step.in_hw)
    if algo is ConvAlgorithm.DIRECT:
        shape = GemmShape(
            netplan.batch * oh * ow, spec.out_channels,
            spec.in_channels * spec.kh * spec.kw,
        )
        est = predict_gemm(shape, plan.block, dtype_bytes=d)
        return int(round(est.memory_s * V5E.hbm_bandwidth))
    if algo is ConvAlgorithm.WINOGRAD:
        return winograd_traffic_bytes(
            oh, ow, spec.in_channels, spec.out_channels,
            batch=netplan.batch, dtype_bytes=d,
        )
    return im2col_gemm_traffic_bytes(
        oh, ow, spec.in_channels, spec.out_channels, spec.kh, spec.kw,
        batch=netplan.batch, dtype_bytes=d,
    )


def reference_netplan(netplan: NetworkPlan) -> NetworkPlan:
    """Rebuild the layout decisions from the stored per-layer plans.

    ``build_network_plan`` is deterministic given (layers, shapes, plans),
    so this reconstructs what the layouts *should* be — the expected side of
    the elision-decision check and of the traffic audit.  A NetworkPlan
    whose stored ``Layout``s were corrupted (inflated physical channels, a
    forced un-elided boundary) diverges from this reference even though its
    stored plans are untouched.
    """
    from repro.core.netplan import build_network_plan

    return build_network_plan(
        [s.layer for s in netplan.steps],
        *netplan.input_hw,
        in_channels=netplan.in_channels,
        batch=netplan.batch,
        plans=[s.plan for s in netplan.steps],
        impl=netplan.impl,
        dtype=netplan.dtype_name,
        vmem_budget=netplan.vmem_budget,
    )


def network_descriptors(
    netplan: NetworkPlan, reference: Optional[NetworkPlan] = None
) -> List[Dict[str, Any]]:
    """Flat, program-ordered descriptor list for the whole network.

    Names/grids/VMEM come from the *stored* plan (those are per-kernel
    facts); each descriptor additionally carries ``ref_traffic_bytes``
    computed from the reference layouts, the traffic audit's expected side.
    """
    reference = reference or reference_netplan(netplan)
    out: List[Dict[str, Any]] = []
    for step, ref_step in zip(netplan.steps, reference.steps):
        stored = step_descriptors(netplan, step)
        ref = step_descriptors(reference, ref_step)
        ideal = ideal_traffic_bytes(netplan, step)
        for i, desc in enumerate(stored):
            desc = dict(desc)
            desc["ref_traffic_bytes"] = (
                ref[i]["traffic_bytes"] if i < len(ref) else None
            )
            desc["ideal_traffic_bytes"] = ideal
            out.append(desc)
    return out
