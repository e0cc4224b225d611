"""The verifier's analysis passes.

Each pass appends ``Finding``s to a shared ``VerifyReport``; all quantities
come from two independent derivations of the same compiled artifact — the
*expected* side from the NetworkPlan via the kernel wrappers' descriptor
functions, the *actual* side from the traced jaxpr (``analysis.trace``) —
so a disagreement is a real contract violation, never a tautology.

Tolerance policy (documented in docs/architecture.md): VMEM model drift is
gated at ``max(32 KiB, 2%)`` — the slack covers sub-block constants the
cost model deliberately ignores (epilogue row double-buffering) while still
catching any real block-sizing error, which moves footprints by whole block multiples (hundreds of KiB).  The VMEM
*budget* check is exact: one byte over is an error.  Traffic is gated at
``max(4 KiB, 2%)``; the ideal-reuse ratio (actual / cost-model bytes on
logical shapes) is reported as a metric but never gated, because physical
channel padding legitimately inflates it (a 3-channel stem planned at a
128-lane block reads 42x the logical bytes — that is the plan, not a bug).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from repro.analysis.report import Finding, VerifyReport
from repro.analysis.trace import PallasCallRecord, channel_boundary_ops

VMEM_TOL_ABS = 32 * 1024
VMEM_TOL_REL = 0.02
TRAFFIC_TOL_ABS = 4 * 1024
TRAFFIC_TOL_REL = 0.02


def structure_pass(
    report: VerifyReport,
    records: List[PallasCallRecord],
    descs: List[Dict[str, Any]],
) -> List[Tuple[PallasCallRecord, Dict[str, Any]]]:
    """Match traced pallas_calls to the plan's expected kernels, in order.

    Returns the (record, descriptor) pairs the per-kernel passes run over;
    a count or name mismatch is itself a finding (the plan and the compiled
    artifact disagree about *which* kernels run, so byte-level comparisons
    on the mismatched tail would be noise).
    """
    if len(records) != len(descs):
        report.add(Finding(
            pass_name="structure", severity="error",
            message=(
                "compiled network emits a different pallas_call count than "
                "the plan expects"
            ),
            expected=len(descs), actual=len(records),
        ))
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]] = []
    for rec, desc in zip(records, descs):
        # A pure q8-marker mismatch is a *dtype* defect (the plan's declared
        # precision disagrees with the compiled kernel), not a structural
        # one — keep the pair so dtype_pass can pin it precisely.
        if rec.name.replace("_q8", "") != desc["name"].replace("_q8", ""):
            report.add(Finding(
                pass_name="structure", severity="error",
                message=(
                    f"kernel body mismatch: plan expects {desc['name']!r}, "
                    f"trace found {rec.name!r}"
                ),
                step=desc.get("step"), kernel=rec.name,
            ))
            continue
        pairs.append((rec, desc))
    return pairs


def dtype_consistent_pairs(
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
) -> List[Tuple[PallasCallRecord, Dict[str, Any]]]:
    """Pairs whose compiled precision matches the plan's declared precision.

    The byte-level passes (VMEM, traffic) only run over these: when a step's
    declared dtype is wrong, every itemsize-derived expected quantity is
    wrong with it, and reporting those mismatches would bury the one real
    finding (the dtype pass's) in arithmetic noise.
    """
    return [
        (rec, desc) for rec, desc in pairs
        if ("_q8" in rec.name) == ("_q8" in desc["name"])
    ]


def vmem_pass(
    report: VerifyReport,
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
    budget: int,
) -> None:
    """Prove every kernel's true footprint fits the budget and tracks the
    cost model's prediction."""
    for rec, desc in pairs:
        actual = rec.vmem_bytes()
        if actual > budget:
            report.add(Finding(
                pass_name="vmem", severity="error",
                message="kernel footprint exceeds the planner's VMEM budget",
                step=desc.get("step"), kernel=rec.name,
                expected=budget, actual=actual,
            ))
        model = desc["model_vmem_bytes"]
        tol = max(VMEM_TOL_ABS, VMEM_TOL_REL * model)
        drift = (
            actual - model if desc.get("vmem_one_sided")
            else abs(actual - model)
        )
        if drift > tol:
            report.add(Finding(
                pass_name="vmem", severity="error",
                message=(
                    "kernel footprint drifted from the "
                    "vmem_model prediction beyond tolerance"
                ),
                step=desc.get("step"), kernel=rec.name,
                expected=model, actual=actual,
            ))


def traffic_pass(
    report: VerifyReport,
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
) -> None:
    """Cross-check each kernel's grid x block HBM bytes against the plan.

    The expected side is recomputed from *reference* layouts
    (``descriptors.reference_netplan``), so corrupt stored ``Layout``s that
    inflate physical channels surface here as byte mismatches.
    """
    for rec, desc in pairs:
        actual = rec.traffic_bytes()
        expected = desc.get("ref_traffic_bytes")
        if expected is None:
            continue
        tol = max(TRAFFIC_TOL_ABS, TRAFFIC_TOL_REL * expected)
        if abs(actual - expected) > tol:
            report.add(Finding(
                pass_name="traffic", severity="error",
                message=(
                    "kernel HBM traffic disagrees with the plan's "
                    "block/grid accounting"
                ),
                step=desc.get("step"), kernel=rec.name,
                expected=expected, actual=actual,
            ))


def kernel_metrics(
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]], budget: int
) -> List[Dict[str, Any]]:
    """Always-recorded per-kernel rows (findings or not)."""
    rows = []
    for rec, desc in pairs:
        traffic = rec.traffic_bytes()
        ideal = desc.get("ideal_traffic_bytes")
        rows.append({
            "step": desc.get("step"),
            "kernel": rec.name,
            "grid": list(rec.grid),
            "vmem_bytes": rec.vmem_bytes(),
            "vmem_model_bytes": desc["model_vmem_bytes"],
            "vmem_budget": budget,
            "traffic_bytes": traffic,
            "traffic_expected_bytes": desc.get("ref_traffic_bytes"),
            "traffic_ideal_bytes": ideal,
            "reuse_ratio": (round(traffic / ideal, 3) if ideal else None),
        })
    return rows


def elision_pass(
    report: VerifyReport,
    netplan,
    reference,
    closed_jaxpr: Optional[Any],
) -> None:
    """Prove the PR-4 layout-elision contract.

    Two halves: (a) every stored boundary *decision* (keep channels padded
    vs crop to logical) matches what ``build_network_plan`` derives from the
    same per-layer plans — a forced un-elided boundary is a planning-level
    violation even though the executor faithfully runs it; (b) the traced
    jaxpr's census of channel-axis pads/crops on activation-derived tensors
    equals ``netplan.expected_channel_ops`` — extra ops are executor drift,
    missing ops are movement the plan promised but the code can't emit.
    """
    from repro.core.netplan import expected_channel_ops

    for s, r in zip(netplan.steps, reference.steps):
        if s.layer.kind != "conv":
            continue
        stored, ref = not s.out_layout.trivial, not r.out_layout.trivial
        if stored != ref:
            report.add(Finding(
                pass_name="elision", severity="error",
                message=(
                    "boundary planned un-elided but the layout rules elide it"
                    if ref else
                    "boundary planned elided but the layout rules forbid it"
                ),
                step=s.index,
                expected=int(ref), actual=int(stored),
            ))
    if closed_jaxpr is None:
        return
    actual_ops = channel_boundary_ops(closed_jaxpr)
    expected_ops = expected_channel_ops(netplan)
    for kind in ("pad", "crop"):
        na = sum(1 for o in actual_ops if o.kind == kind)
        ne = sum(1 for o in expected_ops if o["kind"] == kind)
        if na != ne:
            report.add(Finding(
                pass_name="elision", severity="error",
                message=(
                    f"channel-axis {kind} count in the traced forward "
                    "disagrees with the plan's boundary accounting"
                ),
                expected=ne, actual=na,
            ))


def _kernel_eqns(jaxpr):
    from repro.analysis.trace import iter_eqns

    return iter_eqns(jaxpr, into_pallas=True)


# ---------------------------------------------------------------------------
# Kernel-interior passes (the ``kernel`` rung): race, bounds, accum, overflow.
# The facts come from analysis.grid — affine index-map recovery, guard
# resolution from the kernel jaxpr's pl.when conds, exact rational rank.

#: Symmetric int8 quantization magnitude (core/quant.py clips both
#: activations and weights to [-127, 127]).
Q8_MAX = 127
INT32_MAX = 2**31 - 1


def _output_flush_ok(accesses, ref: int, axis: int, last: int) -> bool:
    """Is every write to output ``ref`` guarded on ``pid(axis) == last``?"""
    writes = [a for a in accesses if a.ref == ref and a.kind == "write"]
    return bool(writes) and all(
        any(
            g.axis == axis and g.step == last and not g.negated
            for g in a.guards
        )
        for a in writes
    )


def race_pass(
    report: VerifyReport,
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
) -> None:
    """Write-disjointness: no two grid programs write the same output block.

    Two obligations per output operand: (a) the index map restricted to the
    grid axes it *does* use is injective (exact rational-rank certificate,
    with a concrete two-program collision witness on failure); (b) every
    grid axis *absent* from the map is a genuine reduction axis — declared
    sequential ('arbitrary') to Mosaic, backed by an accumulator scratch,
    and flushed to the output only under the recovered last-step
    ``pl.program_id`` guard.  The planned reduction axes from the kernel
    descriptor must agree with what the trace shows.
    """
    from repro.analysis import grid as G

    for rec, desc in pairs:
        n_in = len(rec.inputs)
        accesses = G.ref_accesses(rec)
        declared = desc.get("reduction_axes")
        for oi, op in enumerate(rec.outputs):
            red = G.reduction_axes(rec, op)
            if declared is not None and set(red) - set(declared):
                extra = sorted(set(red) - set(declared))
                report.add(Finding(
                    pass_name="race", severity="error",
                    message=(
                        f"grid axes {extra} are absent from the output index "
                        "map but the plan does not declare them reduction "
                        "axes"
                    ),
                    step=desc.get("step"), kernel=rec.name,
                ))
            amap = G.affine_index_map(op.index_map_jaxpr, rec.grid)
            if amap is None:
                if op.index_map_jaxpr is not None:
                    report.add(Finding(
                        pass_name="race", severity="warning",
                        message=(
                            "output index map is not affine; injectivity "
                            "unproved"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))
            else:
                status, witness = G.injectivity_witness(
                    amap, rec.grid, op.dep_axes
                )
                if status == "collision":
                    p, q = witness
                    report.add(Finding(
                        pass_name="race", severity="error",
                        message=(
                            "output index map is not injective: grid "
                            f"programs {p} and {q} write the same output "
                            "block"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))
                elif status == "unknown":
                    report.add(Finding(
                        pass_name="race", severity="warning",
                        message=(
                            "output index map rank-deficient but no "
                            "collision witness found in the search window"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))
            for r in red:
                sem = rec.dimension_semantics
                if sem is not None and sem[r] != "arbitrary":
                    report.add(Finding(
                        pass_name="race", severity="error",
                        message=(
                            f"reduction axis {r} is declared "
                            f"{sem[r]!r} to Mosaic; a parallelized "
                            "reduction races on the shared output block"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))
                if not rec.scratch:
                    report.add(Finding(
                        pass_name="race", severity="error",
                        message=(
                            f"grid axis {r} is absent from the output index "
                            "map but the kernel has no accumulator scratch"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))
                    continue
                if not _output_flush_ok(
                    accesses, n_in + oi, r, rec.grid[r] - 1
                ):
                    report.add(Finding(
                        pass_name="race", severity="error",
                        message=(
                            "output is written outside the last-step guard "
                            f"of reduction axis {r}; intermediate partial "
                            "sums would reach HBM"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))


def bounds_pass(
    report: VerifyReport,
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
) -> None:
    """Every ``index_map x block_shape`` window stays inside the (padded)
    operand bounds at all grid corners — affine maps make the corner check
    exact (see analysis.grid)."""
    from repro.analysis import grid as G

    for rec, desc in pairs:
        for kind, ops in (("input", rec.inputs), ("output", rec.outputs)):
            for pos, op in enumerate(ops):
                if op.index_map_jaxpr is None:
                    continue
                violations, proved = G.window_violations(op, rec.grid)
                if violations:
                    v = violations[0]
                    report.add(Finding(
                        pass_name="bounds", severity="error",
                        message=(
                            f"{kind} operand {pos} block window escapes the "
                            f"operand bounds: at grid point {v.point}, dim "
                            f"{v.dim} covers [{v.start}, {v.stop}) of "
                            f"extent {v.extent} "
                            f"({len(violations)} offending grid point(s))"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                        expected=v.extent, actual=v.stop,
                    ))
                elif not proved:
                    report.add(Finding(
                        pass_name="bounds", severity="warning",
                        message=(
                            f"{kind} operand {pos} index map is not affine "
                            "and the grid is too large to enumerate; "
                            "bounds unproved"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))


def accum_pass(
    report: VerifyReport,
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
) -> None:
    """Accumulator hazards: scratch must be initialized on the first
    reduction step before any read, and reduction axes must be innermost.

    The initializing write's guard is recovered from the kernel body's
    ``pl.program_id`` predicate — a flipped guard (init on the *last* step)
    means every earlier reduction step reads garbage from the previous
    output block's accumulation.  Reduction axes must trail every
    multi-step parallel axis: Pallas revisits an output block consecutively
    only when the axes its index map ignores iterate innermost.
    """
    from repro.analysis import grid as G

    for rec, desc in pairs:
        red = sorted({
            a for op in rec.outputs for a in G.reduction_axes(rec, op)
        })
        for r in red:
            after = [
                a for a in range(r + 1, len(rec.grid))
                if rec.grid[a] > 1 and a not in red
            ]
            if after:
                report.add(Finding(
                    pass_name="accum", severity="error",
                    message=(
                        f"reduction axis {r} is not innermost: parallel "
                        f"axes {after} iterate inside it, so the scratch "
                        "accumulator is clobbered between partial sums"
                    ),
                    step=desc.get("step"), kernel=rec.name,
                ))
        if not rec.scratch:
            continue
        accesses = G.ref_accesses(rec)
        base = len(rec.inputs) + len(rec.outputs)
        for si in range(len(rec.scratch)):
            acc = [a for a in accesses if a.ref == base + si]
            if not acc:
                continue
            first = acc[0]
            if first.kind == "read":
                report.add(Finding(
                    pass_name="accum", severity="error",
                    message=(
                        f"scratch {si} is read before any initializing "
                        "write"
                    ),
                    step=desc.get("step"), kernel=rec.name,
                ))
                continue
            bad = [
                g for g in first.guards
                if (g.step != 0 and not g.negated)
                or (g.step == 0 and g.negated)
            ]
            if bad:
                g = bad[0]
                report.add(Finding(
                    pass_name="accum", severity="error",
                    message=(
                        f"scratch {si} initializing write is guarded on "
                        f"step {g.step} of grid axis {g.axis}"
                        f"{' (negated)' if g.negated else ''}; reads on "
                        "the first reduction step see stale data"
                    ),
                    step=desc.get("step"), kernel=rec.name,
                ))
            elif first.opaque:
                report.add(Finding(
                    pass_name="accum", severity="warning",
                    message=(
                        f"scratch {si} initializing write sits under a "
                        "predicate the analyzer could not resolve"
                    ),
                    step=desc.get("step"), kernel=rec.name,
                ))


def _traced_k_elems(rec: PallasCallRecord, desc: Dict[str, Any]):
    """Reduction depth K from the traced operand shapes, per family."""
    family = desc.get("family")
    if family == "gemm" and rec.inputs:
        return rec.inputs[0].array_shape[1]          # A is (Mp, Kp)
    if family == "im2col" and len(rec.inputs) >= 2:
        kh, kw, cp = rec.inputs[1].array_shape[:3]   # w is (kh, kw, Cp, Op)
        return kh * kw * cp
    return None


def overflow_pass(
    report: VerifyReport,
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
) -> None:
    """int8 overflow certification by interval arithmetic.

    A q8 kernel accumulates ``K`` products of values in [-127, 127] into
    int32, so ``|acc| <= K * 127^2``; the pass proves that bound stays
    under ``2^31 - 1`` for the *traced* reduction depth (kh*kw*Cin at the
    padded channel count — padding lanes are zero, so the physical K is the
    worst case and also the sound one).  The descriptor's declared
    ``k_elems`` must match the traced shapes, pinning plan/trace drift.
    The fused dequant epilogue is fp32-safe a fortiori: the certified
    int32 bound times any representable calibration scale is far below
    fp32 max.
    """
    for rec, desc in pairs:
        if "_q8" not in rec.name:
            continue
        k = _traced_k_elems(rec, desc)
        declared = desc.get("k_elems")
        if declared is not None and k is not None and int(declared) != int(k):
            report.add(Finding(
                pass_name="overflow", severity="error",
                message=(
                    "plan-declared reduction depth disagrees with the "
                    "traced operand shapes"
                ),
                step=desc.get("step"), kernel=rec.name,
                expected=int(declared), actual=int(k),
            ))
        k = k if k is not None else declared
        if k is None:
            report.add(Finding(
                pass_name="overflow", severity="warning",
                message=(
                    "reduction depth unrecoverable from plan or trace; "
                    "int32 accumulator bound unproved"
                ),
                step=desc.get("step"), kernel=rec.name,
            ))
            continue
        bound = int(k) * Q8_MAX * Q8_MAX
        if bound > INT32_MAX:
            report.add(Finding(
                pass_name="overflow", severity="error",
                message=(
                    f"int32 accumulator can overflow: K*127^2 = {bound} "
                    f"exceeds {INT32_MAX} at reduction depth K={k}"
                ),
                step=desc.get("step"), kernel=rec.name,
                expected=INT32_MAX, actual=bound,
            ))


def interior_metrics(
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
) -> List[Dict[str, Any]]:
    """Per-kernel rows of the kernel-interior facts (always recorded)."""
    from repro.analysis import grid as G

    rows = []
    for rec, desc in pairs:
        red = sorted({
            a for op in rec.outputs for a in G.reduction_axes(rec, op)
        })
        row: Dict[str, Any] = {
            "reduction_axes": red,
            "dimension_semantics": (
                list(rec.dimension_semantics)
                if rec.dimension_semantics is not None else None
            ),
            "bounds_points_checked": len(G.grid_corners(rec.grid)),
        }
        if "_q8" in rec.name:
            k = _traced_k_elems(rec, desc) or desc.get("k_elems")
            if k is not None:
                bound = int(k) * Q8_MAX * Q8_MAX
                row["acc_bound"] = bound
                row["acc_headroom"] = round(INT32_MAX / bound, 3)
        rows.append(row)
    return rows


def dtype_pass(
    report: VerifyReport,
    pairs: List[Tuple[PallasCallRecord, Dict[str, Any]]],
    netplan,
    closed_jaxpr: Optional[Any] = None,
) -> None:
    """int8 accumulation legality + upcast lint.

    For every kernel on an int8-planned step: the q8 kernel body must be
    selected, operands must arrive int8, every ``dot_general`` must consume
    int8 and produce int32 (the MXU accumulate path — an fp32 product would
    silently re-quantize), scratch accumulators must be int32, and the
    epilogue must emit fp32.  fp32 steps must not pick up q8 kernels or int8
    avals.  Network-wide, no float64 aval may appear anywhere (a stray
    Python float in an epilogue upcasts the whole layer silently).
    """
    steps = {s.index: s for s in netplan.steps}
    for rec, desc in pairs:
        step = steps.get(desc.get("step"))
        quantized = (
            step is not None and step.plan is not None
            and step.plan.dtype == "int8"
        )
        name_q8 = "_q8" in rec.name
        if quantized != name_q8:
            report.add(Finding(
                pass_name="dtype", severity="error",
                message=(
                    "int8-planned step compiled to a non-q8 kernel"
                    if quantized else
                    "fp32-planned step compiled to a q8 kernel"
                ),
                step=desc.get("step"), kernel=rec.name,
            ))
            continue
        in_dtypes = [op.dtype for op in rec.inputs]
        if quantized:
            if sum(1 for d in in_dtypes if d == "int8") < 2:
                report.add(Finding(
                    pass_name="dtype", severity="error",
                    message="int8 kernel does not consume int8 operands",
                    step=desc.get("step"), kernel=rec.name,
                ))
            for s in rec.scratch:
                if s.dtype != "int32":
                    report.add(Finding(
                        pass_name="dtype", severity="error",
                        message=(
                            "int8 kernel accumulator scratch is "
                            f"{s.dtype}, not int32"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))
        else:
            if any(d == "int8" for d in in_dtypes):
                report.add(Finding(
                    pass_name="dtype", severity="error",
                    message="fp32 kernel consumes int8 operands",
                    step=desc.get("step"), kernel=rec.name,
                ))
        for op in rec.outputs:
            if op.dtype != "float32":
                report.add(Finding(
                    pass_name="dtype", severity="error",
                    message=f"kernel epilogue emits {op.dtype}, not float32",
                    step=desc.get("step"), kernel=rec.name,
                ))
        for eqn in _kernel_eqns(rec.kernel_jaxpr):
            if eqn.primitive.name != "dot_general":
                continue
            lhs, rhs = (str(v.aval.dtype) for v in eqn.invars[:2])
            out = str(eqn.outvars[0].aval.dtype)
            if quantized:
                if (lhs, rhs) != ("int8", "int8") or out != "int32":
                    report.add(Finding(
                        pass_name="dtype", severity="error",
                        message=(
                            f"int8 kernel dot_general is {lhs}x{rhs}->{out}, "
                            "must be int8xint8->int32"
                        ),
                        step=desc.get("step"), kernel=rec.name,
                    ))
            elif out == "float64":
                report.add(Finding(
                    pass_name="dtype", severity="error",
                    message="dot_general accumulates in float64",
                    step=desc.get("step"), kernel=rec.name,
                ))
    if closed_jaxpr is not None:
        for eqn in _kernel_eqns(closed_jaxpr.jaxpr):
            for v in eqn.outvars:
                if str(getattr(v.aval, "dtype", "")) == "float64":
                    report.add(Finding(
                        pass_name="dtype", severity="error",
                        message=(
                            f"float64 value produced by {eqn.primitive.name} "
                            "in the compiled network"
                        ),
                    ))
                    return
