"""Network-level inference planning and execution.

The paper's co-design argument is end-to-end: the 5x win comes from tuning
the kernels *and* the memory system across the whole layer set, not one conv
at a time — and the follow-up RISC-V study makes the same point that
per-layer-optimal choices are not network-optimal.  Executing layer-by-layer
through ``core.conv2d`` leaves pure HBM elementwise traffic between
consecutive convs: every layer crops its block-padded kernel output back to
logical channels and the next layer immediately re-pads it to *its* block
multiple.  This module plans the network once and makes those boundaries a
planner decision:

  Layout        the physical channel layout an NHWC activation carries
                relative to its logical shape (trailing zero channels from
                block alignment).  Trailing *row* padding is never carried:
                the kernels' tail rows hold act(bias), not zeros, so the
                network plan instead snaps each im2col row tile ``toh`` to a
                divisor of OH — the row-block pad/crop pair vanishes
                identically instead of being elided.
  NetworkPlan   the whole network resolved ahead of time: per-layer
                ConvPlans (reusing the planner's persistent cache, keyed by
                batch), network-adjusted kernel blocks, and the inter-layer
                layout decisions — which crop+re-pad pairs are elided so the
                padded activation flows straight into the next pallas_call,
                with a single channel crop at network exit.
  NetworkExecutor  runs a NetworkPlan: offline parameter preparation
                (batchnorm folding, block padding, Winograd weight
                pre-transform), a jitted whole-network forward, and
                data-parallel batch execution via shard_map over a device
                mesh on the batch axis (single-device fallback).

Elision is legal exactly when the padded region stays zero and divisible:
the producer's weight/bias pads make its extra output channels
act(0 + 0) = 0 (relu/leaky/linear all fix 0), maxpool/upsample preserve
zero channels (a padded max pool too: its -inf pad never fills a whole
window, so every window's max over an all-zero channel is 0), and the
consumer's zero weight pads ignore them — so a
producer's physical channel count that divides the consumer's channel block
can flow through unchanged.  Any consumer that needs logical channels
(route concat, shortcut add, fc, avgpool, or a layer referenced by one)
forces a crop back to logical.

Whole-network decisions persist as a "networks" entry in the planner's v4
cache (keyed by a layer-table digest + batch/chip/dtype/impl/policy), so a
warm process rebuilds the NetworkPlan with zero re-tunes.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.core.conv_spec import (
    ConvAlgorithm,
    ConvSpec,
    Epilogue,
    apply_activation,
    max_pool,
    max_pool_out_hw,
    select_algorithm,
)
from repro.core.planner import ConvPlan, Planner
from repro.util import ceil_to, dot_precision


# ---------------------------------------------------------------------------
# Layout


@dataclasses.dataclass(frozen=True)
class Layout:
    """Physical channel layout of an NHWC activation.

    ``c`` logical channels plus ``pad_c`` trailing zero channels (block
    alignment).  The invariant every producer maintains — and every consumer
    may rely on — is that the ``pad_c`` tail is exactly zero.
    """

    c: int
    pad_c: int = 0

    @property
    def phys_c(self) -> int:
        return self.c + self.pad_c

    @property
    def trivial(self) -> bool:
        return self.pad_c == 0

    def to_json(self) -> List[int]:
        return [self.c, self.pad_c]

    @classmethod
    def from_json(cls, d: Sequence[int]) -> Layout:
        return cls(int(d[0]), int(d[1]))


# ---------------------------------------------------------------------------
# NetworkPlan


@dataclasses.dataclass(frozen=True)
class NetStep:
    """One planned layer: its spec/plan plus the layouts it consumes and
    produces.  ``in_layout``/``out_layout`` are only non-trivial for planned
    pallas convs (and the pools between them, which pass layouts through)."""

    index: int
    layer: Any                      # CNNLayer (duck-typed: .kind, ...)
    spec: Optional[ConvSpec]
    plan: Optional[ConvPlan]
    in_hw: Tuple[int, int]
    out_hw: Tuple[int, int]
    in_layout: Layout
    out_layout: Layout


@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """A whole network resolved for one (input shape, batch, impl, dtype)."""

    steps: Tuple[NetStep, ...]
    input_hw: Tuple[int, int]
    in_channels: int
    batch: int
    impl: str
    dtype_name: str
    # The planner's VMEM budget (None: the chip's), for the blocks a kernel
    # sizes at run time: the fused Winograd kernel's NHWC windows.
    vmem_budget: Optional[int] = dataclasses.field(default=None,
                                                   compare=False)

    @property
    def layers(self) -> Tuple[Any, ...]:
        return tuple(s.layer for s in self.steps)

    @property
    def elided_boundaries(self) -> int:
        """Conv boundaries whose crop+re-pad pair was elided (padded
        channels flow straight into the next layer)."""
        return sum(
            1 for s in self.steps
            if s.layer.kind == "conv" and not s.out_layout.trivial
        )

    @property
    def exit_layout(self) -> Layout:
        return self.steps[-1].out_layout if self.steps else Layout(0)


# ---------------------------------------------------------------------------
# Algorithm / block helpers


def _conv_spec(layer, in_ch: int) -> ConvSpec:
    pad = layer.pad if layer.pad is not None else layer.kernel // 2
    return ConvSpec(
        in_channels=in_ch,
        out_channels=layer.out_channels,
        kernel_size=(layer.kernel, layer.kernel),
        stride=(layer.stride, layer.stride),
        padding=(pad, pad),
    )


def resolve_algorithm(
    spec: ConvSpec, plan: Optional[ConvPlan], h: int, w: int
) -> ConvAlgorithm:
    """The algorithm ``conv2d`` would route this layer to (same priority)."""
    if plan is not None:
        return plan.algorithm
    if spec.algorithm is ConvAlgorithm.AUTO_COST:
        from repro.core.codesign import select_algorithm_by_cost

        return select_algorithm_by_cost(spec, h, w)
    return select_algorithm(spec)


def _in_channel_multiple(plan: ConvPlan, algo: ConvAlgorithm) -> int:
    """The input-channel block the layer's Pallas kernel reduces over; 1
    for tap folding, which builds its patch matrix from logical channels."""
    if plan.taps_folded:
        return 1
    if algo is ConvAlgorithm.DIRECT:
        return plan.kernel_blocks[2]        # (bm, bn, bk) -> bk
    return plan.kernel_blocks[1]            # (toh|bt, bc, bo) -> bc


def _out_channel_multiple(plan: ConvPlan, algo: ConvAlgorithm) -> int:
    """The out-channel block the layer's kernel emits in multiples of."""
    if algo is ConvAlgorithm.DIRECT or plan.taps_folded:
        return plan.kernel_blocks[1]        # bn
    return plan.kernel_blocks[2]            # bo


def _snap_row_tile(plan: ConvPlan, algo: ConvAlgorithm, oh: int) -> ConvPlan:
    """Network-level adjustment: make the im2col row tile divide OH.

    The kernel's row-tiled grid emits ceil(OH/toh)*toh rows; rows past OH
    hold act(bias), so they cannot flow to the next layer and the wrapper
    must crop them.  Snapping toh to the largest divisor of OH no bigger
    than the autotuned tile makes the row-block pad/crop pair vanish
    identically — a decision only visible at network scope.  The crop it
    saves is one cheap elementwise op, so the snap is only taken when the
    divisor keeps at least half the tuned tile: a prime OH (best divisor 1)
    must not explode the grid into one program per output row — the
    executor's im2col path crops the row tail exactly like the wrapper.
    """
    if algo is not ConvAlgorithm.IM2COL_GEMM or plan.taps_folded:
        return plan
    toh, bc, bo = plan.kernel_blocks
    snapped = min(toh, oh)
    while oh % snapped:
        snapped -= 1
    if snapped < min(toh, oh) / 2 or (snapped, bc, bo) == plan.kernel_blocks:
        return plan
    return dataclasses.replace(plan, kernel_blocks=(snapped, bc, bo))


# ---------------------------------------------------------------------------
# Building the plan


def _propagate_shapes(
    layers: Tuple[Any, ...], h: int, w: int, in_channels: int
) -> List[Dict[str, Any]]:
    """Per-layer {'spec', 'in': (h,w,c), 'out': (h,w,c)} — the single shape
    walk shared by planning and layout resolution (mirrors
    models/cnn.cnn_forward)."""
    infos: List[Dict[str, Any]] = []
    shapes: List[Tuple[int, int, int]] = []
    cur_c, cur_h, cur_w = in_channels, h, w
    for i, l in enumerate(layers):
        in_shape = (cur_h, cur_w, cur_c)
        spec = None
        if l.kind == "conv":
            spec = _conv_spec(l, cur_c)
            cur_h, cur_w = spec.out_hw(cur_h, cur_w)
            cur_c = l.out_channels
        elif l.kind == "maxpool":
            cur_h, cur_w = max_pool_out_hw(l, cur_h, cur_w)
        elif l.kind == "upsample":
            cur_h, cur_w = cur_h * l.size, cur_w * l.size
        elif l.kind == "route":
            cur_c = sum(shapes[j][2] for j in l.from_layers)
            cur_h, cur_w = shapes[l.from_layers[0]][:2]
        elif l.kind == "avgpool":
            cur_h, cur_w = 1, 1
        elif l.kind == "fc":
            cur_h, cur_w = 1, 1
            cur_c = l.out_channels
        shapes.append((cur_h, cur_w, cur_c))
        infos.append({"spec": spec, "in": in_shape, "out": shapes[i]})
    return infos


def build_network_plan(
    layers: Sequence[Any],
    h: int,
    w: int,
    in_channels: int = 3,
    batch: int = 1,
    plans: Optional[Sequence[Optional[ConvPlan]]] = None,
    impl: str = "jax",
    dtype: Any = "float32",
    snap_rows: bool = True,
    vmem_budget: Optional[int] = None,
) -> NetworkPlan:
    """Pure layout resolution: layer table + per-layer plans -> NetworkPlan.

    No planner and no tuning — ``plan_network`` wraps this with plan
    resolution and the persistent network cache entry.  Deterministic given
    (layers, shapes, plans), so it can also run at trace time (cnn_infer).
    """
    layers = tuple(layers)
    n = len(layers)
    plans = tuple(plans) if plans is not None else (None,) * n
    assert len(plans) == n, (len(plans), n)
    referenced = {j for l in layers for j in getattr(l, "from_layers", ())}
    infos = _propagate_shapes(layers, h, w, in_channels)

    def next_conv(i: int):
        """Follow ``cur`` from layer i through layout-transparent layers.

        Returns ('conv', j) when the next consumer is conv j and no
        intermediate output is referenced by a route/shortcut (padded
        tensors must not land in the saved-outputs list of a logical
        consumer); ('exit',) when the padded activation runs straight off
        the network's end (single crop at exit); ('stop',) otherwise.
        """
        j = i + 1
        while j < n:
            kind = layers[j].kind
            if kind == "conv":
                if any(x in referenced for x in range(i, j)):
                    return ("stop",)
                return ("conv", j)
            if kind in ("maxpool", "upsample"):
                j += 1
                continue
            return ("stop",)
        if any(x in referenced for x in range(i, n)):
            return ("stop",)
        return ("exit",)

    # Pass 2: layout decisions along the ``cur`` chain.
    steps: List[NetStep] = []
    carry = Layout(in_channels)             # layout of `cur` entering layer i
    for i, l in enumerate(layers):
        info = infos[i]
        ih, iw, ic = info["in"]
        oh_, ow_, oc = info["out"]
        plan = plans[i]
        if l.kind == "conv":
            spec = info["spec"]
            algo = resolve_algorithm(spec, plan, ih, iw)
            eff_impl = plan.impl if plan is not None else impl
            planned_pallas = plan is not None and eff_impl == "pallas"
            if planned_pallas and snap_rows:
                plan = _snap_row_tile(plan, algo, oh_)
            if planned_pallas:
                in_mult = _in_channel_multiple(plan, algo)
                if (carry.pad_c and carry.phys_c % in_mult == 0
                        and not plan.taps_folded):
                    in_layout = carry       # producer elided into us
                else:
                    in_layout = Layout(ic, ceil_to(ic, in_mult) - ic)
                out_phys = ceil_to(oc, _out_channel_multiple(plan, algo))
                nxt = next_conv(i)
                elide = nxt[0] == "exit"
                if nxt[0] == "conv":
                    j = nxt[1]
                    pj = plans[j]
                    specj = infos[j]["spec"]
                    if pj is not None and pj.impl == "pallas":
                        algoj = resolve_algorithm(
                            specj, pj, *infos[j]["in"][:2]
                        )
                        # A folded consumer takes logical channels: the
                        # crop happens here, at the producer.
                        elide = (not pj.taps_folded and out_phys
                                 % _in_channel_multiple(pj, algoj) == 0)
                out_layout = (
                    Layout(oc, out_phys - oc) if elide else Layout(oc)
                )
            else:
                if not carry.trivial:       # pragma: no cover - by invariant
                    raise AssertionError(
                        "padded activation reached an unplanned conv"
                    )
                in_layout = Layout(ic)
                out_layout = Layout(oc)
            carry = out_layout
        elif l.kind in ("maxpool", "upsample"):
            # Channel-preserving: zero pad channels stay zero (max over an
            # all-zero channel window is 0; repeat copies zeros).
            in_layout = carry
            out_layout = carry
        else:
            if not carry.trivial:           # pragma: no cover - by invariant
                raise AssertionError(
                    f"padded activation reached logical consumer {l.kind!r}"
                )
            in_layout = Layout(ic)
            out_layout = Layout(oc)
            carry = out_layout
        steps.append(
            NetStep(
                index=i,
                layer=l,
                spec=info["spec"],
                plan=plan,
                in_hw=(ih, iw),
                out_hw=(oh_, ow_),
                in_layout=in_layout,
                out_layout=out_layout,
            )
        )
    dtype_name = getattr(dtype, "__name__", None) or getattr(
        dtype, "name", None
    ) or str(dtype)
    return NetworkPlan(
        steps=tuple(steps),
        input_hw=(h, w),
        in_channels=in_channels,
        batch=batch,
        impl=impl,
        dtype_name=dtype_name,
        vmem_budget=vmem_budget,
    )


# ---------------------------------------------------------------------------
# Planner-backed entry point with the persistent network cache


def network_key(
    layers: Sequence[Any],
    h: int,
    w: int,
    in_channels: int,
    batch: int,
    planner: Planner,
    dtype: Any = "float32",
) -> str:
    """Cache key for a whole-network entry: a digest of the layer table plus
    every planner field that changes per-layer decisions (chip, dtype, impl,
    mode, VMEM budget, policies) and the batch — batch-keyed plans."""
    digest = hashlib.sha1(repr(tuple(layers)).encode()).hexdigest()[:16]
    dtype_name = getattr(dtype, "__name__", None) or getattr(
        dtype, "name", None
    ) or str(dtype)
    return "|".join(
        [
            "net", digest, f"h{h}w{w}", f"ci{in_channels}", f"b{batch}",
            planner.hw.name, dtype_name, planner.impl, planner.mode,
            f"e{int(planner.fuse_epilogue)}",
            f"v{planner.vmem_budget}",
        ]
    )


def plan_network(
    layers: Sequence[Any],
    h: int,
    w: int,
    planner: Planner,
    in_channels: int = 3,
    batch: int = 1,
    dtype: Any = "float32",
) -> NetworkPlan:
    """Resolve a NetworkPlan through a Planner, warm-cached at network scope.

    Cold: resolves every conv's ConvPlan (per-layer cache or tune), builds
    the layout decisions, and stores the whole record as a v4 "networks"
    cache entry.  Warm: reconstructs the NetworkPlan straight from the
    entry — zero per-layer lookups, zero tunes, the layout decisions exactly
    as first planned.
    """
    layers = tuple(layers)
    key = network_key(layers, h, w, in_channels, batch, planner, dtype)
    entry = planner.network_entry(key)
    if entry is not None:
        try:
            netplan = _netplan_from_entry(layers, entry)
        except (KeyError, ValueError, TypeError, IndexError):
            pass                            # corrupt entry -> replan
        else:
            planner.network_hits += 1       # counted only once validated
            return dataclasses.replace(netplan,
                                       vmem_budget=planner.vmem_budget)
    plans: List[Optional[ConvPlan]] = [
        (planner.plan(info["spec"], info["in"][0], info["in"][1],
                      batch=batch, dtype=dtype)
         if l.kind == "conv" else None)
        for l, info in zip(layers, _propagate_shapes(layers, h, w,
                                                     in_channels))
    ]
    netplan = build_network_plan(
        layers, h, w, in_channels=in_channels, batch=batch, plans=plans,
        impl=planner.impl, dtype=dtype, vmem_budget=planner.vmem_budget,
    )
    planner.put_network_entry(key, _entry_from_netplan(netplan))
    return netplan


def _entry_from_netplan(netplan: NetworkPlan) -> Dict[str, Any]:
    return {
        "input_hw": list(netplan.input_hw),
        "in_channels": netplan.in_channels,
        "batch": netplan.batch,
        "impl": netplan.impl,
        "dtype": netplan.dtype_name,
        "steps": [
            {
                "plan": s.plan.to_json() if s.plan is not None else None,
                "in_hw": list(s.in_hw),
                "out_hw": list(s.out_hw),
                "in_layout": s.in_layout.to_json(),
                "out_layout": s.out_layout.to_json(),
            }
            for s in netplan.steps
        ],
    }


def _netplan_from_entry(
    layers: Tuple[Any, ...], entry: Dict[str, Any]
) -> NetworkPlan:
    recs = entry["steps"]
    if len(recs) != len(layers):
        raise ValueError("network entry does not match the layer table")
    steps = []
    for i, (l, r) in enumerate(zip(layers, recs)):
        spec = None
        if l.kind == "conv":
            in_c = Layout.from_json(r["in_layout"]).c
            spec = _conv_spec(l, in_c)
        steps.append(
            NetStep(
                index=i,
                layer=l,
                spec=spec,
                plan=(ConvPlan.from_json(r["plan"])
                      if r["plan"] is not None else None),
                in_hw=tuple(r["in_hw"]),
                out_hw=tuple(r["out_hw"]),
                in_layout=Layout.from_json(r["in_layout"]),
                out_layout=Layout.from_json(r["out_layout"]),
            )
        )
    return NetworkPlan(
        steps=tuple(steps),
        input_hw=tuple(entry["input_hw"]),
        in_channels=entry["in_channels"],
        batch=entry["batch"],
        impl=entry["impl"],
        dtype_name=entry["dtype"],
    )


# ---------------------------------------------------------------------------
# Pipeline partitioning (layer-pipelined multi-chip execution)
#
# The multi-chip analogue of the paper's per-layer co-design: the network
# partition is *planned* from the same per-layer cost model that picked each
# layer's algorithm and blocks (predict_conv_time totals per stage), not
# guessed from layer counts.  A stage is a contiguous ``steps[start:stop]``
# slice; cuts are restricted to boundaries where the PR-4 layout-elision
# contract closes (trivial out_layout — padded channels never cross a chip
# boundary; the crop/re-pad pair materializes at the stage edge via the
# existing exit-crop/_align_channels machinery) and where no route/shortcut
# ``from_layers`` reference would reach back into an earlier stage.

#: Modeled per-tick schedule overhead (dispatch + ppermute launch), the term
#: that keeps the auto-``n_micro`` chooser from degenerating to "as many
#: microbatches as possible": more microbatches shrink the bubble but pay
#: this fixed cost every tick.  Sized well below a typical stage's modeled
#: seconds (~1e-5 for the paper's networks) so it breaks ties rather than
#: dominating the decision.
TICK_OVERHEAD_S = 2e-6


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """A NetworkPlan split into contiguous, cost-balanced pipeline stages.

    ``stage_bounds[s] = (start, stop)`` — stage s runs ``steps[start:stop]``.
    ``stage_seconds[s]`` is the planner-predicted seconds for the stage at
    the plan's full batch (sum of its steps' ``predicted_s``).  ``n_micro``
    is the microbatch count the auto-chooser resolved (the executor may
    override it).
    """

    stage_bounds: Tuple[Tuple[int, int], ...]
    stage_seconds: Tuple[float, ...]
    n_micro: int

    @property
    def n_stages(self) -> int:
        return len(self.stage_bounds)

    def bubble_fraction(self, n_micro: Optional[int] = None) -> float:
        """GPipe fill/drain bubble: (S-1)/(m+S-1) of the schedule's ticks
        run fewer than S active stages."""
        m = self.n_micro if n_micro is None else n_micro
        s = self.n_stages
        return (s - 1) / (m + s - 1)

    def modeled_latency_s(self, n_micro: Optional[int] = None) -> float:
        """Modeled end-to-end seconds for one full batch through the
        pipeline: bubble + per-tick max-stage time (see
        ``modeled_pipeline_latency``)."""
        m = self.n_micro if n_micro is None else n_micro
        return modeled_pipeline_latency(self.stage_seconds, m)

    def to_json(self) -> Dict[str, Any]:
        return {
            "stage_bounds": [list(b) for b in self.stage_bounds],
            "stage_seconds": list(self.stage_seconds),
            "n_micro": self.n_micro,
        }

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> PipelinePlan:
        return cls(
            stage_bounds=tuple(
                (int(b[0]), int(b[1])) for b in d["stage_bounds"]
            ),
            stage_seconds=tuple(float(t) for t in d["stage_seconds"]),
            n_micro=int(d["n_micro"]),
        )


def step_seconds(netplan: NetworkPlan) -> Tuple[float, ...]:
    """Per-step planner-predicted seconds (0.0 for unplanned/free layers —
    pools, routes, fc: their cost is noise next to the convs the cost model
    prices, exactly as in plan_report)."""
    return tuple(
        s.plan.predicted_s if s.plan is not None else 0.0
        for s in netplan.steps
    )


def legal_cut_points(netplan: NetworkPlan) -> List[int]:
    """Boundary indices b where the network may be cut into stages
    (cut between ``steps[b-1]`` and ``steps[b]``).

    A cut at b is legal iff (1) ``steps[b-1].out_layout`` is trivial — the
    boundary activation is logically laid out, so no elision chain spans the
    chip edge and the PR-4 padded-channel contract holds entirely within a
    stage; and (2) no layer j >= b references a layer r < b via
    ``from_layers`` (route concat / shortcut add need the producer's output
    resident on the same stage).
    """
    from repro.models.cnn import layer_ref_spans

    n = len(netplan.steps)
    spans = layer_ref_spans([s.layer for s in netplan.steps])
    legal = []
    for b in range(1, n):
        if not netplan.steps[b - 1].out_layout.trivial:
            continue
        if any(r < b <= j for r, j in spans):
            continue
        legal.append(b)
    return legal


def _bounds_seconds(
    per_step: Sequence[float], bounds: Sequence[Tuple[int, int]]
) -> Tuple[float, ...]:
    return tuple(
        float(sum(per_step[a:z])) for a, z in bounds
    )


#: Exact-search budget: partition candidates up to this count are scored
#: directly on the modeled latency; past it the min-max DP approximation
#: takes over.  comb(20, 3) = 1140 for VGG-16 at 4 stages — the paper's
#: networks never leave the exact regime.
_EXACT_SEARCH_LIMIT = 200_000


def partition_network(
    netplan: NetworkPlan, n_stages: int, n_micro: Optional[int] = None
) -> PipelinePlan:
    """Cost-balanced contiguous partition into ``n_stages`` stages.

    Minimizes ``modeled_pipeline_latency`` — the tick-synchronous schedule
    model over the planner's own ``predict_conv_time`` totals — over the
    legal cut set.  At CNN depth the legal cut combinations number in the
    thousands, so the search is exact (each candidate scored at its own
    best microbatch count); a pathologically deep network falls back to
    the classic min-max linear-partition DP, which optimizes the
    steady-state term only.  Raises ValueError when fewer than
    ``n_stages - 1`` legal cuts exist (e.g. an elision chain covering the
    whole net).

    ``n_micro=None`` runs the auto-chooser over divisors of the plan's
    batch (``choose_n_micro``); a fixed ``n_micro`` scores candidates at
    that count.
    """
    import itertools
    import math

    n = len(netplan.steps)
    if not 1 <= n_stages <= n:
        raise ValueError(f"n_stages={n_stages} for a {n}-step network")
    per_step = step_seconds(netplan)
    cuts = legal_cut_points(netplan)
    if len(cuts) < n_stages - 1:
        raise ValueError(
            f"only {len(cuts)} legal cut points for n_stages={n_stages} "
            f"(elision chains / route spans forbid the rest)"
        )

    def finish(bounds: Tuple[Tuple[int, int], ...]) -> PipelinePlan:
        seconds = _bounds_seconds(per_step, bounds)
        m = (choose_n_micro(seconds, netplan.batch) if n_micro is None
             else n_micro)
        return PipelinePlan(
            stage_bounds=bounds, stage_seconds=seconds, n_micro=m
        )

    n_comb = math.comb(len(cuts), n_stages - 1)
    if n_comb <= _EXACT_SEARCH_LIMIT:
        best_plan: Optional[PipelinePlan] = None
        best_key: Tuple[float, float] = (float("inf"), float("inf"))
        for combo in itertools.combinations(cuts, n_stages - 1):
            edges = (0,) + combo + (n,)
            plan = finish(tuple(zip(edges[:-1], edges[1:])))
            # Tie-break on the steady-state max stage: at n_micro=1 the
            # tick sum is partition-independent (one active stage per
            # tick), and the balanced profile is what a larger batch or a
            # microbatch override will want.
            key = (plan.modeled_latency_s(), max(plan.stage_seconds))
            if key < best_key:
                best_plan, best_key = plan, key
        assert best_plan is not None
        return best_plan

    # DP fallback: minimize the max stage (the steady-state tick) over
    # boundary candidates.  best[(k, e)] = (max stage seconds, prev end).
    prefix = [0.0]
    for t in per_step:
        prefix.append(prefix[-1] + t)

    def seg(a: int, z: int) -> float:
        return prefix[z] - prefix[a]

    ends = cuts + [n]
    best: Dict[Tuple[int, int], Tuple[float, int]] = {(0, 0): (0.0, -1)}
    for k in range(1, n_stages + 1):
        allowed = ends if k < n_stages else [n]
        for e in allowed:
            cand: Optional[Tuple[float, int]] = None
            for (pk, pe), (pmax, _) in best.items():
                if pk != k - 1 or pe >= e:
                    continue
                m = max(pmax, seg(pe, e))
                if cand is None or m < cand[0]:
                    cand = (m, pe)
            if cand is not None:
                best[(k, e)] = cand
    if (n_stages, n) not in best:
        raise ValueError(
            f"no legal {n_stages}-stage partition (cut set {cuts})"
        )
    bounds_rev = []
    e = n
    for k in range(n_stages, 0, -1):
        _, pe = best[(k, e)]
        bounds_rev.append((pe, e))
        e = pe
    return finish(tuple(reversed(bounds_rev)))


def equal_count_partition(
    netplan: NetworkPlan, n_stages: int, n_micro: Optional[int] = None
) -> PipelinePlan:
    """The naive strawman: equal *layer-count* stages, costs ignored.

    Each cut targets ``round(s * n / n_stages)`` and snaps to the nearest
    legal cut point (so the partition is executable — a hand-rolled
    splitter still cannot cut through an elision chain or a route span),
    but per-layer costs are never consulted.  This is the baseline the
    cost-balanced partition must beat on modeled latency.
    """
    n = len(netplan.steps)
    if not 1 <= n_stages <= n:
        raise ValueError(f"n_stages={n_stages} for a {n}-step network")
    legal = legal_cut_points(netplan)
    if len(legal) < n_stages - 1:
        raise ValueError(
            f"only {len(legal)} legal cut points for n_stages={n_stages}"
        )
    cuts: List[int] = []
    for s in range(1, n_stages):
        target = round(s * n / n_stages)
        avail = [b for b in legal if b not in cuts and b > (cuts[-1] if cuts
                                                           else 0)]
        # Keep enough headroom for the remaining cuts to stay increasing.
        remaining = n_stages - 1 - s
        avail = avail[: len(avail) - remaining] if remaining else avail
        if not avail:
            raise ValueError("cannot place equal-count cuts legally")
        cuts.append(min(avail, key=lambda b: (abs(b - target), b)))
    edges = [0] + cuts + [n]
    bounds = tuple(zip(edges[:-1], edges[1:]))
    seconds = _bounds_seconds(step_seconds(netplan), bounds)
    if n_micro is None:
        n_micro = choose_n_micro(seconds, netplan.batch)
    return PipelinePlan(
        stage_bounds=bounds, stage_seconds=seconds, n_micro=n_micro
    )


def modeled_pipeline_latency(
    stage_seconds: Sequence[float],
    n_micro: int,
    tick_overhead_s: float = TICK_OVERHEAD_S,
) -> float:
    """Modeled seconds for one batch through the GPipe schedule.

    The executor's schedule is tick-synchronous — each of the
    ``n_micro + n_stages - 1`` ticks ends in a collective (ppermute), so a
    tick lasts as long as the slowest *active* stage's per-microbatch
    compute (stage seconds are predicted at full batch and scale down
    linearly with the microbatch split):

        latency(m) = sum_t max{T_s / m : stage s active at tick t}
                     + (m + S - 1) * overhead

    In steady state every tick is gated by the global max stage (the
    classic bubble identity); during fill/drain only a prefix/suffix of
    stages is active, which is why balancing the *whole* stage profile —
    not just its max — shows up in the model.  The fixed per-tick overhead
    penalizes over-splitting.
    """
    s = len(stage_seconds)
    per_mb = [t / n_micro for t in stage_seconds]
    total = 0.0
    for t in range(n_micro + s - 1):
        active = [per_mb[i] for i in range(s) if t >= i and t - i < n_micro]
        if active:
            total += max(active)
    return total + (n_micro + s - 1) * tick_overhead_s


def choose_n_micro(
    stage_seconds: Sequence[float],
    batch: int,
    tick_overhead_s: float = TICK_OVERHEAD_S,
) -> int:
    """The microbatch count minimizing modeled latency.

    Candidates are the divisors of ``batch`` (microbatches must tile the
    batch exactly — the executor reshapes to (m, batch//m, ...)); ties break
    to the smaller count (less overhead exposure for the same model).
    """
    if batch < 1:
        raise ValueError(f"batch={batch}")
    best_m, best_t = 1, float("inf")
    for m in range(1, batch + 1):
        if batch % m:
            continue
        t = modeled_pipeline_latency(stage_seconds, m, tick_overhead_s)
        if t < best_t:
            best_m, best_t = m, t
    return best_m


def pipeline_key(
    layers: Sequence[Any],
    h: int,
    w: int,
    in_channels: int,
    batch: int,
    n_stages: int,
    planner: Planner,
    dtype: Any = "float32",
) -> str:
    """Cache key for a stage-partition entry: the network digest key (which
    already folds in chip/dtype/impl/policies/batch) plus the stage count."""
    return (
        network_key(layers, h, w, in_channels, batch, planner, dtype)
        + f"|stages{n_stages}"
    )


def plan_pipeline(
    layers: Sequence[Any],
    h: int,
    w: int,
    planner: Planner,
    n_stages: int,
    in_channels: int = 3,
    batch: int = 1,
    dtype: Any = "float32",
    netplan: Optional[NetworkPlan] = None,
) -> PipelinePlan:
    """Resolve a PipelinePlan through a Planner, warm-cached at v6 scope.

    Cold: partitions the (possibly freshly planned) NetworkPlan and stores
    the record as a "pipelines" cache entry keyed by (network digest,
    n_stages, chip, dtype).  Warm: reconstructs the PipelinePlan straight
    from the entry — zero re-partitions (``planner.pipeline_hits``).
    """
    layers = tuple(layers)
    if netplan is None:
        netplan = plan_network(
            layers, h, w, planner, in_channels=in_channels, batch=batch,
            dtype=dtype,
        )
    key = pipeline_key(
        layers, h, w, in_channels, batch, n_stages, planner, dtype
    )
    entry = planner.pipeline_entry(key)
    if entry is not None:
        try:
            pipeplan = PipelinePlan.from_json(entry)
            _validate_pipeline_bounds(pipeplan, len(netplan.steps), n_stages)
        except (KeyError, ValueError, TypeError, IndexError):
            pass                            # corrupt entry -> repartition
        else:
            planner.pipeline_hits += 1      # counted only once validated
            return pipeplan
    pipeplan = partition_network(netplan, n_stages)
    planner.put_pipeline_entry(key, pipeplan.to_json())
    return pipeplan


def _validate_pipeline_bounds(
    pipeplan: PipelinePlan, n_steps: int, n_stages: int
) -> None:
    """Raise unless the bounds are a contiguous cover of [0, n_steps)."""
    bounds = pipeplan.stage_bounds
    if len(bounds) != n_stages:
        raise ValueError(f"{len(bounds)} stages, wanted {n_stages}")
    if bounds[0][0] != 0 or bounds[-1][1] != n_steps:
        raise ValueError(f"bounds {bounds} do not cover [0, {n_steps})")
    for (a0, z0), (a1, _) in zip(bounds, bounds[1:]):
        if z0 != a1 or a0 >= z0:
            raise ValueError(f"non-contiguous bounds {bounds}")
    if bounds[-1][0] >= bounds[-1][1]:
        raise ValueError(f"empty final stage in {bounds}")
    if pipeplan.n_micro < 1:
        raise ValueError(f"n_micro={pipeplan.n_micro}")
    if len(pipeplan.stage_seconds) != n_stages:
        raise ValueError("stage_seconds length mismatch")


# ---------------------------------------------------------------------------
# Parameter preparation (offline: folding, padding, weight pre-transform)


def pretransform_flags(
    netplan: NetworkPlan, pretransform: bool = True
) -> Tuple[bool, ...]:
    """Per-step "weights carry the offline Winograd transform" flags.

    Exactly the layers ``prepare_net_params(pretransform=True)`` transforms:
    conv steps whose resolved algorithm is Winograd.  The flag travels
    *explicitly* from preparation to execution (``run_network`` /
    ``NetworkExecutor`` / the api facade) — it is never sniffed from weight
    shapes, because a raw kh == 8 kernel is (8, 8, C, O) exactly like a
    pre-transformed 3x3 one.
    """
    if not pretransform:
        return (False,) * len(netplan.steps)
    return tuple(
        s.layer.kind == "conv"
        and resolve_algorithm(s.spec, s.plan, *s.in_hw)
        is ConvAlgorithm.WINOGRAD
        for s in netplan.steps
    )


def prepare_net_params(
    netplan: NetworkPlan,
    params: Sequence[Dict],
    pretransform: bool = False,
    calibration: Optional[jnp.ndarray] = None,
) -> List[Dict]:
    """Offline parameter preparation for a NetworkPlan.

    Folds inference batchnorm into conv weights + bias, pads every conv's
    weights/bias to the step's physical channel layouts (so no weight pads
    appear at layer boundaries in the jitted forward), and — with
    ``pretransform`` — applies the offline Winograd weight transform
    (paper §VII.A excludes it from timing for the same reason).  The layers
    transformed are exactly ``pretransform_flags(netplan, pretransform)``;
    pass those flags to ``run_network`` so execution routes the transformed
    weights explicitly.  A step whose plan folds its taps gets its weights
    folded to the (K, O) matrix its GEMM reads (``fold_weights``).

    Under an int8 network plan the steps whose ConvPlan resolved to
    ``dtype == 'int8'`` are additionally quantized offline (core/quant.py):
    an fp32 oracle walk over ``calibration`` (a sample input batch; a
    deterministic synthetic batch when None) yields per-input-channel
    activation scales, which are folded into the weights before
    per-output-channel int8 weight quantization.  Such a step's prepared
    entry carries ``w`` (int8), ``b`` (fp32), ``w_scale`` (the fused dequant
    row) and ``x_scale`` (the entry quantization scales, padded with ones so
    zero-padded channels quantize to 0 and the layout-elision invariant
    act(0 * scale + 0) = 0 survives quantization).
    """
    from repro.models.cnn import fold_batchnorm

    flags = pretransform_flags(netplan, pretransform)
    params = fold_batchnorm(params, [s.layer for s in netplan.steps])
    int8_steps = {
        s.index
        for s in netplan.steps
        if s.layer.kind == "conv" and s.plan is not None
        and s.plan.dtype == "int8"
    }
    act_scales: Dict[int, jnp.ndarray] = {}
    if int8_steps:
        from repro.core.quant import (
            calibrate_activation_scales,
            default_calibration_batch,
        )

        if calibration is None:
            calibration = default_calibration_batch(
                *netplan.input_hw, netplan.in_channels
            )
        act_scales = calibrate_activation_scales(netplan, params, calibration)
    out: List[Dict] = []
    for s, p, pre in zip(netplan.steps, params, flags):
        if s.layer.kind != "conv":
            out.append(p)
            continue
        w, b = p["w"], p["b"]
        if s.index in int8_steps:
            from repro.core.quant import quantize_conv_weights

            assert not pre, "int8 steps never carry the Winograd transform"
            x_scale = act_scales[s.index]
            w, w_scale = quantize_conv_weights(w, x_scale)
            cin_pad = s.in_layout.phys_c - w.shape[2]
            o_pad = s.out_layout.phys_c - w.shape[3]
            if cin_pad or o_pad:
                w = jnp.pad(w, ((0, 0), (0, 0), (0, cin_pad), (0, o_pad)))
                b = jnp.pad(b, (0, o_pad))
                w_scale = jnp.pad(w_scale, (0, o_pad))
            if cin_pad:
                # Ones, not zeros: the entry quantization divides by these.
                x_scale = jnp.pad(x_scale, (0, cin_pad), constant_values=1.0)
            out.append({"w": w, "b": b, "w_scale": w_scale,
                        "x_scale": x_scale})
            continue
        cin_pad = s.in_layout.phys_c - w.shape[2]
        o_pad = s.out_layout.phys_c - w.shape[3]
        if cin_pad or o_pad:
            w = jnp.pad(w, ((0, 0), (0, 0), (0, cin_pad), (0, o_pad)))
            b = jnp.pad(b, (0, o_pad))
        if pre:
            from repro.core.winograd import transform_weights

            w = transform_weights(w, w.dtype)           # (8, 8, Cp, Op)
        elif s.plan is not None and s.plan.taps_folded:
            from repro.kernels.im2col_gemm.ops import fold_weights

            w = fold_weights(w, step_folded_k(s))       # (Kp, Op)
        out.append({"w": w, "b": b})
    return out


# ---------------------------------------------------------------------------
# Execution


def _align_channels(x: jnp.ndarray, want_phys: int) -> jnp.ndarray:
    have = x.shape[-1]
    if have == want_phys:
        return x
    if have < want_phys:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, want_phys - have)]
        return jnp.pad(x, pad)
    return x[..., :want_phys]


#: The named scope of the single channel crop at network exit.
EXIT_SCOPE = "exit"


def layer_scope(step: NetStep) -> str:
    """The named scope that holds a planned layer's ops in ``run_network``:
    ``L{index:03d}.{kind}``, with the resolved algorithm for convs
    (``L000.conv.winograd``, ``L018.conv.im2col_gemm``, ``L002.maxpool``).
    Indices are absolute, so a pipeline stage names its layers as the whole
    network does.  XLA keeps the scope in each instruction's ``op_name``, so
    a profiler trace puts every device op down to its layer."""
    name = f"L{step.index:03d}.{step.layer.kind}"
    if step.layer.kind == "conv":
        algo = resolve_algorithm(step.spec, step.plan, *step.in_hw)
        name = f"{name}.{algo.value}"
    return name


def winograd_step_tiling(netplan: NetworkPlan, step: NetStep,
                         shards: int = 1):
    """The ``WinogradTiling`` a planned Pallas Winograd step runs with when
    the batch is split over ``shards`` devices (each kernel sees
    batch / shards images), or None for any other step."""
    if (step.layer.kind != "conv" or step.plan is None
            or step.plan.impl != "pallas"
            or resolve_algorithm(step.spec, step.plan, *step.in_hw)
            is not ConvAlgorithm.WINOGRAD):
        return None
    from repro.kernels.winograd.ops import winograd_tiling

    return winograd_tiling(
        netplan.batch // shards, *step.out_hw, tuple(step.plan.kernel_blocks),
        vmem_budget=netplan.vmem_budget,
    )


def step_folded_k(step: NetStep) -> Optional[int]:
    """The contraction depth of a step whose im2col taps are folded into
    one GEMM (kh*kw*C, padded to the lane width when it spans several K
    blocks), or None."""
    if step.plan is None or not step.plan.taps_folded:
        return None
    from repro.kernels.im2col_gemm.ops import folded_k

    return folded_k(step.spec, step.spec.in_channels,
                    step.plan.kernel_blocks[2])


#: A step's ``residual`` role in the layer table, by kind.
_RESIDUAL = {"shortcut": "add", "route": "branch"}


def layer_table(netplan: NetworkPlan, shards: int = 1) -> Dict[str, Any]:
    """What a trace reader needs to put the forward's device ops down to
    planned layers: per step its scope, index, kind, algorithm and the
    plan's ``predicted_s``, under the forward's jitted name; and ``input``,
    the forward's input argument, whose name XLA gives the copy that lays
    the input out for the first layer.  A Pallas Winograd step also says
    ``tile_ratio``, the tiles its kernel computes over the real B*nTH*nTW
    (None on other steps), at the batch of one of ``shards`` devices.
    An im2col step says whether it folds its taps into one GEMM
    (``taps_folded``) and over what K (``folded_k``, None when tap-wise);
    other steps carry None for both.
    ``residual`` marks the residual path: ``"add"`` on a shortcut,
    ``"branch"`` on a route, ``"source"`` on a step whose output a later
    shortcut or route reads (its layout is forced back to logical
    channels), None elsewhere.

    JAX's persistent compilation cache keys a program without its metadata,
    so an executable compiled before a scope was renamed would load with the
    old names.  The name therefore carries a digest of the scopes (and of
    the batch and input size), which the key does include."""
    layers = []
    referenced = {j for s in netplan.steps
                  for j in getattr(s.layer, "from_layers", ())}
    for s in netplan.steps:
        scope = layer_scope(s)
        algo = scope.split(".", 2)[2] if s.layer.kind == "conv" else None
        tiling = winograd_step_tiling(netplan, s, shards)
        layers.append({
            "scope": scope,
            "index": s.index,
            "kind": s.layer.kind,
            "algorithm": algo,
            "predicted_s": s.plan.predicted_s if s.plan is not None else None,
            "tile_ratio": tiling.ratio if tiling is not None else None,
            "taps_folded": (s.plan.taps_folded if s.plan is not None and algo
                            == ConvAlgorithm.IM2COL_GEMM.value else None),
            "folded_k": step_folded_k(s),
            "residual": _RESIDUAL.get(s.layer.kind) or (
                "source" if s.index in referenced else None),
        })
    key = json.dumps([[l["scope"] for l in layers], EXIT_SCOPE,
                      netplan.batch, list(netplan.input_hw)])
    digest = hashlib.sha256(key.encode()).hexdigest()[:8]
    return {"name": f"fwd_{digest}", "input": "xx", "batch": netplan.batch,
            "input_hw": list(netplan.input_hw), "layers": layers}


def run_network(
    netplan: NetworkPlan,
    params: Sequence[Dict],
    x: jnp.ndarray,
    interpret: Optional[bool] = None,
    pretransformed: Optional[Sequence[bool]] = None,
    start: int = 0,
    stop: Optional[int] = None,
) -> jnp.ndarray:
    """The planned whole-network forward on prepared params.

    Pads once at entry (the first conv's input layout), flows block-padded
    activations across every elided boundary, crops once at exit.  Pure
    function of (params, x) given the static NetworkPlan — jit it, or let
    NetworkExecutor do so.

    ``pretransformed`` is the per-step flag tuple from
    ``pretransform_flags`` saying which conv weights already carry the
    offline Winograd transform.  ``None`` is accepted for legacy callers
    and falls back to a *guarded* shape check (8x8 leading dims AND a 3x3
    spec — a raw kh == 8 kernel is never misread as transformed); new code
    should always pass the explicit flags.

    ``start``/``stop`` run the ``steps[start:stop]`` slice only — one
    pipeline stage.  ``params`` is then the slice-aligned parameter list
    (``params[j - start]`` for layer j) while ``pretransformed`` stays
    full-network length (flag lookup is by absolute index).  Legal slices
    begin at a stage boundary from ``legal_cut_points``: the incoming
    activation is logically laid out (trivial layout — the partitioner
    forbids cuts inside an elision chain) and no ``from_layers`` reference
    reaches back before ``start``.  The exit crop runs only when the slice
    includes the final step; interior stages hand their boundary activation
    off as produced.
    """
    from repro.core.conv2d import conv2d

    n_steps = len(netplan.steps)
    stop = n_steps if stop is None else stop
    assert 0 <= start <= stop <= n_steps, (start, stop, n_steps)
    outputs: List[jnp.ndarray] = []
    cur = x
    for s in netplan.steps[start:stop]:
        with jax.named_scope(layer_scope(s)):
            l = s.layer
            if l.kind == "conv":
                p = params[s.index - start]
                cur = _align_channels(cur, s.in_layout.phys_c)
                quantized = "w_scale" in p
                if quantized:
                    # int8 step (prepare_net_params quantized it offline): the
                    # activation re-quantizes at entry with the static
                    # calibrated scales, the kernel accumulates int8 x int8 in
                    # int32, and the fused epilogue dequantizes via w_scale —
                    # inter-layer activations stay fp32.
                    from repro.core.quant import quantize_activation

                    cur = quantize_activation(cur, p["x_scale"])
                    epi = Epilogue(bias=p["b"], activation=l.activation,
                                   scale=p["w_scale"])
                else:
                    epi = Epilogue(bias=p["b"], activation=l.activation)
                eff_impl = s.plan.impl if s.plan is not None else netplan.impl
                if pretransformed is not None:
                    pre = bool(pretransformed[s.index])
                else:                           # legacy guard, not a sniff: a
                    pre = (                     # 3x3 spec can't have raw (8,8)
                        s.spec.kernel_size == (3, 3)
                        and p["w"].ndim == 4
                        and p["w"].shape[0] == 8
                        and p["w"].shape[1] == 8
                    )
                if s.plan is not None and eff_impl == "pallas":
                    # The executor owns the boundary: channels arrive block-
                    # padded per in_layout, the crop defers per out_layout.
                    cur = conv2d(
                        cur, p["w"], s.spec, impl=eff_impl,
                        interpret=interpret, plan=s.plan, epilogue=epi,
                        in_layout=s.in_layout, out_layout=s.out_layout,
                        pretransformed=pre, vmem_budget=netplan.vmem_budget,
                    )
                elif quantized:
                    # Pure-jnp int8 reference: the same integer products in
                    # fp32 (exact for int8 operands; accumulated rounding is
                    # orders below the quantization noise), dequantized by the
                    # shared epilogue.
                    cur = conv2d(
                        cur.astype(jnp.float32), p["w"].astype(jnp.float32),
                        s.spec, impl=eff_impl, interpret=interpret,
                        plan=s.plan, epilogue=epi, pretransformed=pre,
                    )
                else:
                    cur = conv2d(
                        cur, p["w"], s.spec, impl=eff_impl,
                        interpret=interpret, plan=s.plan, epilogue=epi,
                        pretransformed=pre,
                    )
            elif l.kind == "maxpool":
                cur = max_pool(cur, l)
            elif l.kind == "avgpool":
                cur = cur.mean(axis=(1, 2))
            elif l.kind == "upsample":
                cur = jnp.repeat(
                    jnp.repeat(cur, l.size, axis=1), l.size, axis=2
                )
            elif l.kind == "shortcut":
                cur = apply_activation(
                    cur + outputs[l.from_layers[0] - start], l.activation
                )
            elif l.kind == "route":
                cur = jnp.concatenate(
                    [outputs[j - start] for j in l.from_layers], axis=-1
                )
            elif l.kind == "fc":
                p = params[s.index - start]
                if cur.ndim == 4:
                    cur = cur.mean(axis=(1, 2))
                cur = apply_activation(
                    jnp.dot(cur, p["w"], precision=dot_precision(cur.dtype))
                    + p["b"], l.activation,
                )
            outputs.append(cur)
    exit_layout = netplan.exit_layout
    if stop == n_steps and exit_layout.pad_c:
        with jax.named_scope(EXIT_SCOPE):
            cur = cur[..., :exit_layout.c]  # the single crop at network exit
    return cur


def expected_channel_ops(netplan: NetworkPlan) -> List[Dict[str, Any]]:
    """The channel-axis pads/crops ``run_network`` will emit, predicted
    statically from the plan.

    Mirrors the executor walk: the entry/per-conv ``_align_channels`` when
    the carried physical channel count differs from the step's ``in_layout``,
    the kernel wrappers' deferred channel crop when the kernel's out-channel
    grid (``ceil_to(phys, block)``) overshoots the layout's keep count, the
    direct GEMM's K-axis pad when the incoming channels don't divide ``bk``,
    a folded step's pad of its patch matrix to the folded K, and the single
    exit crop.  ``repro.analysis``'s elision pass census
    (taint-tracked pad/slice ops on the traced jaxpr's minor axis) must
    match this list exactly — any extra op is executor drift from the plan,
    any missing op means the plan promised movement that can't happen.

    Row-tile tails, tile-count alignment and spatial padding are intra-layer
    movement on non-minor axes and deliberately outside this contract.
    """
    ops: List[Dict[str, Any]] = []
    outputs_phys: List[int] = []
    cur_phys = netplan.in_channels
    for s in netplan.steps:
        l = s.layer
        if l.kind == "conv":
            planned = s.plan is not None and (
                s.plan.impl if s.plan is not None else netplan.impl
            ) == "pallas"
            if planned:
                want = s.in_layout.phys_c
                if cur_phys != want:
                    ops.append({
                        "step": s.index,
                        "kind": "pad" if cur_phys < want else "crop",
                    })
                algo = resolve_algorithm(s.spec, s.plan, *s.in_hw)
                o_phys = s.out_layout.phys_c
                o_keep = (
                    s.out_layout.phys_c if s.out_layout.pad_c
                    else s.spec.out_channels
                )
                if algo is ConvAlgorithm.DIRECT:
                    bm, bn, bk = s.plan.kernel_blocks
                    if ceil_to(want, bk) != want:
                        ops.append({"step": s.index, "kind": "pad"})
                    emitted = ceil_to(o_phys, bn)
                elif s.plan.taps_folded:
                    # The patch matrix's K tail, padded once.
                    if step_folded_k(s) != s.spec.kh * s.spec.kw * want:
                        ops.append({"step": s.index, "kind": "pad"})
                    emitted = ceil_to(o_phys, s.plan.kernel_blocks[1])
                else:
                    emitted = ceil_to(o_phys, s.plan.kernel_blocks[2])
                if emitted != o_keep:
                    ops.append({"step": s.index, "kind": "crop"})
                cur_phys = o_keep
            else:
                cur_phys = s.spec.out_channels
        elif l.kind == "route":
            cur_phys = sum(outputs_phys[j] for j in l.from_layers)
        elif l.kind == "fc":
            cur_phys = l.out_channels
        # maxpool / upsample / shortcut / avgpool preserve channels
        outputs_phys.append(cur_phys)
    if netplan.exit_layout.pad_c:
        ops.append({"step": len(netplan.steps) - 1, "kind": "crop"})
    return ops


class NetworkExecutor:
    """Jitted whole-network inference over a NetworkPlan.

    Prepares parameters offline (fold + pad + optional Winograd
    pre-transform), compiles one forward for the plan's batch shape, and —
    when more than one device is visible and the batch divides — runs
    data-parallel over a 1-D device mesh on the batch axis via shard_map
    (params replicated, activations batch-sharded; single-device fallback
    is a plain jit).
    """

    def __init__(
        self,
        netplan: NetworkPlan,
        params: Sequence[Dict],
        interpret: Optional[bool] = None,
        devices: Optional[Sequence[Any]] = None,
        pretransform: bool = True,
        prepared: bool = False,
        calibration: Optional[jnp.ndarray] = None,
    ):
        self.netplan = netplan
        self.params = (
            list(params) if prepared
            else prepare_net_params(netplan, params, pretransform=pretransform,
                                    calibration=calibration)
        )
        # The explicit flag contract: which conv weights carry the offline
        # Winograd transform.  With ``prepared=True`` the caller vouches the
        # params were prepared with the same ``pretransform`` policy — and
        # because the old shape sniff tolerated a mismatch here, we verify
        # the claim against the weights instead of failing deep in a kernel.
        self.pretransformed = pretransform_flags(netplan, pretransform)
        if prepared:
            for s, p, pre in zip(netplan.steps, self.params,
                                 self.pretransformed):
                if s.layer.kind != "conv":
                    continue
                looks_transformed = (
                    s.spec.kernel_size == (3, 3) and p["w"].shape[0] == 8
                )
                if pre != looks_transformed:
                    raise ValueError(
                        f"step {s.index}: prepared params "
                        f"{'lack' if pre else 'carry'} the offline Winograd "
                        f"weight transform (w {tuple(p['w'].shape)}) but the "
                        f"executor was built with pretransform={pretransform}"
                        f" — pass the same pretransform= that "
                        f"prepare_net_params ran with"
                    )
        if devices is None:
            devices = jax.devices()
        self.mesh = None
        self._placed = None
        shards = (len(devices) if len(devices) > 1
                  and netplan.batch % len(devices) == 0 else 1)
        table = layer_table(netplan, shards)
        spans.RECORD.register(table)

        def fwd(prms, xx):                 # xx: the table's "input"
            return run_network(netplan, prms, xx, interpret=interpret,
                               pretransformed=self.pretransformed)

        fwd.__name__ = fwd.__qualname__ = table["name"]

        if shards > 1:
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            self.mesh = Mesh(np.array(devices), ("batch",))
            fwd = jax.shard_map(
                fwd, mesh=self.mesh,
                in_specs=(P(), P("batch")), out_specs=P("batch"),
                check_vma=False,
            )
        self._fn = jax.jit(fwd)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, w = x.shape[0], x.shape[1], x.shape[2]
        assert (h, w) == self.netplan.input_hw and b == self.netplan.batch, (
            f"executor planned for batch {self.netplan.batch} at "
            f"{self.netplan.input_hw}, got {x.shape}"
        )
        if self._placed is None:
            self._placed = self._place_params()
        return self._fn(self._placed, x)

    def _place_params(self):
        """The prepared params where the forward reads them: replicated over
        the batch mesh once, rather than broadcast from one device on every
        call.  Placed on first call, so an executor over devices that are
        only described (an ahead-of-time compile) can still be built."""
        if self.mesh is None:
            return self.params
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(self.params, NamedSharding(self.mesh, P()))
