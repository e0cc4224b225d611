"""jaxpr introspection for the compile-time plan verifier.

Everything here is *static*: we trace the compiled forward with
``jax.make_jaxpr`` (no device execution) and recover, per ``pallas_call``
equation, the grid, every operand's block shape and index-map grid-axis
dependence, and the scratch allocations — enough to reconstruct each
kernel's true VMEM footprint and its HBM traffic from first principles.

Also home of the pad/slice boundary walkers:

- ``boundary_ops`` — the promoted test-only walker from
  ``tests/test_netplan.py``: every pad/slice/dynamic_slice/gather outside
  pallas_call interiors, now descending into ``pjit`` / ``custom_jvp`` /
  ``cond`` call params (closed sub-jaxprs used to be silently skipped when
  they arrived as tuples or as ``ClosedJaxpr`` objects).
- ``channel_boundary_ops`` — the elision pass's census: pads/slices that
  change the *channel* (minor) axis of an activation-derived tensor, found
  by forward taint propagation from the input operand.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Iterator, List, Tuple

import jax

#: Data-movement primitives the layout-elision contract is about.
BOUNDARY_PRIMS = ("pad", "slice", "dynamic_slice", "gather")


def _is_literal(v) -> bool:
    """Literals carry ``val``; Vars don't (stable across jax versions)."""
    return hasattr(v, "val")


def _subjaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Every Jaxpr/ClosedJaxpr reachable from an eqn's params.

    Handles the three shapes jax uses: a bare ``Jaxpr``, a ``ClosedJaxpr``
    (``pjit``, ``custom_jvp_call``'s ``call_jaxpr``) and tuples/lists of
    either (``cond`` branches, ``scan`` bodies).  The old test walker only
    recognized values with a ``.jaxpr`` attribute, so a nested fusion inside
    a pjit'd callee whose param arrived as a tuple was silently skipped.
    """
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            # ClosedJaxpr first: it *also* forwards .eqns, but the walkers
            # need the underlying Jaxpr (its invars/outvars).
            if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                yield u.jaxpr
            elif hasattr(u, "eqns"):        # bare Jaxpr
                yield u
    return


def iter_eqns(jaxpr, *, into_pallas: bool = False) -> Iterator[Any]:
    """All equations of ``jaxpr`` and its sub-jaxprs, in program order.

    ``into_pallas=False`` (the default) treats each ``pallas_call`` as a
    leaf: its interior block-level data movement is the kernel's own
    business, not a network-boundary op.
    """
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            continue
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub, into_pallas=into_pallas)


def boundary_ops(fn, *args) -> List[str]:
    """Names of pad/slice/dynamic_slice/gather ops outside pallas kernels.

    The production home of the jaxpr walk ``tests/test_netplan.py`` used to
    carry: trace ``fn(*args)`` and list every boundary primitive that would
    execute between kernels.  An elided two-conv chain traces to ``[]``.
    """
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [
        eqn.primitive.name
        for eqn in iter_eqns(jaxpr)
        if eqn.primitive.name in BOUNDARY_PRIMS
    ]


# ---------------------------------------------------------------------------
# pallas_call recovery


@dataclasses.dataclass(frozen=True)
class OperandInfo:
    """One streamed operand (input or output) of a pallas_call."""

    kind: str                     # "in" | "out"
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: str
    itemsize: int
    dep_axes: Tuple[int, ...]     # grid axes the index map depends on
    #: Per dim: True where the index map yields an element offset
    #: (``pl.Element``) rather than a block index.
    element_dims: Tuple[bool, ...] = ()
    #: The operand's index-map ClosedJaxpr, kept so the kernel-interior
    #: passes (analysis.grid) can evaluate the map at concrete grid points.
    #: None for hand-built records in tests; excluded from equality.
    index_map_jaxpr: Any = dataclasses.field(
        default=None, compare=False, repr=False
    )

    @property
    def block_bytes(self) -> int:
        return int(math.prod(self.block_shape)) * self.itemsize

    def fetches(self, grid: Tuple[int, ...]) -> int:
        """How many times the kernel fetches (or writes) this operand's
        blocks over the whole grid.

        The grid iterates row-major (last axis innermost) and Pallas elides
        the copy when consecutive steps map to the same block, so an operand
        whose index map depends on grid axes up to ``a`` is re-fetched once
        per step of the sub-grid ``grid[:a+1]`` — the BLIS panel-re-read
        count.  A constant index map fetches exactly once.
        """
        if not self.dep_axes:
            return 1
        return int(math.prod(grid[: max(self.dep_axes) + 1]))

    def bytes_moved(self, grid: Tuple[int, ...]) -> int:
        return self.fetches(grid) * self.block_bytes


@dataclasses.dataclass(frozen=True)
class ScratchInfo:
    shape: Tuple[int, ...]
    dtype: str

    @property
    def nbytes(self) -> int:
        import numpy as np

        return int(math.prod(self.shape)) * np.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class PallasCallRecord:
    """Everything the verifier needs about one compiled pallas_call."""

    name: str                     # kernel body function name
    grid: Tuple[int, ...]
    inputs: Tuple[OperandInfo, ...]
    outputs: Tuple[OperandInfo, ...]
    scratch: Tuple[ScratchInfo, ...]
    kernel_jaxpr: Any             # the kernel-interior jaxpr (dtype lint)
    #: Mosaic's per-grid-axis schedule declaration ('parallel' |
    #: 'arbitrary'), recovered from compiler_params — the kernel-interior
    #: race pass checks reduction axes are declared sequential.  None when
    #: the pallas_call carried no dimension_semantics.
    dimension_semantics: Any = dataclasses.field(default=None, compare=False)

    @property
    def operands(self) -> Tuple[OperandInfo, ...]:
        return self.inputs + self.outputs

    def vmem_bytes(self) -> int:
        """True per-program footprint: every streamed block double-buffered
        (Pallas revolving windows) plus the scratch allocations, each laid
        out on Mosaic's (sublane, lane) tiles (``vmem_model.tiled_bytes``)."""
        import numpy as np

        from repro.core.vmem_model import tiled_bytes

        return (
            2 * sum(tiled_bytes(op.block_shape, op.itemsize)
                    for op in self.operands)
            + sum(tiled_bytes(s.shape, np.dtype(s.dtype).itemsize)
                  for s in self.scratch)
        )

    def traffic_bytes(self) -> int:
        """Whole-grid HBM bytes implied by the block/grid structure."""
        return sum(op.bytes_moved(self.grid) for op in self.operands)


def _index_map_deps(index_map_jaxpr, n_axes: int) -> Tuple[int, ...]:
    """Which grid axes an index map's outputs transitively depend on."""
    jx = index_map_jaxpr.jaxpr
    needed = {id(v) for v in jx.outvars if not _is_literal(v)}
    for eqn in reversed(jx.eqns):
        if any(id(ov) in needed for ov in eqn.outvars):
            for iv in eqn.invars:
                if not _is_literal(iv):
                    needed.add(id(iv))
    return tuple(
        i for i, v in enumerate(jx.invars[:n_axes]) if id(v) in needed
    )


def _block_dim(bd) -> Tuple[int, bool]:
    """(window size, element-indexed) of one ``BlockMapping`` dim."""
    from jax.experimental import pallas as pl

    if isinstance(bd, pl.Element):
        return int(bd.block_size), True
    if bd is None or isinstance(bd, pl.Squeezed):
        return 1, False
    if isinstance(bd, pl.Blocked):
        return int(bd.block_size), False
    return int(bd), False


def _record_from_eqn(eqn) -> PallasCallRecord:
    import numpy as np

    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    n_axes = len(grid)
    ops: List[OperandInfo] = []
    for pos, bm in enumerate(gm.block_mappings):
        aval = bm.array_aval
        dims = [_block_dim(bd) for bd in bm.block_shape]
        ops.append(
            OperandInfo(
                kind="in" if pos < gm.num_inputs else "out",
                block_shape=tuple(size for size, _ in dims),
                array_shape=tuple(int(d) for d in aval.shape),
                dtype=str(aval.dtype),
                itemsize=int(np.dtype(aval.dtype).itemsize),
                dep_axes=_index_map_deps(bm.index_map_jaxpr, n_axes),
                element_dims=tuple(elem for _, elem in dims),
                index_map_jaxpr=bm.index_map_jaxpr,
            )
        )
    kernel_jaxpr = eqn.params["jaxpr"]
    mosaic = (eqn.params.get("compiler_params") or {}).get("mosaic_tpu")
    semantics = getattr(mosaic, "dimension_semantics", None)
    if semantics is not None:
        semantics = tuple(str(s) for s in semantics)
    n_scratch = int(gm.num_scratch_operands)
    scratch: List[ScratchInfo] = []
    if n_scratch:
        for v in kernel_jaxpr.invars[-n_scratch:]:
            scratch.append(
                ScratchInfo(
                    shape=tuple(int(d) for d in v.aval.shape),
                    dtype=str(v.aval.dtype),
                )
            )
    return PallasCallRecord(
        name=eqn.params.get("name") or kernel_jaxpr.debug_info.func_name,
        grid=grid,
        inputs=tuple(op for op in ops if op.kind == "in"),
        outputs=tuple(op for op in ops if op.kind == "out"),
        scratch=tuple(scratch),
        kernel_jaxpr=kernel_jaxpr,
        dimension_semantics=semantics,
    )


def pallas_calls(jaxpr) -> List[PallasCallRecord]:
    """All pallas_call records of a (sub-)jaxpr walk, in program order."""
    return [
        _record_from_eqn(eqn)
        for eqn in iter_eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"
    ]


def trace_forward(fn, *args):
    """(closed_jaxpr, [PallasCallRecord]) for ``fn(*args)`` — trace only."""
    closed = jax.make_jaxpr(fn)(*args)
    return closed, pallas_calls(closed.jaxpr)


# ---------------------------------------------------------------------------
# Activation taint + channel-axis boundary census


@dataclasses.dataclass(frozen=True)
class ChannelOp:
    """One channel-axis pad or crop on an activation-derived tensor."""

    kind: str                     # "pad" | "crop"
    in_shape: Tuple[int, ...]
    out_shape: Tuple[int, ...]


def _census_walk(jaxpr, tainted: set, out: List[ChannelOp]) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        tainted_in = any(
            not _is_literal(v) and id(v) in tainted for v in eqn.invars
        )
        if prim in ("pad", "slice") and not _is_literal(eqn.invars[0]):
            src, dst = eqn.invars[0], eqn.outvars[0]
            s_in = getattr(src.aval, "shape", ())
            s_out = getattr(dst.aval, "shape", ())
            if (
                id(src) in tainted
                and len(s_in) == len(s_out)
                and len(s_in) >= 1
                and s_in[-1] != s_out[-1]
            ):
                out.append(
                    ChannelOp(
                        kind="pad" if s_out[-1] > s_in[-1] else "crop",
                        in_shape=tuple(int(d) for d in s_in),
                        out_shape=tuple(int(d) for d in s_out),
                    )
                )
        if tainted_in:
            for ov in eqn.outvars:
                tainted.add(id(ov))
        if prim == "pallas_call":
            continue                        # interior movement is the kernel's
        # Descend into call-like sub-jaxprs whose invars mirror the eqn's
        # (pjit, closed_call, custom_jvp/vjp call params) so channel ops
        # inside nested fusions are still counted.  ``cond`` — which also
        # carries ``lax.switch``, jax lowers both to cond_p — leads with the
        # branch-selector operand, so its branch jaxprs mirror
        # ``eqn.invars[1:]``; the old exact-length match silently skipped
        # them, hiding e.g. pipeline stage bodies (switch branches) from the
        # elision census.
        for sub in _subjaxprs(eqn.params):
            if len(sub.invars) == len(eqn.invars):
                operands = eqn.invars
            elif len(sub.invars) == len(eqn.invars) - 1:
                operands = eqn.invars[1:]   # cond/switch: drop the selector
            else:
                continue
            inner = {
                id(sv)
                for sv, ev in zip(sub.invars, operands)
                if not _is_literal(ev) and id(ev) in tainted
            }
            _census_walk(sub, inner, out)
            # conservative: sub-jaxpr outvars already handled above via
            # tainted_in -> outvars
    return


def channel_boundary_ops(closed_jaxpr, taint_invar: int = -1) -> List[ChannelOp]:
    """Channel-axis pads/crops on tensors derived from one input.

    ``taint_invar`` indexes the traced function's flattened invars;
    the verifier traces ``lambda params, x: run_network(...)`` so the
    activation is the *last* invar.  Weight/bias block-padding (untainted
    params) and spatial pads (non-minor axes) are excluded by construction —
    what remains is exactly the set of layer-boundary channel ops the PR-4
    elision contract governs.
    """
    jx = closed_jaxpr.jaxpr
    tainted = {id(jx.invars[taint_invar])}
    out: List[ChannelOp] = []
    _census_walk(jx, tainted, out)
    return out
