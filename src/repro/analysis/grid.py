"""Kernel-interior grid analysis: the machinery behind the ``kernel`` rung.

The plan-level verifier (PR 7) treats each ``pallas_call`` as a black box —
it proves byte budgets and boundary contracts but not that the BlockSpec
tiling itself is *sound*.  This module recovers, from the traced pallas_call
parameters alone (no execution), the facts the kernel-interior passes gate
on:

- **Affine index-map recovery** (``affine_index_map``): every index map is
  a jaxpr over the grid indices; evaluating it concretely at the zero
  vector, the unit vectors and the grid corners either certifies an exact
  affine form ``idx = c0 + A @ program_ids`` or reports the map non-affine.
  Affinity is what turns box-wide claims into corner checks: an affine
  function over an integer box attains each output coordinate's extremes at
  the box corners, so bounds proofs need only ``2^n`` evaluations
  (``n = len(grid) <= 4`` here, i.e. at most 16 points).

- **Injectivity certificates** (``injectivity_witness``): an output index
  map restricted to its varying grid axes is injective iff its coefficient
  columns are linearly independent (exact rational rank, no floats).  On
  rank deficiency the search for an integer null vector inside the grid box
  produces a concrete two-program collision witness when one exists.

- **Block-window bounds** (``window_violations``): for affine maps, each
  ``index_map x block_shape`` window is checked at every grid corner
  against the (padded) operand bounds; non-affine maps fall back to full
  grid enumeration when the grid is small enough, else the claim is
  reported unprovable (a warning, never a silent pass).

- **Guard recovery** (``ref_accesses``): ``pl.when(pl.program_id(a) == s)``
  traces to a ``cond`` whose predicate chains back through
  ``convert_element_type`` to ``eq(program_id[axis=a], literal)`` — note
  the *last-step* literal, because ``pl.num_programs`` folds at trace time.
  Walking the kernel jaxpr with that resolution yields every read/write of
  every kernel ref together with its enclosing guard stack, which is what
  the race pass (guarded flush) and the accumulator pass (read-before-init)
  interrogate.

Everything returns plain data; the gating policy lives in
``analysis.passes``.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from fractions import Fraction
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.analysis.trace import PallasCallRecord, _is_literal, _subjaxprs

#: Full-grid enumeration ceiling for non-affine index maps — beyond this the
#: bounds claim is reported unprovable instead of silently sampled.
MAX_ENUM_POINTS = 4096

#: Ref-access primitive families inside Pallas kernel jaxprs.
_READ_PRIMS = ("get", "load", "masked_load")
_WRITE_PRIMS = ("swap", "store", "masked_swap")


def eval_index_map(index_map_jaxpr, point: Sequence[int]) -> Tuple[int, ...]:
    """Evaluate an index-map ClosedJaxpr at one concrete grid point."""
    from jax.core import eval_jaxpr

    out = eval_jaxpr(
        index_map_jaxpr.jaxpr, index_map_jaxpr.consts,
        *[int(p) for p in point],
    )
    return tuple(int(v) for v in out)


@dataclasses.dataclass(frozen=True)
class AffineMap:
    """Certified affine form of an index map: ``idx = offset + coeffs @ p``.

    ``coeffs[d][a]`` is output dimension ``d``'s coefficient on grid axis
    ``a``.  Only constructed after verification at every grid corner plus
    the box midpoint, so ``apply`` is exact on the whole grid box.
    """

    offset: Tuple[int, ...]
    coeffs: Tuple[Tuple[int, ...], ...]

    def apply(self, point: Sequence[int]) -> Tuple[int, ...]:
        return tuple(
            c0 + sum(c * int(p) for c, p in zip(row, point))
            for c0, row in zip(self.offset, self.coeffs)
        )


def grid_corners(grid: Sequence[int]) -> List[Tuple[int, ...]]:
    """The ``2^n`` extreme points of the grid box (deduplicated for
    extent-1 axes)."""
    axes = [(0,) if g <= 1 else (0, int(g) - 1) for g in grid]
    return list(itertools.product(*axes))


def affine_index_map(index_map_jaxpr, grid: Sequence[int]) -> Optional[AffineMap]:
    """Recover and certify the affine form of an index map, or None.

    Probes the map at the zero vector and the unit vectors to read off the
    offset and coefficient columns, then verifies the resulting affine form
    at every grid corner and at the box midpoint.  A disagreement anywhere
    means the map is not affine over the box (e.g. uses mod/div of a
    program id) and the caller must fall back to enumeration.
    """
    if index_map_jaxpr is None:
        return None
    n = len(grid)
    try:
        zero = eval_index_map(index_map_jaxpr, (0,) * n)
        cols = []
        for a in range(n):
            unit = tuple(1 if i == a else 0 for i in range(n))
            probe = eval_index_map(index_map_jaxpr, unit)
            cols.append(tuple(p - z for p, z in zip(probe, zero)))
        amap = AffineMap(
            offset=zero,
            coeffs=tuple(
                tuple(cols[a][d] for a in range(n)) for d in range(len(zero))
            ),
        )
        mid = tuple(int(g) // 2 for g in grid)
        for pt in grid_corners(grid) + [mid]:
            if amap.apply(pt) != eval_index_map(index_map_jaxpr, pt):
                return None
    except Exception:
        return None
    return amap


def _rational_rank(vectors: Sequence[Sequence[int]]) -> int:
    """Exact rank of a set of integer vectors (Gaussian elimination over Q)."""
    rows = [[Fraction(x) for x in v] for v in vectors]
    if not rows:
        return 0
    rank = 0
    for c in range(len(rows[0])):
        piv = next((r for r in range(rank, len(rows)) if rows[r][c]), None)
        if piv is None:
            continue
        rows[rank], rows[piv] = rows[piv], rows[rank]
        for r in range(len(rows)):
            if r != rank and rows[r][c]:
                f = rows[r][c] / rows[rank][c]
                rows[r] = [x - f * y for x, y in zip(rows[r], rows[rank])]
        rank += 1
        if rank == len(rows):
            break
    return rank


def injectivity_witness(
    amap: AffineMap, grid: Sequence[int], axes: Sequence[int],
) -> Tuple[str, Optional[Tuple[Tuple[int, ...], Tuple[int, ...]]]]:
    """Is the affine map injective over the given grid axes' box?

    Returns ``("injective", None)`` when the coefficient columns on ``axes``
    are linearly independent (a proof — independent columns are injective
    over the whole integer lattice, a fortiori over the box).  On rank
    deficiency, searches bounded integer null vectors for a concrete
    collision: ``("collision", (p, q))`` gives two distinct grid points
    whose output block indices coincide.  ``("unknown", None)`` means rank
    deficiency without a witness inside the search window — possible for
    maps with large coprime coefficients, never for the projection maps
    real kernels use.
    """
    axes = [a for a in axes if grid[a] > 1]
    if not axes:
        return "injective", None
    columns = [
        [amap.coeffs[d][a] for d in range(len(amap.offset))] for a in axes
    ]
    if _rational_rank(columns) == len(axes):
        return "injective", None
    search = [
        range(-(min(int(grid[a]) - 1, 3)), min(int(grid[a]) - 1, 3) + 1)
        for a in axes
    ]
    for d in itertools.product(*search):
        if not any(d):
            continue
        if all(
            sum(col[dim] * dd for col, dd in zip(columns, d)) == 0
            for dim in range(len(amap.offset))
        ):
            p = [0] * len(grid)
            q = [0] * len(grid)
            for a, dd in zip(axes, d):
                p[a] = max(0, -dd)
                q[a] = p[a] + dd
            return "collision", (tuple(p), tuple(q))
    return "unknown", None


@dataclasses.dataclass(frozen=True)
class WindowViolation:
    """One block window escaping its operand's (padded) bounds."""

    point: Tuple[int, ...]        # the offending grid point
    dim: int                      # operand dimension
    start: int                    # window start element (inclusive)
    stop: int                     # window stop element (exclusive)
    extent: int                   # operand extent along dim


def window_violations(
    op, grid: Sequence[int],
) -> Tuple[List[WindowViolation], bool]:
    """(violations, proved) for one operand's block windows over the grid.

    Affine maps are checked at the grid corners only — exact, because each
    window-start coordinate is affine in the program ids and so attains its
    extremes at box corners.  Non-affine maps enumerate the full grid when
    it has at most ``MAX_ENUM_POINTS`` points; otherwise ``proved`` is
    False and the caller should report the claim unprovable.
    """
    amap = affine_index_map(op.index_map_jaxpr, grid)
    if amap is not None:
        points = grid_corners(grid)
        evaluate = amap.apply
    else:
        if op.index_map_jaxpr is None or math.prod(grid) > MAX_ENUM_POINTS:
            return [], False
        points = list(itertools.product(*[range(int(g)) for g in grid]))
        evaluate = lambda pt: eval_index_map(op.index_map_jaxpr, pt)  # noqa: E731
    element = tuple(op.element_dims) or (False,) * len(op.block_shape)
    violations: List[WindowViolation] = []
    for pt in points:
        idx = evaluate(pt)
        for d, (i, bs, n, elem) in enumerate(
            zip(idx, op.block_shape, op.array_shape, element)
        ):
            start = int(i) if elem else int(i) * int(bs)
            if start < 0 or start + int(bs) > int(n):
                violations.append(WindowViolation(
                    point=tuple(pt), dim=d,
                    start=start, stop=start + int(bs), extent=int(n),
                ))
    return violations, True


# ---------------------------------------------------------------------------
# Guard recovery: pl.when predicates and per-ref access order


@dataclasses.dataclass(frozen=True)
class Guard:
    """One resolved ``pl.when(pl.program_id(axis) == step)`` predicate.

    ``negated`` marks accesses on the *false* branch of the cond (pl.when's
    false branch is empty, but the walk is generic).
    """

    axis: int
    step: int
    negated: bool = False


@dataclasses.dataclass(frozen=True)
class RefAccess:
    """One read or write of a kernel ref, in program order.

    ``guards`` is the stack of resolved enclosing predicates; ``opaque`` is
    True when some enclosing cond's predicate resisted resolution, so the
    access's guard condition is not fully known (passes report that as a
    warning, never as a silent pass).
    """

    ref: int                      # position in the kernel jaxpr's invars
    kind: str                     # "read" | "write"
    guards: Tuple[Guard, ...]
    opaque: bool = False


def _resolve_guard(var, producers: Dict[int, Any]) -> Optional[Guard]:
    """Chase a cond predicate back to ``eq(program_id[axis], literal)``."""
    for _ in range(8):              # bounded chase; chains are short
        if _is_literal(var):
            return None
        eqn = producers.get(id(var))
        if eqn is None:
            return None
        if eqn.primitive.name == "convert_element_type":
            var = eqn.invars[0]
            continue
        if eqn.primitive.name == "eq":
            a, b = eqn.invars
            for x, y in ((a, b), (b, a)):
                if _is_literal(x) or not _is_literal(y):
                    continue
                pe = producers.get(id(x))
                if pe is not None and pe.primitive.name == "program_id":
                    return Guard(
                        axis=int(pe.params["axis"]), step=int(y.val)
                    )
            return None
        return None
    return None


def _access_walk(
    jaxpr,
    refmap: Dict[int, int],
    guards: Tuple[Guard, ...],
    opaque: bool,
    out: List[RefAccess],
) -> None:
    producers = {id(ov): e for e in jaxpr.eqns for ov in e.outvars}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "cond":
            g = _resolve_guard(eqn.invars[0], producers)
            for bi, branch in enumerate(eqn.params["branches"]):
                bjx = branch.jaxpr
                sub_ref = {
                    id(sv): refmap[id(ev)]
                    for sv, ev in zip(bjx.invars, eqn.invars[1:])
                    if not _is_literal(ev) and id(ev) in refmap
                }
                if g is None:
                    _access_walk(bjx, sub_ref, guards, True, out)
                else:
                    bg = g if bi == 1 else dataclasses.replace(
                        g, negated=True
                    )
                    _access_walk(bjx, sub_ref, guards + (bg,), opaque, out)
            continue
        if name in _READ_PRIMS or name in _WRITE_PRIMS:
            v = eqn.invars[0]
            if not _is_literal(v) and id(v) in refmap:
                out.append(RefAccess(
                    ref=refmap[id(v)],
                    kind="read" if name in _READ_PRIMS else "write",
                    guards=guards,
                    opaque=opaque,
                ))
            continue
        for sub in _subjaxprs(eqn.params):
            if len(sub.invars) != len(eqn.invars):
                continue
            sub_ref = {
                id(sv): refmap[id(ev)]
                for sv, ev in zip(sub.invars, eqn.invars)
                if not _is_literal(ev) and id(ev) in refmap
            }
            _access_walk(sub, sub_ref, guards, opaque, out)


def ref_accesses(record: PallasCallRecord) -> List[RefAccess]:
    """Every read/write of every kernel ref, in program order, with guards.

    Ref positions follow the kernel jaxpr's invars: inputs, then outputs,
    then scratch — so output ``i`` is ref ``len(inputs) + i`` and scratch
    ``j`` is ref ``len(inputs) + len(outputs) + j``.
    """
    jx = record.kernel_jaxpr
    refmap = {id(v): i for i, v in enumerate(jx.invars)}
    out: List[RefAccess] = []
    _access_walk(jx, refmap, (), False, out)
    return out


def reduction_axes(record: PallasCallRecord, out_op) -> Tuple[int, ...]:
    """Grid axes with more than one step absent from an output's index map —
    the axes over which the kernel must be accumulating, not racing."""
    return tuple(
        a for a in range(len(record.grid))
        if record.grid[a] > 1 and a not in out_op.dep_axes
    )
