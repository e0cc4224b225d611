"""residual_share (%): device time of the residual path over device busy
time, all chips together.

The program's layer table marks each step's ``residual`` role.  Counted
are all device time inside the scopes of steps marked ``"add"`` (a
shortcut: the add, its activation and any crop XLA fuses into it) and
``"branch"`` (a route), and, inside the scopes of steps marked
``"source"`` (a step whose output a later shortcut or route reads), the
crop that such a reference forces: a ``slice`` to fewer channels than the
scope's kernel writes.  Not counted: the rest of a source's scope (its
kernel, its input padding and stride-phase split, row crops, a pool), and
the re-pads in the consumers' scopes, which XLA merges with their own conv
padding.  None where the table marks no role (a program that keeps none).
Moves ``images_per_s``.
"""
import re
from collections import defaultdict

from harness import scopes

#: The result type at the end of an op's label: ``f32[64,56,56,64]``.
_SHAPE = re.compile(r"\[([\d,]+)\]$")


def _channels(label: str):
    """The last dimension of an op's result, None without one."""
    m = _SHAPE.search(label)
    return int(m.group(1).rsplit(",", 1)[-1]) if m else None


def _primitive(tf_op: str) -> str:
    """The JAX primitive that named an op: ``jit(f)/L001.x/slice:`` ->
    ``slice``."""
    return tf_op.split(":", 1)[0].rsplit("/", 1)[-1]


def read(ctx):
    sc = scopes.for_run(ctx)
    table = sc.layer_table() if sc is not None else None
    busy = ctx.reduced.total_busy_s if ctx.reduced is not None else 0.0
    if table is None or busy <= 0 or not any("residual" in l
                                             for l in table["layers"]):
        return None
    role = {l["scope"]: l.get("residual") for l in table["layers"]}
    lo, hi = sc.window
    t = 0.0
    written = defaultdict(int)      # source scope -> channels its kernel writes
    slices = []                     # (source scope, channels, seconds)
    for ops in sc.ops.values():
        for o in ops:
            d = (min(o.end_ns, hi) - max(o.start_ns, lo)) / 1e9
            r = role.get(o.scope)
            if d <= 0 or r is None:
                continue
            if r in ("add", "branch"):
                t += d
            elif r == "source" and o.kernel:
                written[o.scope] = max(written[o.scope],
                                       _channels(o.label) or 0)
            elif r == "source" and _primitive(o.tf_op) == "slice":
                slices.append((o.scope, _channels(o.label), d))
    t += sum(d for scope, c, d in slices
             if c is not None and c < written[scope])
    return 100.0 * t / busy
