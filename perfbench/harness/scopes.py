"""Device ops and idle gaps put down to the program's planned layers.

The planned forward wraps each layer's ops in a named scope
(``L{index:03d}.{kind}``, with the algorithm for convs, e.g.
``L000.conv.winograd``; ``exit`` for the exit crop).  XLA keeps the scope in
each instruction's ``op_name``; a fusion takes its root instruction's.  The
profiler writes it, as the ``tf_op`` stat, into the event metadata of the
device plane, which ``jax.profiler.ProfileData`` does not expose: this
module reads the ``.xplane.pb`` with a small protobuf wire-format decoder.

The program also keeps host spans (``run``, ``run.asarray``,
``run.executor``, ``run.call``) on the host's real-time clock, and each
executor's layer table (scope, index, kind, algorithm, ``predicted_s``),
in ``repro.spans``.  The first reader of a run writes them beside the
trace as ``program.json``; they are placed on the trace's clock by the
profile's start time, as the harness's spans are.

The one device op XLA names after the forward's input argument rather than
a scope, the copy that lays the input out for the first layer, counts to
the first layer.  Readers find the run's own trace: the newest
``spans.json`` under the trace root whose ``window`` span gives the
reduced window.  A trace without layer
scopes (a program that sets none, or an executable loaded from a cache
written before it did) reads as None.

    python perfbench/harness/scopes.py <trace dir>   # the per-layer table
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import struct
import sys
from collections import defaultdict
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace as tr  # noqa: E402
from harness.spec import CHECKOUT  # noqa: E402

TRACE_ROOT = os.path.join(CHECKOUT, ".cache", "perfbench", "trace")
PROGRAM_FILE = "program.json"
EXIT = "exit"
#: A layer scope, or the exit crop's, as one component of an op's name.
_SCOPE = re.compile(r"(?:^|/)(L\d{3}\.[A-Za-z0-9_.]+?|exit)(?=/|:|$)")
_LAYER = re.compile(r"^L(\d{3})\.([A-Za-z0-9_]+)(?:\.([A-Za-z0-9_]+))?$")
#: The jitted function an op belongs to: ``jit(<name>)/...``.
_JIT = re.compile(r"^jit\(([^)]*)\)")


# ---------------------------------------------------------------------------
# Protobuf wire format (the parts of XSpace/XPlane this reader needs)


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, lo: int, hi: int) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of a message in ``b[lo:hi]``: an
    int for varint and fixed fields, a (start, end) slice for bytes."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = int.from_bytes(b[i:i + 8], "little")
            i += 8
        elif wt == 5:
            v = int.from_bytes(b[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield f, wt, v


def _str(b: bytes, v: Tuple[int, int]) -> str:
    return b[v[0]:v[1]].decode("utf-8", "replace")


def _map_entries(b: bytes, v) -> Tuple[int, Tuple[int, int]]:
    key, val = 0, (v[0], v[0])
    for f, _, x in _fields(b, *v):
        if f == 1:
            key = x
        elif f == 2:
            val = x
    return key, val


def _stat(b: bytes, v, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """An XStat: (its metadata's name, its value)."""
    mid, val = 0, None
    for f, wt, x in _fields(b, *v):
        if f == 1:
            mid = x
        elif f == 2:                         # double
            val = struct.unpack("<d", x.to_bytes(8, "little"))[0]
        elif f in (3, 7):                    # uint64, ref to a stat name
            val = stat_names.get(x) if f == 7 else x
        elif f == 4:                         # int64
            val = x - (1 << 64) if x >= 1 << 63 else x
        elif f in (5, 6):                    # string, bytes
            val = _str(b, x)
    return stat_names.get(mid, str(mid)), val


@dataclasses.dataclass
class _Plane:
    name: str = ""
    lines: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    event_meta: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    stat_meta: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    stats: List[Tuple[int, int]] = dataclasses.field(default_factory=list)


def _planes(b: bytes) -> Iterator[_Plane]:
    for f, _, v in _fields(b, 0, len(b)):
        if f != 1:
            continue
        p = _Plane()
        for g, _, x in _fields(b, *v):
            if g == 2:
                p.name = _str(b, x)
            elif g == 3:
                p.lines.append(x)
            elif g == 4:
                p.event_meta.append(x)
            elif g == 5:
                p.stat_meta.append(x)
            elif g == 6:
                p.stats.append(x)
        yield p


def _stat_names(b: bytes, p: _Plane) -> Dict[int, str]:
    out = {}
    for e in p.stat_meta:
        k, v = _map_entries(b, e)
        for f, _, x in _fields(b, *v):
            if f == 2:
                out[k] = _str(b, x)
    return out


def _event_meta(b: bytes, p: _Plane, stat_names) -> Dict[int, Tuple[str, str]]:
    """Event metadata id -> (name, the ``tf_op`` stat or "")."""
    out = {}
    for e in p.event_meta:
        k, v = _map_entries(b, e)
        name, tf_op = "", ""
        for f, _, x in _fields(b, *v):
            if f == 2:
                name = _str(b, x)
            elif f == 5:
                sname, sval = _stat(b, x, stat_names)
                if sname == "tf_op" and sval:
                    tf_op = str(sval)
        out[k] = (name, tf_op)
    return out


# ---------------------------------------------------------------------------
# A trace with each device op's layer scope


@dataclasses.dataclass(frozen=True)
class ScopedOp:
    label: str              # ``trace.op_label`` of the op's HLO text
    start_ns: float
    end_ns: float
    kernel: bool
    tf_op: str
    scope: Optional[str]    # layer scope or ``exit``; None outside any


def scope_of(tf_op: str) -> Optional[str]:
    """The one layer scope (or ``exit``) an op's name carries, else None."""
    found = set(_SCOPE.findall(tf_op))
    return found.pop() if len(found) == 1 else None


def parse_scope(scope: str) -> Tuple[int, str, Optional[str]]:
    """``L000.conv.winograd`` -> (0, "conv", "winograd")."""
    m = _LAYER.match(scope)
    if not m:
        raise ValueError(f"not a layer scope: {scope!r}")
    return int(m.group(1)), m.group(2), m.group(3)


@dataclasses.dataclass
class LayerTime:
    scope: str
    kernel_s: float = 0.0          # Pallas kernel time in the window, all chips
    glue_s: float = 0.0            # other device time in the window, all chips
    top_op: str = ""
    top_s: float = 0.0

    @property
    def total_s(self) -> float:
        return self.kernel_s + self.glue_s

    @property
    def index(self) -> Optional[int]:
        return parse_scope(self.scope)[0] if self.scope != EXIT else None

    @property
    def kind(self) -> str:
        return parse_scope(self.scope)[1] if self.scope != EXIT else EXIT

    @property
    def algorithm(self) -> Optional[str]:
        return parse_scope(self.scope)[2] if self.scope != EXIT else None


@dataclasses.dataclass
class Scoped:
    ops: Dict[str, List[ScopedOp]]        # device plane -> ops by start
    spans: List[Tuple[str, float, float]]  # the harness's, on the trace clock
    program_spans: List[Tuple[str, float, float]]   # the program's, ditto
    layer_tables: List[dict]               # registered, oldest first

    @property
    def window(self) -> Tuple[float, float]:
        return tr.Trace({}, self.spans).window

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def _inside(self):
        lo, hi = self.window
        for plane, ops in self.ops.items():
            for o in ops:
                d = min(o.end_ns, hi) - max(o.start_ns, lo)
                if d > 0:
                    yield plane, o, d / 1e9

    @property
    def has_scopes(self) -> bool:
        return any(o.scope is not None for _, o, _ in self._inside())

    def layers(self) -> Dict[str, LayerTime]:
        """Device time in the window per scope, all chips, in layer order
        (``exit`` last); ops outside any scope are left out."""
        out: Dict[str, LayerTime] = {}
        per_op: Dict[Tuple[str, str], float] = defaultdict(float)
        for _, o, d in self._inside():
            if o.scope is None:
                continue
            t = out.setdefault(o.scope, LayerTime(o.scope))
            if o.kernel:
                t.kernel_s += d
            else:
                t.glue_s += d
            per_op[(o.scope, o.label)] += d
        for (scope, label), d in per_op.items():
            if d > out[scope].top_s:
                out[scope].top_op, out[scope].top_s = label, d
        return dict(sorted(out.items(), key=lambda kv: (kv[0] == EXIT, kv[0])))

    def coverage(self) -> float:
        """The share of device time in the window that lies in a scope."""
        total = scoped = 0.0
        for _, o, d in self._inside():
            total += d
            scoped += d if o.scope is not None else 0.0
        return scoped / total if total else 0.0

    def forward_name(self) -> Optional[str]:
        """The jitted function that ran the most scoped device time."""
        by: Dict[str, float] = defaultdict(float)
        for _, o, d in self._inside():
            m = _JIT.match(o.tf_op)
            if o.scope is not None and m:
                by[m.group(1)] += d
        return max(by, key=by.get) if by else None

    def layer_table(self) -> Optional[dict]:
        """The newest registered table of the forward that ran."""
        name = self.forward_name()
        tables = [t for t in self.layer_tables if t.get("name") == name]
        return tables[-1] if tables else None

    def idle_in_spans(self, name: str) -> Dict[str, float]:
        """Per device plane, the seconds of the window in which no op ran
        and the program was inside a span called ``name``."""
        lo, hi = self.window
        spans = _merge([(a, b) for n, a, b in self.program_spans if n == name],
                       lo, hi)
        out = {}
        for plane, ops in self.ops.items():
            busy = _merge([(o.start_ns, o.end_ns) for o in ops], lo, hi)
            span_s = sum(b - a for a, b in spans)
            out[plane] = (span_s - _overlap(spans, busy)) / 1e9
        return out


def _merge(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Sorted, disjoint intervals covering ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(xs, ys) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _place(spans, start_ns: int) -> List[Tuple[str, float, float]]:
    return [(n, a - start_ns, b - start_ns) for n, a, b in spans]


def decode(xplane: str) -> Tuple[Dict[str, List[ScopedOp]], Optional[int]]:
    """Every device plane's ``XLA Ops`` events with their ``tf_op`` and
    scope, and the profile's start time."""
    with open(xplane, "rb") as f:
        b = f.read()
    ops: Dict[str, List[ScopedOp]] = {}
    start_ns = None
    for p in _planes(b):
        names = _stat_names(b, p)
        if not tr.DEVICE_PLANE.match(p.name):
            for s in p.stats:
                k, v = _stat(b, s, names)
                if k == "profile_start_time" and start_ns is None:
                    start_ns = v
            continue
        meta = _event_meta(b, p, names)
        evs: List[ScopedOp] = []
        for line in p.lines:
            lname, ts, events = "", 0, []
            for f, _, x in _fields(b, *line):
                if f == 2:
                    lname = _str(b, x)
                elif f == 3:
                    ts = x
                elif f == 4:
                    events.append(x)
            if lname != tr.OP_LINE:
                continue
            for e in events:
                mid = off = dur = 0
                for f, _, x in _fields(b, *e):
                    if f == 1:
                        mid = x
                    elif f == 2:
                        off = x
                    elif f == 3:
                        dur = x
                text, tf_op = meta.get(mid, ("", ""))
                start = ts + off // 1000      # whole ns, as ProfileData gives
                evs.append(ScopedOp(tr.op_label(text), float(start),
                                    float(start + dur // 1000),
                                    tr.is_kernel(text), tf_op, scope_of(tf_op)))
        ops[p.name] = sorted(evs, key=lambda o: o.start_ns)
    return ops, start_ns


def load(trace_dir: str) -> Scoped:
    """A trace directory's device ops with their scopes, the harness's
    ``spans.json`` and the program's ``program.json`` (where there is one),
    all on the trace's clock."""
    ops, start_ns = decode(tr.find_xplane(trace_dir))
    if start_ns is None:
        raise ValueError(f"{trace_dir}: no profile_start_time to place host spans")
    with open(os.path.join(trace_dir, tr.SPANS_FILE)) as f:
        spans = _place(json.load(f), start_ns)
    program = {"spans": [], "layer_tables": []}
    prog_path = os.path.join(trace_dir, PROGRAM_FILE)
    if os.path.exists(prog_path):
        with open(prog_path) as f:
            program = json.load(f)
    sc = Scoped(ops, spans, _place(program["spans"], start_ns),
                program["layer_tables"])
    _scope_input(sc)
    return sc


def _scope_input(sc: Scoped) -> None:
    """Put the copy that lays the forward's input out for its first layer
    down to that layer.  XLA names the copy after the input argument (the
    layer table's ``input``), not after a scope; the first layer's own
    entry pad already sits in its scope."""
    table = sc.layer_table()
    if not table or not table.get("input") or not table["layers"]:
        return
    arg, first = table["input"], table["layers"][0]["scope"]
    for plane, ops in sc.ops.items():
        sc.ops[plane] = [
            dataclasses.replace(o, scope=first)
            if o.scope is None and o.tf_op.split(":")[0] == arg else o
            for o in ops]


def _save_program(trace_dir: str) -> None:
    """Write the running program's record beside its trace, kept to the
    spans that meet the window; nothing where the program keeps none."""
    try:
        from repro import spans as program
    except ImportError:
        return
    tables = program.RECORD.layer_tables()
    if not tables:
        return
    with open(os.path.join(trace_dir, tr.SPANS_FILE)) as f:
        window = [(a, b) for n, a, b in json.load(f) if n == "window"]
    lo, hi = min(a for a, _ in window), max(b for _, b in window)
    kept = [s for s in program.RECORD.spans() if s[2] >= lo and s[1] <= hi]
    with open(os.path.join(trace_dir, PROGRAM_FILE), "w") as f:
        json.dump({"spans": kept, "layer_tables": tables}, f)


_cache: Dict[Tuple[str, float], Scoped] = {}


def for_run(ctx) -> Optional[Scoped]:
    """The run's own trace, decoded once: the newest ``spans.json`` under
    the trace root, taken only if its window is the reduced one; None
    where there is none or it holds no layer scope.  The running program's
    record is written beside it first, as ``program.json``."""
    found = sorted(glob.glob(os.path.join(TRACE_ROOT, "*", tr.SPANS_FILE)),
                   key=os.path.getmtime)
    if not found or ctx.reduced is None:
        return None
    trace_dir = os.path.dirname(found[-1])
    key = (trace_dir, os.path.getmtime(found[-1]))
    if key not in _cache:
        _cache.clear()
        if not os.path.exists(os.path.join(trace_dir, PROGRAM_FILE)):
            _save_program(trace_dir)
        _cache[key] = load(trace_dir)
    sc = _cache[key]
    if abs(sc.window_s - ctx.reduced.window_s) > 1e-6 or not sc.has_scopes:
        return None
    return sc


# ---------------------------------------------------------------------------
# What the readers share


def family_roofline(ctx, algorithms: Sequence[str]) -> Optional[float]:
    """The least time (``harness.work``) of the convs whose scope names one
    of ``algorithms``, over the Pallas kernel time inside those scopes, per
    forward per chip, in %."""
    sc = for_run(ctx)
    if sc is None or not ctx.window.forwards:
        return None
    rows = [t for t in sc.layers().values()
            if t.kind == "conv" and t.algorithm in algorithms and t.kernel_s > 0]
    if not rows:
        return None
    per_chip_batch = ctx.batch // ctx.chips
    least = sum(ctx.work[t.index].least_time(per_chip_batch, ctx.dtypes[t.index],
                                             ctx.peaks) for t in rows)
    kernel = sum(t.kernel_s for t in rows) / (ctx.chips * ctx.window.forwards)
    return 100.0 * least / kernel


def main(argv: Sequence[str]) -> int:
    sc = load(argv[0])
    lo, hi = sc.window
    forwards = sum(1 for n, a, b in sc.program_spans
                   if n == "run.call" and lo <= a < hi) or None
    per = "per forward" if forwards else "over the window"
    print(f"window {sc.window_s:.6f} s; forward {sc.forward_name()}; "
          f"executor calls in the window {forwards}; device time in a scope "
          f"{100 * sc.coverage():.3f}%; times {per}, all chips")
    print(f"{'scope':<24} {'kernel ms':>10} {'glue ms':>10}  top op (ms)")
    ms = 1e3 / (forwards or 1)
    for t in sc.layers().values():
        print(f"{t.scope:<24} {ms * t.kernel_s:>10.4f} {ms * t.glue_s:>10.4f}  "
              f"{t.top_op} ({ms * t.top_s:.4f})")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
