"""Reduction of a profiler trace (``.xplane.pb``) to device-op events, busy
time, the Pallas-kernel/XLA split, idle gaps and a breakdown.

Device planes are ``/device:TPU:<n>``; their op line is ``XLA Ops``, one
event per HLO instruction run, named by the instruction's HLO text.  An op
is a Pallas kernel when it is a Mosaic custom call (``tpu_custom_call``).
Host spans are the harness's own (``traffic.Spans``), kept in ``spans.json``
beside the trace on the host's real-time clock and put on the trace's clock
by the profile's start time; the ``window`` span bounds the measured window.

    python perfbench/harness/trace.py <trace dir or .xplane.pb>   # dump
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SPANS_FILE = "spans.json"
#: A Mosaic (Pallas) kernel: an HLO custom call to ``tpu_custom_call``.
_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
#: "%name = type opcode(" at the head of an op's HLO text.
_HEAD = re.compile(r"^%(\S+) = (\S+?)(\{[^}]*\})? ([\w-]+)\(")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    end_ns: float
    kernel: bool


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]                 # device plane -> ops by start
    spans: List[Tuple[str, float, float]]    # (name, start_ns, end_ns)

    @property
    def window(self) -> Tuple[float, float]:
        w = [(a, b) for n, a, b in self.spans if n == "window"]
        if not w:
            raise ValueError("trace has no 'window' host span")
        return min(a for a, _ in w), max(b for _, b in w)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def is_kernel(hlo_text: str) -> bool:
    return bool(_KERNEL.search(hlo_text))


def op_label(hlo_text: str) -> str:
    """A short name for an op: ``<instruction> <opcode> <result type>``,
    with ``pallas`` as the opcode of a Mosaic kernel, layouts dropped."""
    m = _HEAD.match(hlo_text)
    if not m:
        m = re.match(r"^%(\S+) = .*? ([\w-]+)\(", hlo_text)
        return f"{m.group(1)} {m.group(2)}" if m else hlo_text[:80]
    name, typ, _layout, opcode = m.groups()
    return f"{name} {'pallas' if is_kernel(hlo_text) else opcode} {typ}"


def _plane_stat(plane, key: str):
    for k, v in plane.stats:
        if k == key:
            return v
    return None


def load(path: str) -> Trace:
    """The device ops of a trace directory (or file), with the host spans
    of ``spans.json`` beside it put on the trace's clock."""
    from jax.profiler import ProfileData

    xplane = find_xplane(path)
    pd = ProfileData.from_file(xplane)
    ops: Dict[str, List[Op]] = {}
    start_ns = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for e in line.events:
                    name = e.name
                    evs.append(Op(op_label(name), e.start_ns,
                                  e.start_ns + e.duration_ns, is_kernel(name)))
            ops[plane.name] = sorted(evs, key=lambda o: o.start_ns)
        elif start_ns is None:
            start_ns = _plane_stat(plane, "profile_start_time")
    spans: List[Tuple[str, float, float]] = []
    spans_file = os.path.join(path if os.path.isdir(path) else os.path.dirname(xplane),
                              SPANS_FILE)
    if os.path.exists(spans_file):
        if start_ns is None:
            raise ValueError(f"{xplane}: no profile_start_time to place host spans")
        with open(spans_file) as f:
            spans = [(n, a - start_ns, b - start_ns) for n, a, b in json.load(f)]
    return Trace(ops, spans)


def union_ns(intervals: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total = 0.0
    cur_a = cur_b = None
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: Dict[str, float]        # per device plane, union of op intervals
    kernel_s: Dict[str, float]      # per device plane, union of kernel ops
    kernel_events: int
    device_ops: List[Tuple[str, float]]    # top ops by summed time, all chips
    idle_gaps: List[Tuple[str, float]]     # longest gaps, named by host span
    first_op_after_window_s: float         # a check of the clocks' alignment

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def total_kernel_s(self) -> float:
        return sum(self.kernel_s.values())

    @property
    def total_busy_s(self) -> float:
        return sum(self.busy_s.values())


def _host_activity(spans, t: float) -> str:
    """The innermost harness span (other than the window) covering ``t``."""
    best = None
    for n, a, b in spans:
        if n != "window" and a <= t <= b and (best is None or b - a < best[1]):
            best = (n, b - a)
    return best[0] if best else "host"


def reduce(trace: Trace, top: int = 10) -> Reduced:
    lo, hi = trace.window
    busy, kern = {}, {}
    per_name: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    n_kernel = 0
    for plane, ops in trace.ops.items():
        inside = [o for o in ops if o.end_ns > lo and o.start_ns < hi]
        busy[plane] = union_ns([(o.start_ns, o.end_ns) for o in inside], lo, hi) / 1e9
        kern[plane] = union_ns([(o.start_ns, o.end_ns) for o in inside if o.kernel],
                               lo, hi) / 1e9
        n_kernel += sum(o.kernel for o in inside)
        for o in inside:
            per_name[o.name] += (min(o.end_ns, hi) - max(o.start_ns, lo)) / 1e9
        edge = lo
        for o in inside:
            if o.start_ns > edge:
                gaps.append(((o.start_ns - edge) / 1e9,
                             _host_activity(trace.spans, (o.start_ns + edge) / 2)))
            edge = max(edge, o.end_ns)
        if hi > edge:
            gaps.append(((hi - edge) / 1e9, _host_activity(trace.spans, (hi + edge) / 2)))
    if not busy:
        raise ValueError("trace has no TPU device plane")
    gaps.sort(reverse=True)
    first = min((o.start_ns for ops in trace.ops.values() for o in ops
                 if o.start_ns >= lo), default=hi)
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=busy, kernel_s=kern,
        kernel_events=n_kernel,
        device_ops=sorted(per_name.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[(name, s) for s, name in gaps[:top]],
        first_op_after_window_s=(first - lo) / 1e9,
    )


def dump(path: str, limit: int = 40) -> dict:
    """Planes, lines and the heaviest events with their stats: for looking
    at a trace by hand."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    out = {"planes": []}
    for plane in pd.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            tot: Dict[str, List] = {}
            for e in evs:
                t = tot.setdefault(e.name, [0.0, 0, str(list(e.stats))[:600]])
                t[0] += e.duration_ns
                t[1] += 1
            heavy = sorted(tot.items(), key=lambda kv: -kv[1][0])[:limit]
            p["lines"].append({"name": line.name, "events": len(evs),
                               "heaviest": [[k, *v] for k, v in heavy]})
        out["planes"].append(p)
    return out


if __name__ == "__main__":
    json.dump(dump(sys.argv[1]), sys.stdout, indent=1)
    print()
