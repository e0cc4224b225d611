"""The one general traffic generator: a mix is a data file of parameters
(``perfbench/traffic/<name>.json``) whose ``kind`` names the loop that
drives it, ``perfbench/traffic_kinds/<kind>.py``.  A kind file defines
``Kind(traffic, input_shape, rng, sharding)`` with

- ``compile_batch()``: the batch the program is compiled for;
- ``pool_images``: every image the mix can send, (N, H, W, C) on the host;
- ``warm(compiled)``: one call of every shape the mix uses (set-up);
- ``drive(compiled, seconds) -> Window``: the measured window, whose
  ``end_to_end`` holds the end-to-end metrics the kind measures, by name.

Adding a kind adds a file; nothing here changes.  Host spans (``Spans``)
mark what the host is doing, so idle gaps in a device trace can be put
down to it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import os
import time
from typing import Any, Dict, List

import numpy as np

KINDS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "traffic_kinds")


@dataclasses.dataclass
class Window:
    seconds: float                   # length of the measured window
    attempted: int                   # images (requests) due in the window
    completed: int                   # of those, ready inside the window
    failed: int                      # failed or never answered
    forwards: int                    # executor calls dispatched
    #: (image's index in ``pool_images``, output row on the host) of each
    #: answer compared with the reference
    answers: List[Any]
    #: the end-to-end metrics this kind measures, by name (``setup_s`` is
    #: the harness's own)
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: whatever else the kind measured, for per-layer readers
    details: Dict[str, Any] = dataclasses.field(default_factory=dict)
    spans: List[Any] = dataclasses.field(default_factory=list)

    @property
    def images_per_s(self) -> float:
        return self.completed / self.seconds


class Spans:
    """The harness's host spans, ``(name, start, end)`` in nanoseconds of
    the host's real-time clock, the clock the profiler stamps its trace
    with.  Recorded here rather than as profiler annotations: the profiler's
    host tracer, which annotations need, also records ~10^5 runtime events
    per input transfer and slows a traced run several times over."""

    def __init__(self):
        self.items: List[Any] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.items.append((name, t0, time.time_ns()))


def make_pool(rng: np.random.Generator, n: int, shape) -> np.ndarray:
    return rng.standard_normal((n, *shape), dtype=np.float32)


def known_kinds() -> List[str]:
    return sorted(os.path.basename(p)[:-3]
                  for p in glob.glob(os.path.join(KINDS_DIR, "*.py")))


def make_traffic(traffic: dict, input_shape, rng, sharding):
    """The loop of ``traffic['kind']``, found by name in ``traffic_kinds/``."""
    kind = traffic.get("kind")
    path = os.path.join(KINDS_DIR, f"{kind}.py")
    if not isinstance(kind, str) or not os.path.isfile(path):
        raise ValueError(f"unknown traffic kind {kind!r} (known: {known_kinds()})")
    spec = importlib.util.spec_from_file_location(f"perfbench_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Kind(traffic, input_shape, rng, sharding)
