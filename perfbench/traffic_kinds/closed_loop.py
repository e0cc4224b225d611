"""Closed loop through ``CompiledModel.run``: offline batches and camera
streams.

Parameters (the traffic file): ``batch``, the images per call;
``in_flight``, the most calls dispatched and not yet ready (2 hides the
host behind the device; 1 is a stream, each frame waiting for its output);
``pool_batches``, the seeded host batches the inputs cycle through;
``compare``, ``"all"`` or how many executor calls' answers to keep, drawn
from the seed by reservoir sampling.

End-to-end: ``images_per_s``, the images whose outputs were ready inside
the window over its seconds.
"""
from __future__ import annotations

import math
import time
from collections import deque
from typing import Any, List

import numpy as np

from harness.traffic import Spans, Window, make_pool

clock = time.perf_counter


class Kind:
    def __init__(self, traffic: dict, input_shape, rng, sharding):
        self.batch = int(traffic["batch"])
        self.in_flight = int(traffic["in_flight"])
        self.compare = traffic.get("compare", "all")
        n = int(traffic["pool_batches"])
        self.pool = make_pool(rng, n * self.batch, input_shape).reshape(
            n, self.batch, *input_shape)
        self.sharding = sharding
        self.rng = rng

    def compile_batch(self) -> int:
        return self.batch

    @property
    def pool_images(self) -> np.ndarray:
        return self.pool.reshape(-1, *self.pool.shape[2:])

    def warm(self, compiled) -> None:
        import jax

        for x in self.pool:
            jax.block_until_ready(compiled.run(jax.device_put(x, self.sharding)))

    def drive(self, compiled, seconds: float) -> Window:
        import jax

        keep = math.inf if self.compare == "all" else int(self.compare)
        kept: List[Any] = []    # reservoir of (first image index, device output)
        inflight: deque = deque()
        span = Spans()
        done = dispatched = seen = 0
        t0 = clock()
        t_end = t0 + seconds

        def retire():
            nonlocal done, seen
            k, y = inflight.popleft()
            with span("wait"):
                y.block_until_ready()
            if clock() <= t_end:
                done += self.batch
            item = ((k % len(self.pool)) * self.batch, y)
            if len(kept) < keep:
                kept.append(item)
            else:                       # reservoir sampling, seeded
                j = int(self.rng.integers(0, seen + 1))
                if j < keep:
                    kept[j] = item
            seen += 1

        with span("window"):
            while clock() < t_end:
                x = self.pool[dispatched % len(self.pool)]
                with span("h2d"):
                    xd = jax.device_put(x, self.sharding)
                with span("dispatch"):
                    y = compiled.run(xd)
                inflight.append((dispatched, y))
                dispatched += 1
                while len(inflight) >= self.in_flight:
                    retire()
            while inflight:
                retire()
        answers = []
        for first, y in kept:
            rows = np.asarray(y)
            answers.extend((first + i, rows[i]) for i in range(rows.shape[0]))
        window = Window(
            seconds=seconds, attempted=dispatched * self.batch, completed=done,
            failed=0, forwards=dispatched, answers=answers, spans=span.items,
        )
        window.end_to_end["images_per_s"] = window.images_per_s
        return window
