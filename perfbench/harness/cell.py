"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the metrics.

Set-up (``setup_s``) runs from process start to the window's start: seeded
weights made on the device in one jitted call, plan + prepare through
``repro.compile``, and one call of every shape the cell's traffic uses.
The reference runs after the window, once the program's state is freed,
and is not counted in ``setup_s``.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Callable, Dict, Optional

import numpy as np

from harness import compare, trace as tr
from harness.device import PLAN_CACHE, CompileCounter
from harness.spec import CHECKOUT, Cell, readers, reference_module
from harness.traffic import make_traffic
from harness.work import network_work

clock = time.perf_counter
TRACE_ROOT = os.path.join(CHECKOUT, ".cache", "perfbench", "trace")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed_streams(seed: int):
    """(jax key for weights, numpy generator for inputs and arrivals) from
    any non-negative whole number, 64 bits and beyond."""
    import jax
    import jax.numpy as jnp

    w, x = np.random.SeedSequence(int(seed)).spawn(2)
    key = jax.random.wrap_key_data(jnp.asarray(w.generate_state(2), jnp.uint32))
    return key, np.random.default_rng(x)


def _layer_table(cfg: dict):
    from repro.models.cnn import CNNLayer

    return tuple(
        CNNLayer(**{k: tuple(v) if isinstance(v, list) else v for k, v in l.items()})
        for l in cfg["layers"]
    )


def input_sharding(devices):
    """Where a host batch is put: one chip, or split over the chips along
    the batch."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(devices) == 1:
        return jax.sharding.SingleDeviceSharding(devices[0])
    return NamedSharding(Mesh(np.array(devices), ("batch",)), P("batch"))


def _layer_dtypes(compiled, batch: int, cfg: dict, n_layers: int):
    """Each layer's execution dtype as the plan resolved it (conv layers),
    the configuration's dtype elsewhere."""
    dtypes = [cfg["dtype"]] * n_layers
    for row in compiled.plan_report(batch)["layers"]:
        dtypes[row["index"]] = row["dtype"]
    return dtypes


def setup_cell(cell: Cell, seed: int, devices, options: Optional[Dict] = None):
    """Weights, plan + prepare, and a warm-up call of every shape the
    cell's traffic uses; returns what the window and the check need."""
    import jax

    import repro
    from repro.api.model import CNNModel

    cfg = cell.config
    ref = reference_module(cfg)
    t = clock()

    def phase(name: str) -> None:
        nonlocal t
        now = clock()
        log(f"[setup] {name} {now - t:.3f} s")
        t = now

    key, rng = seed_streams(seed)
    with jax.default_device(devices[0]):
        params = jax.jit(lambda k: ref.init_params(
            k, cfg["layers"], cfg["in_channels"]))(key)
        jax.block_until_ready(params)
    phase("weights (seeded, one jitted call)")

    input_shape = (*cfg["input_hw"], cfg["in_channels"])
    traffic = make_traffic(cell.traffic, input_shape, rng, input_sharding(devices))
    model = CNNModel(_layer_table(cfg), tuple(cfg["input_hw"]),
                     in_channels=cfg["in_channels"], name=cfg["name"])
    opts = dict(impl="pallas", interpret=False, batch=traffic.compile_batch(),
                dtype=cfg["dtype"], fallback="off", cache_path=PLAN_CACHE)
    opts.update(options or {})
    compiled = repro.compile(model, params, repro.ExecutionOptions(**opts),
                             devices=list(devices))
    phase("plan + prepare (repro.compile)")
    traffic.warm(compiled)
    phase("warm-up: trace, lower, compile or load, run each shape")
    return SimpleNamespace(cfg=cfg, ref=ref, params=params, traffic=traffic,
                           compiled=compiled)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, t_start: float, limits: dict,
             options: Optional[Dict] = None,
             fault: Optional[Callable] = None) -> dict:
    """Run ``cell`` once and return the result line's object.

    ``options`` overrides ``ExecutionOptions`` fields (tests run the
    Pallas kernels in interpret mode on the CPU); ``fault(compiled)``, where
    given, breaks the timed path after set-up (tests of the comparison).
    """
    import jax

    counter = CompileCounter()
    chips = len(devices)
    s = setup_cell(cell, seed, devices, options)
    cfg, ref, params, traffic, compiled = (
        s.cfg, s.ref, s.params, s.traffic, s.compiled)
    del s
    if fault is not None:
        fault(compiled)
    setup_s = clock() - t_start
    log(f"[setup] setup_s {setup_s:.3f} s; executables built or loaded "
        f"{counter.counts['compiles']}, cache hits "
        f"{counter.counts['cache_hits']}, misses {counter.counts['cache_misses']}")

    before = counter.snapshot()
    trace_dir = os.path.join(TRACE_ROOT, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        popts = jax.profiler.ProfileOptions()
        popts.python_tracer_level = 0
        popts.host_tracer_level = 0      # device ops only; spans: traffic.Spans
        jax.profiler.start_trace(trace_dir, profiler_options=popts)
    try:
        window = traffic.drive(compiled, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
            with open(os.path.join(trace_dir, tr.SPANS_FILE), "w") as f:
                json.dump(window.spans, f)
    in_window = counter.counts["compiles"] - before["compiles"]
    log(f"[window] {window.seconds:.3f} s: {window.attempted} attempted, "
        f"{window.completed} completed, {window.failed} failed, "
        f"{window.forwards} executor calls; executables built in the window "
        f"{in_window}")

    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    n_layers = len(cfg["layers"])
    dtypes = _layer_dtypes(compiled, traffic.compile_batch(), cfg, n_layers)
    images = traffic.pool_images
    batch = traffic.compile_batch()
    del compiled, traffic
    gc.collect()

    t_ref = clock()
    ref_rows = compare.reference_rows(
        ref, params, cfg["layers"], images,
        block=int(cell.traffic.get("reference_block", 8)))
    worst = compare.worst_rel_l2(window.answers, ref_rows)
    log(f"[check] reference over {len(images)} pool images, "
        f"{len(window.answers)} answers compared, {clock() - t_ref:.3f} s")
    limit = float(limits["worst_rel_l2"])
    correct = bool(window.failed == 0 and window.answers and worst <= limit)

    metrics: Dict[str, dict] = {}
    device_info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": chips,
        "memory_peak_bytes": int(memory_peak),
    }
    result = {"correct": correct, "attempted": int(window.attempted),
              "failed": int(window.failed)}
    if not trace:
        values = dict(window.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(
                    f"traffic kind {cell.traffic['kind']!r} does not measure "
                    f"{m['name']!r} (it measures {sorted(values)})")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        t_red = clock()
        reduced = tr.reduce(tr.load(trace_dir))
        log(f"[trace] reduced in {clock() - t_red:.3f} s: window "
            f"{reduced.window_s:.3f} s, busy {reduced.mean_busy_s:.3f} s per "
            f"chip, {reduced.kernel_events} kernel events, first device op "
            f"{1e3 * reduced.first_op_after_window_s:.3f} ms after the window "
            f"opened")
        if reduced.kernel_events == 0:
            raise RuntimeError("the trace holds no Pallas kernel event")
        ctx = SimpleNamespace(
            reduced=reduced, window=window, chips=chips, peaks=peaks,
            work=network_work(cfg["layers"], cfg["input_hw"], cfg["in_channels"]),
            dtypes=dtypes, batch=batch,
        )
        read = readers(cell)
        for m in cell.per_layer:
            v = read[m["name"]](ctx)
            if v is None:
                continue
            if m["unit"] == "%" and v > 100.0:
                raise RuntimeError(f"{m['name']} reads {v} %: over 100")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device_info["busy_s"] = reduced.mean_busy_s
        device_info["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.device_ops],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps],
        }
    result["metrics"] = metrics
    result["device"] = device_info
    checks = {"worst_rel_l2": {"value": worst, "limit": limit},
              "failed": {"value": int(window.failed), "limit": 0}}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result
