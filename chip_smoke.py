"""Smoke run of planned VGG-16 inference on a TPU, through the public facade.

    python chip_smoke.py             # one chip: fp32, int8 and serving phases
    python chip_smoke.py --chips 4   # four chips: batch-sharded and
                                     # 4-stage pipelined executors only

Drives ``repro.compile(vgg16.MODEL, params, ExecutionOptions(impl="pallas",
interpret=False, fallback="off"))`` at full width (224x224, batch 8, seeded
random weights) and checks what comes out:

- fp32: relative L2 error of the logits against the XLA reference forward
  (``cnn_forward(impl="xla")`` at ``highest`` matmul precision) <= 1e-3;
- int8 (activation scales calibrated on the batch): SQNR against that fp32
  reference >= 30 dB (the repo's int8 gate);
- serving: ``.serve(buckets=(1, 4, 8))`` answers 12 single-image requests,
  each equal to ``.run`` on the same images, with every degradation counter
  of ``engine.health()`` at 0.

With ``--chips 4`` the batch-sharded executor (batch 8 over 4 devices) and
``pipeline_stages=4`` are each compared with the one-chip result in the same
process, and their params and outputs must span all four devices.

Times printed here are one run after one warm-up call, not a benchmark.
The script refuses to run anywhere but a TPU whose ``device_kind`` is in
``repro.hw.CHIPS``: there is no CPU or interpret-mode fallback.  Any failed
check raises, so the process exits non-zero; on success the last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

BATCH = 8
FP32_REL_L2_MAX = 1e-3
INT8_SQNR_MIN_DB = 30.0
SERVE_BUCKETS = (1, 4, 8)
SERVE_REQUESTS = 12


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_l2(ref, got) -> float:
    import numpy as np

    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def count_cache_events():
    """Persistent compile-cache hits and misses, from JAX's monitoring
    events, counted from now on."""
    import jax

    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    return counts


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def timed_first_and_steady(fn, x):
    """(first-call seconds incl. compile, steady ms of one more call, out)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(x))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(x))
    return first, 1e3 * (time.perf_counter() - t0), out


def compile_vgg(params, devices=None, calibration=None, **opts):
    import repro
    from repro.configs import vgg16

    options = repro.ExecutionOptions(
        impl="pallas", interpret=False, batch=BATCH, fallback="off", **opts
    )
    return repro.compile(vgg16.MODEL, params, options, devices=devices,
                         calibration=calibration)


def print_plan(compiled, label: str) -> None:
    rep = compiled.plan_report()
    log(f"[{label}] plan: {len(rep['layers'])} conv layers, batch "
        f"{rep['batch']}, dtype {rep['dtype']}")
    for r in rep["layers"]:
        log(f"[{label}]   layer {r['index']:2d} in {r['in_hw']} "
            f"{r['algorithm']:<12s} {r['dtype']:<8s} blocks "
            f"{tuple(r['kernel_blocks'])}")


def run_phase(compiled, x, label: str):
    """Plan table, compile + steady timing; returns the logits."""
    print_plan(compiled, label)
    first, steady_ms, out = timed_first_and_steady(compiled.run, x)
    log(f"[{label}] first call (trace + compile + run) {first:.3f} s; "
        f"steady {steady_ms:.3f} ms per batch of {x.shape[0]} "
        f"(one run after warm-up, not a benchmark)")
    return out


def reference_logits(params, x):
    import jax

    from repro.configs import vgg16
    from repro.models.cnn import cnn_forward

    layers = vgg16.MODEL.layers
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, xx: cnn_forward(p, layers, xx, impl="xla"))
        return jax.block_until_ready(fwd(params, x))


def one_chip(params, x, ref) -> None:
    import numpy as np

    from repro.core.quant import sqnr_db
    from repro.serving.resilience import is_failure

    # fp32 ------------------------------------------------------------------
    fp32 = compile_vgg(params)
    out = run_phase(fp32, x, "fp32")
    check(out.shape == ref.shape, f"fp32 logits shape {out.shape} != {ref.shape}")
    check(bool(np.isfinite(np.asarray(out)).all()), "fp32 logits not finite")
    err = rel_l2(ref, out)
    log(f"[fp32] relative L2 error vs XLA reference: {err:.3e} "
        f"(limit {FP32_REL_L2_MAX:g})")
    check(err <= FP32_REL_L2_MAX, f"fp32 relative L2 error {err} > {FP32_REL_L2_MAX}")

    # int8 ------------------------------------------------------------------
    # Activation scales calibrated on the batch itself, as the repo's int8
    # conformance gate does.
    q8 = compile_vgg(params, dtype="int8", calibration=x)
    out8 = run_phase(q8, x, "int8")
    check(out8.shape == ref.shape, f"int8 logits shape {out8.shape}")
    sq = sqnr_db(ref, out8)
    log(f"[int8] SQNR vs fp32 XLA reference: {sq:.2f} dB "
        f"(limit {INT8_SQNR_MIN_DB:g} dB)")
    check(sq >= INT8_SQNR_MIN_DB, f"int8 SQNR {sq} dB < {INT8_SQNR_MIN_DB}")

    # serving ---------------------------------------------------------------
    import jax

    extra = jax.random.normal(
        jax.random.PRNGKey(2), (SERVE_REQUESTS - BATCH, *x.shape[1:]), x.dtype
    )
    images = np.concatenate([np.asarray(x), np.asarray(extra)])
    t0 = time.perf_counter()
    engine = fp32.serve(buckets=SERVE_BUCKETS)
    uids = [engine.submit(img) for img in images]
    results = engine.run()
    serve_s = time.perf_counter() - t0
    failed = [u for u in uids if is_failure(results[u])]
    check(not failed, f"served requests failed: {[results[u] for u in failed]}")
    # The engine fills the largest bucket first: requests 0-7 ride bucket 8,
    # 8-11 bucket 4 — the same executors ``.run`` uses at those batches.
    expect = np.concatenate([
        np.asarray(out), np.asarray(fp32.run(extra)),
    ])
    worst = max(rel_l2(expect[i], results[u]) for i, u in enumerate(uids))
    log(f"[serve] {len(uids)} requests in {serve_s:.3f} s (first use of "
        f"buckets compiles), batches {engine.stats['batches']}, worst "
        f"relative L2 vs .run {worst:.3e}")
    check(worst <= 1e-6, f"served answers differ from .run (rel L2 {worst})")
    health = engine.health()
    counters = {
        k: health[k] for k in (
            "fallback_depth", "evictions", "rejections", "retries",
            "request_failures", "fallback_batches", "faults_injected",
        )
    }
    log(f"[serve] health counters {counters}")
    check(all(v == 0 for v in counters.values()),
          f"degradation counters not all 0: {counters}")


def four_chips(params, x, ref) -> None:
    import jax
    import numpy as np

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX sees {len(devices)}")

    single = compile_vgg(params, devices=devices[:1])
    base = run_phase(single, x, "1chip")
    log(f"[1chip] relative L2 error vs XLA reference: {rel_l2(ref, base):.3e}")

    sharded = compile_vgg(params)
    out = run_phase(sharded, x, "shard4")
    ex = sharded.executor(BATCH)
    check(ex.mesh is not None and ex.mesh.devices.size == 4,
          f"batch mesh spans {None if ex.mesh is None else ex.mesh.devices.size}"
          " devices, not 4")
    check(len(out.sharding.device_set) == 4,
          f"sharded logits live on {len(out.sharding.device_set)} device(s)")
    placed = jax.tree.leaves(ex._placed)
    check(all(len(a.sharding.device_set) == 4 for a in placed),
          "batch-sharded params are not replicated over all 4 devices")
    err = rel_l2(base, out)
    log(f"[shard4] mesh {dict(ex.mesh.shape)}, logits on "
        f"{len(out.sharding.device_set)} devices, relative L2 vs 1 chip {err:.3e}")
    check(err <= 1e-5, f"sharded logits differ from one chip: {err}")

    piped = compile_vgg(params, pipeline_stages=4)
    out = run_phase(piped, x, "pipe4")
    pex = piped.pipeline_executor(BATCH)
    check(pex.mesh.devices.size == 4,
          f"stage mesh spans {pex.mesh.devices.size} devices, not 4")
    for name, buf in pex._placed.items():
        shards = {s.device for s in buf.addressable_shards}
        check(len(shards) == 4 and buf.sharding.shard_shape(buf.shape)[0] == 1,
              f"stage params {name} not split one stage per device")
    err = rel_l2(base, out)
    log(f"[pipe4] stages {piped.pipeline_plan(BATCH).stage_bounds}, "
        f"n_micro {pex.n_micro}, relative L2 vs 1 chip {err:.3e}")
    check(err <= 1e-5, f"pipelined logits differ from one chip: {err}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the batch-sharded and pipelined "
                         "executors on four chips")
    args = ap.parse_args()

    # Otherwise the TPU library writes its logs to a fixed directory outside
    # the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.hw import chip_for
    from repro.launch.compile_cache import configure_compile_cache

    dev = jax.devices()[0]
    chip_for(dev)                       # raises off-TPU or on an unknown kind
    cache_dir = configure_compile_cache()
    cache = count_cache_events()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    from repro.configs import vgg16

    t0 = time.perf_counter()
    params = vgg16.MODEL.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(
        jax.random.PRNGKey(1), (BATCH, *vgg16.MODEL.input_hw, 3), jax.numpy.float32
    )
    ref = reference_logits(params, x)
    log(f"[ref] XLA reference logits {ref.shape} in "
        f"{time.perf_counter() - t0:.3f} s (init + compile + run)")

    (four_chips if args.chips == 4 else one_chip)(params, x, ref)
    log(f"total {time.perf_counter() - t0:.3f} s; compile cache {cache_dir}: "
        f"{cache['hits']} hits, {cache['misses']} misses")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
