"""End-to-end planned CNN inference through the `repro.api` facade.

The paper's bottom line is a fully co-designed network run: every conv layer
executes the algorithm + blocking the per-layer analysis chose (§VII, Figs
9-10).  This benchmark reproduces that shape through the public entry point:

  1. ``repro.compile(model, params, options)`` plans the whole network
     (cost-model autotune on a cold cache; pure lookups on a warm one) —
     ``plan_report()`` is printed as a per-layer table of (algorithm, block
     config, predicted cost, provenance).
  2. The network runs end-to-end three ways: per-layer planned (unfused),
     per-layer fused (bn folded, epilogue in-kernel), and the compiled
     executor (``compiled.run``: layout persistence + offline-prepared
     params), per batch-sweep entry.
  3. A second ``repro.compile`` on the same cache must re-plan the network
     with **zero re-tunes**, which the emitted ``warm_retunes`` row asserts.
  4. Every row also lands in machine-readable ``BENCH_e2e.json``
     (name/seconds/plan provenance) so the perf trajectory is tracked.

Models: vgg16 (default, paper's classification network), yolov3-tiny, and
yolov3-20 (the first-20-layer Darknet-53 slice the paper sweeps in gem5).

Run directly:  PYTHONPATH=src python -m benchmarks.e2e_cnn --model vgg16
"""
from __future__ import annotations

import argparse
from typing import Optional, Tuple

from benchmarks.common import emit, time_jit, write_bench_json


def _model(model: str):
    """The facade CNNModel descriptor for a model name."""
    from repro.configs import vgg16, yolov3

    if model == "vgg16":
        return vgg16.MODEL
    if model == "yolov3-tiny":
        return yolov3.TINY_MODEL
    if model == "yolov3-20":
        return yolov3.MODEL_20
    raise ValueError(f"unknown model {model!r}")


def run(
    model: str = "vgg16",
    input_hw: Optional[Tuple[int, int]] = None,
    batch: int = 1,
    impl: str = "jax",
    mode: str = "cost",
    cache_path: Optional[str] = None,
    reps: int = 2,
    batch_sweep: Optional[Tuple[int, ...]] = None,
    json_path: Optional[str] = None,
    predict_only: bool = False,
    pipeline_sweep: Optional[Tuple[int, ...]] = None,
) -> None:
    import jax

    import repro
    from benchmarks import common
    from repro.core.planner import DEFAULT_CACHE_PATH
    from repro.models.cnn import cnn_forward, fold_batchnorm, init_cnn

    rows_start = len(common.ROWS)       # this run's slice of the row log
    desc = _model(model)
    if input_hw is not None:
        desc = desc.with_input_hw(input_hw)
    h, w = desc.input_hw
    layers, in_ch = desc.layers, desc.in_channels
    cache = cache_path if cache_path is not None else DEFAULT_CACHE_PATH
    options = repro.ExecutionOptions(
        impl=impl, mode=mode, cache_path=cache, batch=batch,
    )

    # -- 1. compile: plan the whole network (cold: tunes; warm: hits) --------
    rng = jax.random.PRNGKey(0)
    params = init_cnn(rng, layers, in_channels=in_ch)
    compiled = repro.compile(desc, params, options)
    report = compiled.plan_report()
    for conv_i, row in enumerate(report["layers"]):
        emit(
            f"e2e_{model}_L{conv_i:02d}",
            row["predicted_s"],
            f"{row['algorithm']} {row['kernel']}x{row['kernel']}"
            f"/s{row['stride']} "
            f"kblocks={'x'.join(map(str, row['kernel_blocks']))} "
            f"[{row['source']}]",
            provenance=row,
        )
    emit(f"e2e_{model}_predicted_total", report["predicted_total_s"],
         f"tunes={report['tunes']} hits={report['hits']}",
         provenance={"tunes": report["tunes"], "hits": report["hits"]})

    # -- 1b. int8: the quantized compilation's resolved per-layer decisions --
    # Modeled (cost-model) rows like section 1 — deterministic, so they land
    # in the committed baseline and the regression gate.  The planner
    # resolves dtype per layer: entry/head layers whose fp32 output writes
    # dominate stay fp32, everything else quantizes and its predicted time
    # reflects the int8 MAC rate + halved operand traffic.
    options8 = options.replace(dtype="int8")
    compiled8 = repro.compile(desc, params, options8)
    report8 = compiled8.plan_report()
    for conv_i, row in enumerate(report8["layers"]):
        emit(
            f"e2e_{model}_int8_L{conv_i:02d}",
            row["predicted_s"],
            f"{row['algorithm']} {row['kernel']}x{row['kernel']}"
            f"/s{row['stride']} dtype={row['dtype']} [{row['source']}]",
            provenance=row,
        )
    n_q = sum(1 for r in report8["layers"] if r["dtype"] == "int8")
    t32 = report["predicted_total_s"]
    t8 = report8["predicted_total_s"]
    emit(f"e2e_{model}_int8_predicted_total", t8,
         f"quantized_layers={n_q}/{len(report8['layers'])} "
         f"vs_fp32={t32 / t8 if t8 > 0 else 0:.2f}x",
         provenance={"quantized_layers": n_q,
                     "fp32_predicted_total_s": t32})
    compiled8.save_plans()

    # -- 1c. serving resilience: healthy-path degradation counters -----------
    # One request through the serving engine; ``seconds`` is the sum of the
    # resilience degradation counters — 0.0 on a healthy stack — so the
    # regression gate's exact-equality rule for zero-second rows catches a
    # silently-degraded baseline (any fallback, eviction, retry, or
    # request failure flips the row non-zero and fails the build).
    import numpy as np

    eng = compiled.serve(buckets=(1,))
    eng.submit(np.zeros((h, w, in_ch), np.float32))
    eng.run()
    health = eng.health()
    degraded = float(
        health["fallback_depth"] + health["evictions"]
        + health["rejections"] + health["retries"]
        + health["request_failures"] + health["fallback_batches"]
    )
    emit(f"e2e_{model}_serving_resilience", degraded,
         f"fallback_depth={health['fallback_depth']} "
         f"evictions={health['evictions']} retries={health['retries']} "
         f"failures={health['request_failures']} "
         f"ladder={'>'.join(health['ladder'])}",
         provenance=health)

    # -- 1d. pipeline sweep: cost-balanced stage partitions, modeled ---------
    # Deterministic rows (planner cost model only — no devices needed): the
    # stage partitioner splits the NetworkPlan at legal cut points balancing
    # planner-predicted seconds, and the row's ``seconds`` is the GPipe
    # tick-synchronous modeled latency at the auto-chosen microbatch count.
    # Committed to the baseline so a partitioner or cost-model regression
    # (worse balance, lost cut legality, broken n_micro chooser) fails the
    # regression gate.
    if pipeline_sweep:
        from repro.core.netplan import choose_n_micro, partition_network

        netplan_p = compiled.network_plan(batch)
        for n_stages in pipeline_sweep:
            pipeplan = partition_network(netplan_p, n_stages)
            n_micro = choose_n_micro(pipeplan.stage_seconds, batch)
            emit(
                f"e2e_{model}_pipeline_s{n_stages}",
                pipeplan.modeled_latency_s(n_micro),
                f"stages={'/'.join(f'{a}:{z}' for a, z in pipeplan.stage_bounds)} "
                f"n_micro={n_micro} "
                f"bubble={pipeplan.bubble_fraction(n_micro):.3f} "
                f"max_stage_s={max(pipeplan.stage_seconds):.6g}",
                provenance={
                    "stage_bounds": [list(b) for b in pipeplan.stage_bounds],
                    "stage_seconds": list(pipeplan.stage_seconds),
                    "n_micro": n_micro,
                },
            )

    # -- 1e. kernel-interior proofs: zero-cost verification row --------------
    # The kernel-level static analyzer runs over the *Pallas* compilation of
    # the same network (interpret mode, trace-only — nothing executes), fp32
    # and int8, and ``seconds`` is the total error-finding count: 0.0 while
    # every pallas_call's write-disjointness, block-bounds, accumulator-guard
    # and int8-overflow proof holds.  The regression gate's exact-equality
    # rule for zero-second rows turns any new finding into a build failure.
    import time

    n_err = n_kernels = 0
    prov = {}
    t0 = time.monotonic()
    for tag, opts_v in (("fp32", options), ("int8", options8)):
        compiled_v = repro.compile(
            desc, params, opts_v.replace(impl="pallas", interpret=True))
        rep = compiled_v.verify_report(level="kernel")
        errs = sum(1 for f in rep.findings if f.severity == "error")
        n_err += errs
        n_kernels += len(rep.kernels)
        prov[tag] = {
            "kernels": len(rep.kernels),
            "errors": errs,
            "warnings": sum(
                1 for f in rep.findings if f.severity == "warning"),
            "passes_run": list(rep.passes_run),
        }
    prov["wall_s"] = round(time.monotonic() - t0, 3)
    emit(f"e2e_{model}_verify_kernel", float(n_err),
         f"kernels={n_kernels} errors={n_err} (fp32+int8, pallas interpret, "
         f"level=kernel, {prov['wall_s']:.1f}s)",
         provenance=prov)

    if predict_only:
        # Modeled rows only: skip the wall-clock sections (2, 2b, 2c) but
        # keep the warm-cache proof — everything emitted is deterministic,
        # which is what the committed baseline + regression gate need.
        _warm_proof(repro, desc, params, options, model, batch_sweep, batch)
        if json_path:
            print(f"# wrote "
                  f"{write_bench_json(json_path, extra={'model': model}, rows=common.ROWS[rows_start:])}")
        return

    # -- 2. per-layer planned run (unfused): the pre-executor reference ------
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, h, w, in_ch))
    plans_t = tuple(s.plan for s in compiled.network_plan(batch).steps)
    fwd = jax.jit(
        lambda xx: cnn_forward(params, layers, xx, impl=impl, plans=plans_t)
    )
    t = time_jit(fwd, x, reps=reps, warmup=1)
    emit(f"e2e_{model}_total", t,
         f"{model} {h}x{w} b{batch} impl={impl} planned end-to-end")

    # -- 2b. fused epilogue: batchnorm folded offline, bias+act in-kernel ----
    # Folding runs once ahead of serving (like the paper's offline Winograd
    # weight transform, §VII.A), so it is excluded from the timed loop.
    folded = jax.block_until_ready(
        jax.jit(lambda p: fold_batchnorm(p, layers))(params)
    )
    fused = jax.jit(
        lambda xx: cnn_forward(folded, layers, xx, impl=impl, plans=plans_t,
                               fuse_epilogue=True)
    )
    t_fused = time_jit(fused, x, reps=reps, warmup=1)
    speedup = t / t_fused if t_fused > 0 else float("inf")
    emit(f"e2e_{model}_total_fused", t_fused,
         f"{model} {h}x{w} b{batch} impl={impl} bn-folded fused epilogue "
         f"({speedup:.2f}x vs unfused)")

    # -- 2c. the compiled executor: whole-graph planned, layout-persistent ---
    # ``compiled.run`` is the facade's deployment path: NetworkPlan (layout
    # elision, row tiles snapped to divisors of OH) + offline-prepared
    # params.  The honest per-layer baseline is the *fused* path on
    # bn-folded params with plans re-resolved at each batch (plans are
    # batch-keyed) — so the ratio isolates the layer-boundary work, not
    # epilogue fusion the per-layer path also has.
    for bn in (batch_sweep or (batch,)):
        netplan_b = compiled.network_plan(bn)
        xb = jax.random.normal(jax.random.PRNGKey(2), (bn, h, w, in_ch))
        t_exec = time_jit(compiled.run, xb, reps=reps, warmup=1)
        plans_b = tuple(s.plan for s in netplan_b.steps)
        fwd_b = jax.jit(lambda xx, pb=plans_b: cnn_forward(
            folded, layers, xx, impl=impl, plans=pb, fuse_epilogue=True))
        t_perlayer = time_jit(fwd_b, xb, reps=reps, warmup=1)
        emit(f"e2e_{model}_b{bn}_perlayer", t_perlayer,
             f"{model} {h}x{w} b{bn} impl={impl} per-layer planned (fused, "
             f"bn-folded)")
        emit(f"e2e_{model}_b{bn}_executor", t_exec,
             f"{model} {h}x{w} b{bn} impl={impl} compiled executor "
             f"elided={netplan_b.elided_boundaries} "
             f"vs_perlayer={t_perlayer / t_exec if t_exec > 0 else 0:.2f}x",
             provenance={"elided_boundaries": netplan_b.elided_boundaries,
                         "batch": bn})
    compiled.save_plans()

    _warm_proof(repro, desc, params, options, model, batch_sweep, batch)

    if json_path:
        print(f"# wrote "
              f"{write_bench_json(json_path, extra={'model': model}, rows=common.ROWS[rows_start:])}")


def _warm_proof(repro, desc, params, options, model, batch_sweep, batch):
    """Warm-cache proof: a fresh compile must re-tune nothing."""
    compiled2 = repro.compile(desc, params, options)
    for bn in (batch_sweep or (batch,)):
        compiled2.network_plan(bn)
    report2 = compiled2.plan_report()
    retunes = report2["tunes"]
    emit(f"e2e_{model}_warm_retunes", 0.0,
         f"retunes={retunes} hits={report2['hits']} "
         f"network_hits={report2['network_hits']}",
         provenance={"retunes": retunes,
                     "network_hits": report2["network_hits"]})
    assert retunes == 0, (
        f"warm plan cache re-tuned {retunes} layers — persistence is broken"
    )
    assert report2["network_hits"] >= 1, (
        "warm network-level cache entry missing — netplan persistence broken"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="vgg16",
                    choices=["vgg16", "yolov3-tiny", "yolov3-20"])
    ap.add_argument("--hw", type=int, default=None,
                    help="square input resolution (default: model's own)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--impl", default="jax", choices=["jax", "pallas"])
    ap.add_argument("--mode", default="cost", choices=["cost", "measure"])
    ap.add_argument("--cache", default=None,
                    help="plan-cache JSON path (default: REPRO_PLAN_CACHE or "
                         ".cache/conv_plans.json)")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--batch-sweep", default=None,
                    help="comma list of batch sizes, e.g. 1,4,8: emit an "
                         "e2e_<model>_b<N>_executor row (compiled executor, "
                         "layout persistence) next to the per-layer planned "
                         "total for each N")
    ap.add_argument("--pipeline-sweep", default=None,
                    help="comma list of stage counts, e.g. 2,4: emit an "
                         "e2e_<model>_pipeline_s<N> row (cost-balanced stage "
                         "partition, modeled GPipe latency) for each N — "
                         "deterministic, lands in the committed baseline")
    ap.add_argument("--json", default="BENCH_e2e.json",
                    help="machine-readable output path (empty to disable)")
    ap.add_argument("--predict-only", action="store_true",
                    help="emit only the deterministic modeled rows (plan "
                         "report, int8 decisions, warm-retunes proof) — no "
                         "wall-clock timing; what the committed baseline "
                         "and benchmarks.check_regression gate on")
    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run(
        model=args.model,
        input_hw=(args.hw, args.hw) if args.hw else None,
        batch=args.batch,
        impl=args.impl,
        mode=args.mode,
        cache_path=args.cache,
        reps=args.reps,
        batch_sweep=(tuple(int(b) for b in args.batch_sweep.split(","))
                     if args.batch_sweep else None),
        json_path=args.json or None,
        predict_only=args.predict_only,
        pipeline_sweep=(tuple(int(s) for s in args.pipeline_sweep.split(","))
                        if args.pipeline_sweep else None),
    )


if __name__ == "__main__":
    main()
