"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.emit).
Sections:
  table2_blocksizes  paper Table II (BLIS block tuning, VMEM model)
  table3_veclen      paper Fig 6    (vector-length scaling)
  fig_cache_sweep    paper Figs 7-10 (cache x veclen co-design, both algos)
  table4_ai          paper Table IV (per-layer AI + %peak)
  winograd_vs_im2col paper §VII     (2.4x / 1.35x / 1.5x claims)
  e2e_cnn            paper Figs 9-10 (planned end-to-end network; small
                     resolution here — full runs via benchmarks.e2e_cnn)
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        e2e_cnn,
        fig_cache_sweep,
        table2_blocksizes,
        table3_veclen,
        table4_ai,
        winograd_vs_im2col,
    )

    sections = [
        ("table2_blocksizes", table2_blocksizes.run),
        ("table3_veclen", table3_veclen.run),
        ("fig_cache_sweep", fig_cache_sweep.run),
        ("table4_ai", table4_ai.run),
        ("winograd_vs_im2col", winograd_vs_im2col.run),
        ("e2e_cnn", lambda: e2e_cnn.run(model="vgg16", input_hw=(64, 64),
                                        reps=1)),
    ]
    failures = 0
    for name, fn in sections:
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR")
            traceback.print_exc()
    # Every emitted row, machine-readable — the perf trajectory is tracked
    # from this file, not scraped from stdout.
    from benchmarks.common import write_bench_json

    print(f"# wrote {write_bench_json('BENCH_e2e.json', extra={'driver': 'benchmarks.run', 'failures': failures})}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
