"""The chip benchmark: one run of one cell, one JSON result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells are ``BENCHMARK.json``'s ``workloads``; each names a configuration
(``perfbench/configs/``) and a traffic mix (``perfbench/traffic/``), whose
``kind`` names its loop (``perfbench/traffic_kinds/``); its comparison
limits are in ``perfbench/cells/<cell>.json``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window (``perfbench/metrics/<name>.py``).  Progress, set-up phases and the
numbers compared go to standard error; the result is the last line of
standard output.  Without a TPU of a known kind, or with fewer chips than
the cell asks for, the run exits with code 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH_DIR), "src"), BENCH_DIR]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    # Otherwise the TPU runtime logs to a fixed directory outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness import device
    from harness.cell import log, run_cell
    from harness.spec import load_cell, load_limits

    cell = load_cell(args.workload)
    try:
        devices, peaks = device.chips_for(cell.chips)
    except device.NoChip as e:
        log(f"refused: {e}")
        return 3
    cache = device.configure_compile_cache()
    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                      peaks, T_START, load_limits(args.workload))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
