"""The comparison's control: the reference computed one precision below
the configuration's (``high3``, three bf16 passes, for float32 at
``highest``), put in the program's place on the cell's own images.

    python3 perfbench/control.py --workload vgg16-b8-offline --seeds 1,2,3

For each seed it makes the weights and the image pool exactly as a run of
the cell does and prints the number the run compares (worst relative L2
against the reference) for the control, one JSON line per seed.  A sound
limit lies below every reading this gives.  Not part of a benchmark run.
"""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH_DIR), "src"), BENCH_DIR]


def control_reading(cell, seed: int, devices, precision: str = "high3") -> float:
    import jax

    from harness import compare
    from harness.cell import input_sharding, seed_streams
    from harness.spec import reference_module
    from harness.traffic import make_traffic

    cfg = cell.config
    ref = reference_module(cfg)
    key, rng = seed_streams(seed)
    with jax.default_device(devices[0]):
        params = jax.jit(lambda k: ref.init_params(
            k, cfg["layers"], cfg["in_channels"]))(key)
    traffic = make_traffic(cell.traffic, (*cfg["input_hw"], cfg["in_channels"]),
                           rng, input_sharding(devices))
    images = traffic.pool_images
    block = int(cell.traffic.get("reference_block", 8))
    want = compare.reference_rows(ref, params, cfg["layers"], images, block)
    got = compare.reference_rows(ref, params, cfg["layers"], images, block,
                                 precision=precision)
    return compare.worst_rel_l2(list(enumerate(got)), want)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from harness import device
    from harness.spec import load_cell

    cell = load_cell(args.workload)
    devices, _ = device.chips_for(cell.chips)
    device.configure_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        v = control_reading(cell, seed, devices)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": "high3", "worst_rel_l2": v}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
