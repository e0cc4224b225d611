"""Paper §VII: Winograd vs optimized im2col+GEMM.

Paper claims (A64FX, SVE): 2.4x on 3x3/stride-1 layers, 1.35x YOLOv3
end-to-end, 1.5x VGG16 end-to-end (weight transform offline).

Three measurements here:
  1. MEASURED on this CPU: jitted pure-JAX winograd vs im2col conv at real
     YOLOv3/VGG16 layer sizes (XLA:CPU timing is a proxy, but the FLOP
     advantage is algorithm-level and shows through).
  2. MODELED for TPU v5e: FLOP+traffic roofline of im2col vs the Winograd
     megakernel (V/M stay in VMEM) at the block tuple the planner autotuned
     for it, resolved through the persistent plan cache (a second resolve
     must re-tune nothing).
  3. Network-level Amdahl projection from the eligible-FLOPs fraction.

Run directly:  PYTHONPATH=src python -m benchmarks.winograd_vs_im2col
CI smoke:      ... --layers 1 --modeled-only
"""
from __future__ import annotations

import argparse

import jax

from benchmarks.common import emit, time_jit, vgg16_gemms, yolov3_20_gemms
from repro.core.conv_spec import ConvAlgorithm, ConvSpec
from repro.core.im2col import conv2d_im2col
from repro.core.winograd import conv2d_winograd, transform_weights

# Representative 3x3/stride-1 YOLOv3 layers (paper's winograd-eligible set).
LAYER_SET = [
    dict(h=152, w=152, cin=64, cout=128),
    dict(h=76, w=76, cin=128, cout=256),
    dict(h=38, w=38, cin=256, cout=512),
]


def _measured(layer) -> tuple:
    spec = ConvSpec(layer["cin"], layer["cout"], (3, 3), (1, 1), (1, 1))
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (1, layer["h"], layer["w"], layer["cin"]))
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (3, 3, layer["cin"], layer["cout"])) * 0.05
    u = transform_weights(w)  # offline, like the paper
    im2col_fn = jax.jit(lambda a, b: conv2d_im2col(a, b, spec))
    wino_fn = jax.jit(
        lambda a, b: conv2d_winograd(a, b, spec, pretransformed=True)
    )
    t_i = time_jit(im2col_fn, x, w, reps=3)
    t_w = time_jit(wino_fn, x, u, reps=3)
    return t_i, t_w


def _modeled(layer, planner) -> tuple:
    """v5e modeled seconds + plan for one layer.

    Returns ``(t_i, t_w, plan)``: im2col and Winograd roofline seconds from
    the repo's shared model (``predict_conv_time`` — the same numbers the
    planner's algorithm selection and e2e_cnn.py use, so the rows are
    mutually consistent), plus the Winograd plan, which round-trips through
    the shared persistent cache.
    """
    from repro.core.codesign import predict_conv_time

    h, w, cin, cout = layer["h"], layer["w"], layer["cin"], layer["cout"]
    spec = ConvSpec(cin, cout, (3, 3), (1, 1), (1, 1),
                    algorithm=ConvAlgorithm.WINOGRAD)
    t_i = predict_conv_time(spec, h, w, ConvAlgorithm.IM2COL_GEMM)
    t_w = predict_conv_time(spec, h, w, ConvAlgorithm.WINOGRAD)
    return t_i, t_w, planner.plan(spec, h, w)


def run(layers: int | None = None, modeled_only: bool = False,
        cache_path: str | None = None) -> None:
    from repro.core.planner import DEFAULT_CACHE_PATH, Planner

    cache = cache_path if cache_path is not None else DEFAULT_CACHE_PATH
    # autosave=False: one merge+write after the layer loop, not one locked
    # read-merge-rewrite of the shared file per miss.
    planner = Planner(cache_path=cache, autosave=False)
    layer_set = LAYER_SET[:layers] if layers is not None else LAYER_SET
    ratios_m = []
    for layer in layer_set:
        m_i, m_w, plan = _modeled(layer, planner)
        t_i, t_w = (0.0, 0.0) if modeled_only else _measured(layer)
        if not modeled_only:
            ratios_m.append(t_i / t_w)
        emit(
            f"winograd/3x3s1_{layer['h']}x{layer['w']}x{layer['cin']}",
            t_w,
            (f"im2col_s={t_i:.4f};measured_speedup="
             f"{(t_i / t_w) if t_w else 0:.2f};"
             f"v5e_fused_speedup={m_i / m_w:.2f};"
             f"fused_blocks={'x'.join(map(str, plan.kernel_blocks))};"
             f"paper=2.4"),
        )

    planner.save()

    # Warm-cache proof: a fresh planner on the same file re-tunes nothing.
    warm = Planner(cache_path=cache)
    for layer in layer_set:
        spec = ConvSpec(layer["cin"], layer["cout"], (3, 3), (1, 1), (1, 1),
                        algorithm=ConvAlgorithm.WINOGRAD)
        warm.plan(spec, layer["h"], layer["w"])
    retunes = warm.stats["tunes"]
    emit("winograd/warm_retunes", 0.0,
         f"retunes={retunes};hits={warm.stats['hits']}")
    assert retunes == 0, "warm winograd plan cache re-tuned — persistence broken"

    if modeled_only:
        return

    # Network level: fraction of conv FLOPs in 3x3 s1 layers scales the gain
    # (paper: YOLOv3 1.35x with 38/75 layers eligible; VGG16 1.5x with all).
    for net, dims, paper in (("yolov3_20", yolov3_20_gemms(), 1.35),
                             ("vgg16", vgg16_gemms(), 1.5)):
        elig = sum(2 * d["M"] * d["N"] * d["K"] for d in dims
                   if d["kernel"] == 3 and d["stride"] == 1)
        total = sum(2 * d["M"] * d["N"] * d["K"] for d in dims)
        per_layer = sum(ratios_m) / len(ratios_m)
        amdahl = 1.0 / ((1 - elig / total) + (elig / total) / per_layer)
        emit(f"winograd/network_{net}", 0.0,
             f"eligible_flops={elig / total:.2f};"
             f"projected_speedup={amdahl:.2f};paper={paper}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    def _positive(v: str) -> int:
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError("--layers must be >= 1")
        return n

    ap.add_argument("--layers", type=_positive, default=None,
                    help="run only the first N layers of the set")
    ap.add_argument("--modeled-only", action="store_true",
                    help="skip the measured CPU timing (CI smoke)")
    ap.add_argument("--cache", default=None, help="plan-cache JSON path")
    args = ap.parse_args()
    run(layers=args.layers, modeled_only=args.modeled_only,
        cache_path=args.cache)


if __name__ == "__main__":
    main()
