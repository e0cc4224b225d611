"""Work counts against hand counts of the layer tables."""
import json
import math
import os

import pytest

from harness.peaks import UnknownDevice, compute_peak, load_peaks
from harness.spec import BENCH_DIR
from harness.work import forward_least_time, image_peak_seconds, network_work

V5E = "TPU v5 lite"


def _work(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        c = json.load(f)
    return network_work(c["layers"], c["input_hw"], c["in_channels"])


def test_vgg16_per_image_counts():
    w = _work("vgg16")
    features = [x for x in w[:18] if x.kind == "conv"]
    assert len(features) == 13
    assert sum(x.direct_ops for x in features) == 30_693_261_312
    assert sum(x.floor_ops for x in features) == 7_028_441_088
    # Classifier: flatten 7x7x512 -> 4096 (a 7x7 conv with no padding, to a
    # 1x1 map) -> 4096 -> 1000; 0.25 GFLOP per image.
    fc1 = w[18]
    assert (fc1.kind, fc1.in_shape, fc1.out_shape) == ("conv", (7, 7, 512), (1, 1, 4096))
    assert fc1.weight_elems == 25088 * 4096
    classifier = sum(x.floor_ops for x in w[18:])
    assert classifier == 2 * (25088 * 4096 + 4096 * 4096 + 4096 * 1000)
    assert round(classifier / 1e7) == 25
    assert w[-1].out_shape == (1000,)


def test_vgg16_first_layer_by_hand():
    l0 = _work("vgg16")[0]
    assert (l0.in_shape, l0.out_shape) == ((224, 224, 3), (224, 224, 64))
    assert l0.direct_ops == 2 * 224 * 224 * 3 * 64 * 9
    assert l0.floor_ops == 2 * 38 * 38 * 64 * 3 * 64       # ceil(224/6) = 38
    assert l0.floor_bytes(8, "float32") == 4 * (
        8 * (224 * 224 * 3 + 224 * 224 * 64) + 9 * 3 * 64)


def test_yolov3_20_per_image_counts():
    w = _work("yolov3-20")
    conv = [x for x in w if x.kind == "conv"]
    assert len(conv) == 15
    assert sum(x.direct_ops for x in conv) == 30_164_582_400
    assert sum(x.floor_ops for x in conv) == 16_136_716_288
    act = sum(x.floor_bytes(1, "float32") - 4 * x.weight_elems for x in conv)
    assert round(act / 1e6) == 362
    assert round(sum(4 * x.weight_elems for x in conv) / 1e5) == 50   # 5.0 MB
    # Stride-2 and 1x1 convs count directly; shortcuts carry no work.
    l1 = w[1]
    assert (l1.kernel, l1.stride, l1.out_shape) == (3, 2, (304, 304, 64))
    assert l1.floor_ops == l1.direct_ops == 2 * 304 * 304 * 64 * 32 * 9
    assert w[4].kind == "shortcut" and w[4].floor_ops == 0
    assert w[-1].out_shape == (76, 76, 128)


def test_floor_never_above_direct():
    for name in ("vgg16", "yolov3-20"):
        for x in _work(name):
            assert x.floor_ops <= x.direct_ops


def test_peaks_are_published_and_unknown_kinds_raise():
    p = load_peaks(V5E)
    assert p["matmul_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert compute_peak(p, "float32") == compute_peak(p, "bfloat16") == 197e12
    assert compute_peak(p, "int8") == 393e12
    with pytest.raises(UnknownDevice):
        load_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        compute_peak(p, "float64")


def test_least_time_and_peak_seconds():
    p = load_peaks(V5E)
    w = _work("vgg16")
    dt = ["float32"] * len(w)
    least = forward_least_time(w, 8, dt, p)
    # Each conv is bound by the larger of its compute and memory time.
    by_hand = sum(max(8 * x.floor_ops / 197e12, x.floor_bytes(8, "float32") / 819e9)
                  for x in w if x.kind == "conv")
    assert math.isclose(least, by_hand)
    assert math.isclose(image_peak_seconds(w, dt, p),
                        sum(x.floor_ops for x in w) / 197e12)
