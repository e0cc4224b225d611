"""Trace reduction: on synthetic events, and on a small trace recorded on
a TPU v5e (``data/vgg16_b8_small``: a ``vgg16-b8-offline`` run with a
0.3 s window, from when the classifier still pooled to 512 channels, so 13
conv kernels per call; its ``.xplane.pb``, cut to the device plane's
``XLA Ops`` line and the plane that holds ``profile_start_time``, and the
harness's ``spans.json``)."""
import os

import pytest

from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "vgg16_b8_small")


def test_union_clips_and_merges():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 50)]
    assert tr.union_ns(iv, 0, 100) == 15 + 11 + 10
    assert tr.union_ns(iv, 8, 25) == 7 + 5
    assert tr.union_ns([], 0, 10) == 0


def _synthetic():
    ops = {
        "/device:TPU:0": [
            tr.Op("_fused_winograd_bias_kernel", 100, 300, True),
            tr.Op("fusion.1", 300, 350, False),
            tr.Op("_conv_bias_kernel", 500, 700, True),
        ],
        "/device:TPU:1": [
            tr.Op("_fused_winograd_bias_kernel", 100, 400, True),
        ],
    }
    spans = [("window", 0, 1000), ("dispatch", 0, 90), ("wait", 360, 990)]
    return tr.Trace(ops, spans)


def test_reduce_synthetic():
    r = tr.reduce(_synthetic())
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == {"/device:TPU:0": pytest.approx(450e-9),
                        "/device:TPU:1": pytest.approx(300e-9)}
    assert r.kernel_s["/device:TPU:0"] == pytest.approx(400e-9)
    assert r.kernel_events == 3
    assert r.mean_busy_s == pytest.approx(375e-9)
    assert r.device_ops[0] == ("_fused_winograd_bias_kernel", pytest.approx(500e-9))
    # Longest gap: chip 1 idle from 400 to 1000, the host waiting.
    assert r.idle_gaps[0] == ("wait", pytest.approx(600e-9))
    assert ("dispatch", pytest.approx(100e-9)) in r.idle_gaps


def test_window_span_required():
    t = _synthetic()
    t.spans = [s for s in t.spans if s[0] != "window"]
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_kernel_classification_and_labels():
    kernel = ('%fwd.22 = f32[6,6,224,512]{3,2,1,0:T(8,128)S(1)} custom-call('
              'f32[8,8,224,512]{3,2,1,0:T(8,128)} %pad.39), '
              'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    copy = ('%copy.41 = f32[8,8,11552,128]{3,2,1,0:T(8,128)} copy('
            'f32[8,8,8,38,38,128]{5,1,4,3,2,0:T(8,128)} %bitcast.1)')
    assert tr.is_kernel(kernel) and not tr.is_kernel(copy)
    assert tr.op_label(kernel) == "fwd.22 pallas f32[6,6,224,512]"
    assert tr.op_label(copy) == "copy.41 copy f32[8,8,11552,128]"
    assert tr.op_label("%cs = (f32[4]{0}, u32[]) copy-start(f32[4]{0} %a)") == "cs copy-start"


def test_recorded_chip_trace():
    t = tr.load(SMALL)
    assert list(t.ops) == ["/device:TPU:0"]
    r = tr.reduce(t)
    busy, kern = r.busy_s["/device:TPU:0"], r.kernel_s["/device:TPU:0"]
    assert 0 < kern < busy < r.window_s
    # Eight executor calls of 13 conv kernels each.
    assert r.kernel_events == 8 * 13
    assert len(r.device_ops) == 10 and 0 < len(r.idle_gaps) <= 10
    assert any(" pallas f32[" in n for n, _ in r.device_ops)
    # The device waits on the host's first input transfer, then runs.
    assert 0 < r.first_op_after_window_s < 0.1
    assert {n for n, _ in r.idle_gaps} <= {"h2d", "dispatch", "wait", "host"}
