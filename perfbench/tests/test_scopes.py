"""Layer scopes read from a trace, and the five readers built on them.

Two traces recorded on a TPU v5e: ``data/vgg16_b8_small`` (from before the
program set layer scopes) and ``data/vgg16_b8_scoped``: a
``vgg16-b8-offline`` run of the scoped forward with a 0.3 s window.  The
latter's ``.xplane.pb`` is cut as the former's was, to the device plane's
``XLA Ops`` line with its event metadata (less the stats that give source
file paths) and stat metadata, and the plane that holds
``profile_start_time``; beside it are the harness's ``spans.json``, the
program's spans and layer table (``program.json``, as a reader wrote it)
and the run's result line (``result.json``).
"""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from harness import scopes, trace as tr
from harness.peaks import load_peaks
from harness.spec import load_config, metric_reader
from harness.work import network_work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = os.path.join(DATA, "vgg16_b8_small")
SCOPED = os.path.join(DATA, "vgg16_b8_scoped")
NEW_METRICS = ("winograd_roofline", "gemm_roofline", "winograd_glue_share",
               "plan_error", "run_idle_ms")
#: Ops XLA adds that carry no op metadata: transfers and copies of the
#: forward's arguments.
COPIES = {"copy", "copy-start", "copy-done", "async-start", "async-done",
          "custom-call"}


def _result():
    with open(os.path.join(SCOPED, "result.json")) as f:
        return json.load(f)


@pytest.fixture
def run_ctx(tmp_path, monkeypatch):
    """What the harness hands a reader after the committed run, with the
    trace where the harness leaves it."""
    shutil.copytree(SCOPED, tmp_path / "vgg16-b8-offline")
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    scopes._cache.clear()
    res = _result()
    cfg = load_config("vgg16")
    batch = 8
    return SimpleNamespace(
        reduced=tr.reduce(tr.load(str(tmp_path / "vgg16-b8-offline"))),
        window=SimpleNamespace(forwards=res["attempted"] // batch),
        chips=1, peaks=load_peaks(res["device"]["kind"]),
        work=network_work(cfg["layers"], cfg["input_hw"], cfg["in_channels"]),
        dtypes=[cfg["dtype"]] * len(cfg["layers"]), batch=batch,
    )


@pytest.mark.parametrize("trace_dir", [OLD, SCOPED], ids=["old", "scoped"])
def test_decoder_matches_profile_data(trace_dir):
    """The wire-format decoder reads the same ops, times and kernel flags
    as ``jax.profiler.ProfileData``, and the same profile start."""
    ops, start_ns = scopes.decode(tr.find_xplane(trace_dir))
    want = tr.load(trace_dir)
    assert list(ops) == list(want.ops)
    for plane, mine in ops.items():
        assert [(o.label, o.start_ns, o.end_ns, o.kernel) for o in mine] == [
            (o.name, o.start_ns, o.end_ns, o.kernel) for o in want.ops[plane]]
    assert scopes.load(trace_dir).spans == want.spans
    assert start_ns > 0


@pytest.mark.parametrize("trace_dir", [OLD, SCOPED], ids=["old", "scoped"])
def test_decoder_resolves_tf_op(trace_dir):
    """Every op has its event metadata and every op of the forward its
    ``tf_op``; the ops without one are copies and transfers of arguments
    that XLA added, under 1% of device time."""
    ops, _ = scopes.decode(tr.find_xplane(trace_dir))
    all_ops = [o for evs in ops.values() for o in evs]
    assert all(o.label for o in all_ops)
    with_tf_op = [o for o in all_ops if o.tf_op]
    # An op of the forward, or the copy that lays out its input ``xx``.
    assert with_tf_op and all(o.tf_op.startswith("jit(") or o.tf_op == "xx:"
                              for o in with_tf_op)
    without = [o for o in all_ops if not o.tf_op]
    assert {o.label.split()[1] for o in without} <= COPIES
    assert not any(o.kernel for o in without)
    total = sum(o.end_ns - o.start_ns for o in all_ops)
    assert sum(o.end_ns - o.start_ns for o in without) < 0.01 * total


def test_old_trace_has_no_scopes_and_readers_read_none(tmp_path, monkeypatch):
    """A program that sets no scopes (the parent of the scoped forward)
    gives nothing to read, and no reader raises."""
    shutil.copytree(OLD, tmp_path / "vgg16-b8-offline")
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    scopes._cache.clear()
    sc = scopes.load(str(tmp_path / "vgg16-b8-offline"))
    assert not sc.has_scopes and sc.coverage() == 0.0
    ctx = SimpleNamespace(reduced=tr.reduce(tr.load(OLD)),
                          window=SimpleNamespace(forwards=8), chips=1,
                          peaks=None, work=None, dtypes=None, batch=8)
    for name in NEW_METRICS:
        assert metric_reader(name)(ctx) is None, name


def test_scoped_trace_coverage_and_table():
    sc = scopes.load(SCOPED)
    assert sc.coverage() >= 0.99
    layers = sc.layers()
    table = sc.layer_table()
    assert table is not None and table["name"] == sc.forward_name()
    planned = {l["scope"] for l in table["layers"]}
    assert set(layers) - {scopes.EXIT} <= planned
    # Every conv of VGG-16 ran a Pallas kernel inside its own scope.
    convs = [l["scope"] for l in table["layers"] if l["kind"] == "conv"]
    assert len(convs) == 14 and all(layers[s].kernel_s > 0 for s in convs)
    assert sum(t.kernel_s for t in layers.values()) == pytest.approx(
        tr.reduce(tr.load(SCOPED)).total_kernel_s, rel=1e-9)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_reproduce_the_run(name, run_ctx):
    want = _result()["metrics"][name]["value"]
    assert metric_reader(name)(run_ctx) == pytest.approx(want, rel=1e-9)


def test_families_sum_to_the_kernel_time_and_glue_within_xla(run_ctx):
    sc = scopes.for_run(run_ctx)
    layers = sc.layers().values()
    winograd = sum(t.kernel_s for t in layers if t.algorithm == "winograd")
    gemm = sum(t.kernel_s for t in layers
               if t.algorithm in ("im2col_gemm", "direct"))
    assert winograd > 0 and gemm > 0
    assert winograd + gemm == pytest.approx(run_ctx.reduced.total_kernel_s,
                                            rel=0.01)
    glue = metric_reader("winograd_glue_share")(run_ctx)
    assert 0 < glue <= metric_reader("xla_share")(run_ctx)


def test_run_must_match_the_window(run_ctx):
    """A trace whose window is not the run's is not read."""
    run_ctx.reduced.window_s += 0.5
    assert scopes.for_run(run_ctx) is None


def _synthetic():
    O = scopes.ScopedOp
    w, g = "L000.conv.winograd", "L018.conv.im2col_gemm"
    ops = {"/device:TPU:0": [
        O("a", 100, 200, True, f"jit(fwd_x)/{w}/pallas_call:", w),
        O("b", 200, 260, False, f"jit(fwd_x)/{w}/pad:", w),
        O("c", 300, 400, True, f"jit(fwd_x)/{g}/pallas_call:", g),
        O("d", 400, 420, False, "jit(fwd_x)/exit/slice:", "exit"),
        O("e", 420, 425, False, "", None)]}
    program = [("run", 0, 150), ("run.call", 10, 140), ("run", 280, 320)]
    table = {"name": "fwd_x", "layers": [
        {"scope": w, "index": 0, "kind": "conv", "predicted_s": 1.0},
        {"scope": g, "index": 18, "kind": "conv", "predicted_s": 3.0},
        {"scope": "L002.maxpool", "index": 2, "kind": "maxpool",
         "predicted_s": None}]}
    return scopes.Scoped(ops, [("window", 0, 1000)], program, [table])


def test_synthetic_layers_idle_and_plan_error(monkeypatch):
    sc = _synthetic()
    layers = sc.layers()
    assert list(layers) == ["L000.conv.winograd", "L018.conv.im2col_gemm", "exit"]
    assert layers["L000.conv.winograd"].kernel_s == pytest.approx(100e-9)
    assert layers["L000.conv.winograd"].glue_s == pytest.approx(60e-9)
    assert sc.coverage() == pytest.approx(280 / 285)
    # Idle inside ``run``: [0, 100] and [280, 300].
    assert sc.idle_in_spans("run") == {"/device:TPU:0": pytest.approx(120e-9)}
    monkeypatch.setattr(scopes, "for_run", lambda ctx: sc)
    ctx = SimpleNamespace(reduced=SimpleNamespace(total_busy_s=285e-9),
                          window=SimpleNamespace(forwards=2))
    # m = (160, 100) of 260, p = (1, 3) of 4.
    assert metric_reader("plan_error")(ctx) == pytest.approx(
        50 * (abs(160 / 260 - 0.25) + abs(100 / 260 - 0.75)))
    assert metric_reader("run_idle_ms")(ctx) == pytest.approx(120e-9 * 1e3 / 2)
    assert metric_reader("winograd_glue_share")(ctx) == pytest.approx(
        100 * 60 / 285)


def test_scope_names():
    assert scopes.scope_of("jit(f)/L000.conv.winograd/jit(_pad)/pad:") == \
        "L000.conv.winograd"
    assert scopes.scope_of("jit(f)/exit/slice:") == "exit"
    assert scopes.scope_of("jit(f)/transpose:") is None
    assert scopes.scope_of("jit(f)/L001.fc/L002.fc/dot:") is None
    assert scopes.parse_scope("L018.conv.im2col_gemm") == (18, "conv", "im2col_gemm")
    assert scopes.parse_scope("L004.shortcut") == (4, "shortcut", None)


def test_table_command(capsys):
    assert scopes.main([SCOPED]) == 0
    out = capsys.readouterr().out
    assert "L000.conv." in out and "per forward" in out
