"""Layer scopes in the planned forward and the program's host spans.

``run_network`` wraps each planned layer's ops in a named scope
(``L{index:03d}.{kind}``, with the resolved algorithm for convs; ``exit``
for the exit crop), which XLA keeps in every instruction's ``op_name`` and a
profiler trace carries to each device op.  ``repro.spans`` keeps the
program's host spans and each executor's layer table for a trace reader.

  - the compiled forward of VGG-16 and YOLOv3-20 (CPU, small input, Pallas
    kernels in interpret mode): every instruction's ``op_name`` carries
    exactly one layer scope or ``exit``, whose index, kind and algorithm
    are those of the plan's step;
  - a pipeline slice names its layers by their absolute indices;
  - the span record: ``time.time_ns``, bounded, ``run.*`` nested inside
    ``run``, one layer table per executor;
  - ``CompiledCNN.run`` records its spans.
"""
import re
import time

import jax
import jax.numpy as jnp
import pytest

import repro
from repro import spans
from repro.configs import vgg16, yolov3
from repro.core.netplan import (
    EXIT_SCOPE,
    NetworkExecutor,
    layer_scope,
    layer_table,
    plan_network,
    resolve_algorithm,
    run_network,
)
from repro.core.planner import Planner
from repro.models.cnn import CNNLayer, init_cnn

C = CNNLayer
_SCOPE = re.compile(r"(?:^|/)(L\d{3}\.[A-Za-z0-9_.]+?|exit)(?=/|$)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
MODELS = {"vgg16": vgg16.LAYERS, "yolov3-20": yolov3.LAYERS_20}


#: The op_name of a parameter, or of a copy XLA makes of one: the name of
#: the forward's argument, not an op of the forward.
_ARGUMENT = re.compile(r"^(prms\[\d+\]\[\\'\w+\\'\]|xx?)$")


def _scopes_by_op(hlo_text: str):
    """Each op_name of an op of the forward -> the set of scopes it
    carries.  Parameters (of the forward, or of a reducer's region) and
    copies named after the forward's arguments are left out; any other
    op_name must belong to the jitted forward."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if not m or " parameter(" in line or _ARGUMENT.match(m.group(1)):
            continue
        assert m.group(1).startswith("jit("), line
        out[m.group(1)] = set(_SCOPE.findall(m.group(1)))
    return out


@pytest.fixture(scope="module", params=sorted(MODELS))
def compiled_forward(request):
    """(netplan, executor, optimized HLO text) of a planned forward at
    32x32, batch 1, Pallas kernels in interpret mode."""
    layers = MODELS[request.param]
    netplan = plan_network(layers, 32, 32,
                           Planner(impl="pallas", cache_path=None), batch=1)
    ex = NetworkExecutor(netplan, init_cnn(jax.random.PRNGKey(0), layers),
                         interpret=True, devices=jax.devices()[:1])
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    text = ex._fn.lower(ex.params, x).compile().as_text()
    return netplan, ex, text


def test_every_op_carries_one_layer_scope(compiled_forward):
    netplan, ex, text = compiled_forward
    by_op = _scopes_by_op(text)
    assert len(by_op) > 50
    unscoped = [n for n, s in by_op.items() if len(s) != 1]
    assert not unscoped, unscoped[:5]
    table = layer_table(netplan)
    jitted = {n.split("/", 1)[0] for n in by_op}
    assert jitted == {f"jit({table['name']})"}
    # The input's argument name, which the table gives a trace reader.
    assert f'op_name="{table["input"]}"' in text


def test_scopes_match_the_plan(compiled_forward):
    netplan, ex, text = compiled_forward
    found = set().union(*_scopes_by_op(text).values())
    layers = found - {EXIT_SCOPE}
    assert {int(s[1:4]) for s in layers} == {s.index for s in netplan.steps}
    for scope in layers:
        step = netplan.steps[int(scope[1:4])]
        kind, _, algo = scope[5:].partition(".")
        assert kind == step.layer.kind, scope
        if kind == "conv":
            assert algo == resolve_algorithm(step.spec, step.plan,
                                             *step.in_hw).value, scope
        else:
            assert algo == "", scope
        assert scope == layer_scope(step)
    assert (EXIT_SCOPE in found) == bool(netplan.exit_layout.pad_c)


def test_pipeline_slice_keeps_absolute_indices():
    netplan = plan_network(vgg16.LAYERS, 32, 32,
                           Planner(impl="jax", cache_path=None), batch=1)
    params = init_cnn(jax.random.PRNGKey(0), vgg16.LAYERS)
    ex = NetworkExecutor(netplan, params, devices=jax.devices()[:1])
    start, stop = 3, 9
    h, w = netplan.steps[start].in_hw
    c = netplan.steps[start - 1].out_layout.phys_c

    def stage(prms, x):
        return run_network(netplan, prms, x, pretransformed=ex.pretransformed,
                           start=start, stop=stop)

    x = jax.ShapeDtypeStruct((1, h, w, c), jnp.float32)
    text = jax.jit(stage).lower(ex.params[start:stop], x).compile().as_text()
    by_op = _scopes_by_op(text)
    assert all(len(s) == 1 for s in by_op.values())
    found = set().union(*by_op.values())
    assert found == {layer_scope(s) for s in netplan.steps[start:stop]}
    assert {int(s[1:4]) for s in found} == set(range(start, stop))


def test_span_record_clock_bound_and_nesting(monkeypatch):
    ticks = iter(range(100, 10**6, 10))
    monkeypatch.setattr(spans.time, "time_ns", lambda: next(ticks))
    r = spans.Record(ring=3)
    with r.span("run"):
        with r.span("run.asarray"):
            pass
        with r.span("run.call"):
            pass
    assert r.spans() == [("run.asarray", 110, 120), ("run.call", 130, 140),
                         ("run", 100, 150)]
    for _ in range(5):
        with r.span("x"):
            pass
    assert [n for n, _, _ in r.spans()] == ["x", "x", "x"]
    assert r.spans()[-1] == ("x", 240, 250)


def test_span_record_holds_one_table_per_executor():
    layers = (C("conv", out_channels=8, kernel=3, activation="relu"),
              C("maxpool", size=2, stride=2))
    netplan = plan_network(layers, 8, 8, Planner(impl="jax", cache_path=None),
                           batch=2)
    before = len(spans.RECORD.layer_tables())
    ex = NetworkExecutor(netplan, init_cnn(jax.random.PRNGKey(0), layers),
                         devices=jax.devices()[:1])
    tables = spans.RECORD.layer_tables()
    assert len(tables) == min(before + 1, spans.TABLES)
    table = tables[-1]
    assert table == layer_table(netplan)
    assert table["batch"] == 2 and table["input_hw"] == [8, 8]
    assert [l["scope"] for l in table["layers"]] == [
        "L000.conv." + table["layers"][0]["algorithm"], "L001.maxpool"]
    plan = netplan.steps[0].plan
    assert table["layers"][0]["predicted_s"] == plan.predicted_s
    assert table["layers"][1]["predicted_s"] is None
    assert ex._fn.__name__ == table["name"]


def test_compiled_run_records_its_spans():
    layers = (C("conv", out_channels=8, kernel=3, activation="relu"),)
    model = repro.CNNModel(layers, (8, 8), name="spans")
    compiled = repro.compile(model, init_cnn(jax.random.PRNGKey(0), layers),
                             repro.ExecutionOptions(impl="jax", batch=1,
                                                    cache_path=None))
    t0 = time.time_ns()
    jax.block_until_ready(compiled.run(jnp.ones((1, 8, 8, 3))))
    t1 = time.time_ns()
    mine = [s for s in spans.RECORD.spans() if s[1] >= t0 and s[2] <= t1]
    names = [n for n, _, _ in mine]
    assert names == ["run.asarray", "run.executor", "run.call", "run"]
    _, a, b = mine[-1]
    assert all(a <= s <= e <= b for _, s, e in mine[:-1])
