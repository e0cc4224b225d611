"""A run drives a tiny network on the CPU end to end (Pallas kernels in
interpret mode, the harness's look for a chip skipped): sound, it comes out
``correct``; with the timed path broken underneath, it does not.

Faults are planted in the executor's compiled forward after set-up:

- ``alter_answer``: one answer per executor call altered where produced;
- ``half_batch``: the second half of each batch left out (copies of the
  first half returned in its place);
- ``no_exchange``: on four chips, a batch split whose shards are never
  gathered (every shard returns the first shard's rows).  No cell runs on
  four chips yet; this keeps the harness's batch-sharded path honest for
  the one that will.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from harness.cell import run_cell
from harness.spec import Cell, load_limits

TINY = {
    "name": "tiny", "source": "test", "input_hw": [16, 16], "in_channels": 3,
    "dtype": "float32", "reference": "cnn",
    "layers": [
        {"kind": "conv", "out_channels": 8, "kernel": 3, "stride": 1, "activation": "relu"},
        {"kind": "conv", "out_channels": 8, "kernel": 3, "stride": 2, "activation": "leaky"},
        {"kind": "conv", "out_channels": 8, "kernel": 1, "stride": 1, "activation": "leaky"},
        {"kind": "shortcut", "from_layers": [1]},
        {"kind": "maxpool", "size": 2, "stride": 2},
        {"kind": "fc", "out_channels": 10, "activation": "linear", "batch_norm": False},
    ],
}
#: Tiny mixes, with the end-to-end metrics each reports.
MIXES = {
    "offline": {"kind": "closed_loop", "batch": 4, "in_flight": 2,
                "pool_batches": 2, "compare": "all"},
    "stream": {"kind": "closed_loop", "batch": 1, "in_flight": 1,
               "pool_batches": 3, "compare": 4},
}
METRICS = ["images_per_s", "setup_s"]
PEAKS = {"matmul_flops_per_s": 1e12, "int8_ops_per_s": 2e12, "hbm_bytes_per_s": 1e11}
#: The limit every tiny run is held to: the tightest cell's.
LIMITS = load_limits("vgg16-b8-offline")


def _alter_answer(y):
    return y.at[0].multiply(1.01)


def _half_batch(y):
    h = (y.shape[0] + 1) // 2
    return jnp.concatenate([y[:h], y[:y.shape[0] - h]])


def _no_exchange(y):
    q = max(1, y.shape[0] // 4)
    return jnp.concatenate([y[:q]] * (y.shape[0] // q))


FAULTS = {"alter_answer": _alter_answer, "half_batch": _half_batch,
          "no_exchange": _no_exchange}


def _plant(breaker):
    def fault(compiled):
        for ex in compiled._executors.values():
            fn = ex._fn
            ex._fn = lambda p, x, fn=fn: breaker(fn(p, x))
    return fault


def _run(mix: str, fault=None, chips: int = 1):
    cell = Cell("tiny-" + mix, chips, TINY, MIXES[mix],
                [{"name": m, "unit": "-"} for m in METRICS], [])
    return run_cell(cell, 2**40 + 3, 0.5, False, jax.devices()[:chips], PEAKS,
                    time.perf_counter(), LIMITS,
                    options={"interpret": True, "cache_path": None}, fault=fault)


@pytest.mark.parametrize("mix,chips", [("offline", 1), ("stream", 1), ("offline", 4)])
def test_sound_run_is_correct(mix, chips):
    res = _run(mix, chips=chips)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(METRICS) == set(res["metrics"])
    assert res["device"]["count"] == chips


@pytest.mark.parametrize("mix,fault,chips", [
    ("offline", "alter_answer", 1), ("offline", "half_batch", 1),
    ("stream", "alter_answer", 1), ("offline", "no_exchange", 4),
])
def test_broken_path_is_not_correct(mix, fault, chips):
    res = _run(mix, _plant(FAULTS[fault]), chips=chips)
    assert not res["correct"]
    c = res["checks"]["worst_rel_l2"]
    assert c["value"] > c["limit"]
