"""A run of a tiny ResNet configuration on the CPU end to end (Pallas
kernels in interpret mode): the padded pool, the projection route and the
ReLU after each add run the same in the program and in
``references/resnet.py``, so a sound run comes out ``correct``."""
import time

import jax

from harness.cell import run_cell
from harness.spec import Cell, load_limits


def _conv(co, k=1, s=1, act="relu"):
    return {"kind": "conv", "out_channels": co, "kernel": k, "stride": s,
            "batch_norm": True, "activation": act}


TINY = {
    "name": "tiny-resnet", "source": "test", "input_hw": [32, 32],
    "in_channels": 3, "dtype": "float32", "reference": "resnet",
    "layers": [
        _conv(8, 7, 2), {"kind": "maxpool", "size": 3, "stride": 2, "pad": 1},
        _conv(8), _conv(8, 3, 2), _conv(32, act="linear"),
        {"kind": "route", "from_layers": [1]}, _conv(32, 1, 2, act="linear"),
        {"kind": "shortcut", "from_layers": [4], "activation": "relu"},
        _conv(8), _conv(8, 3), _conv(32, act="linear"),
        {"kind": "shortcut", "from_layers": [7], "activation": "relu"},
        {"kind": "fc", "out_channels": 10, "activation": "linear",
         "batch_norm": False},
    ],
}
MIX = {"kind": "closed_loop", "batch": 2, "in_flight": 2, "pool_batches": 1,
       "compare": "all"}
PEAKS = {"matmul_flops_per_s": 1e12, "int8_ops_per_s": 2e12, "hbm_bytes_per_s": 1e11}


def test_sound_resnet_run_is_correct():
    cell = Cell("tiny-resnet", 1, TINY, MIX,
                [{"name": "images_per_s", "unit": "-"}], [])
    res = run_cell(cell, 2**40 + 9, 0.5, False, jax.devices()[:1], PEAKS,
                   time.perf_counter(), load_limits("resnet50-b64-offline"),
                   options={"interpret": True, "cache_path": None})
    assert res["correct"], res["checks"]
    assert res["checks"]["worst_rel_l2"]["value"] < 1e-6
