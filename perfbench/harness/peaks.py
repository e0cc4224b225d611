"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Only published figures go in ``peaks.json``.  float32 and bfloat16 layers
are held to the MXU's one published matmul peak (bf16), so float32 at
``HIGHEST`` reads low by design; int8 layers to the int8 peak.  A device
kind that is not in the table raises: there is no default.
"""
from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(RuntimeError):
    pass


def load_peaks(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    try:
        return table[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(table)})"
        ) from None


def compute_peak(peaks: dict, dtype: str) -> float:
    """Operations per second a layer of ``dtype`` is held to."""
    if dtype in ("float32", "bfloat16"):
        return peaks["matmul_flops_per_s"]
    if dtype == "int8":
        return peaks["int8_ops_per_s"]
    raise ValueError(f"no peak for dtype {dtype!r}")
