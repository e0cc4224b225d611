"""Plain float32 reference of a ResNet layer table, with torchvision's
``resnet50`` semantics, and the seeded weights the program runs too.

Straight ``jax.lax``: ``conv_general_dilated`` with (k // 2) padding,
inference batchnorm (eps 1e-5) and the conv's named activation; max pool
over a symmetric -inf pad (``MaxPool2d(3, 2, 1)``); a route of one source
(the block input, for the projection branch); a shortcut that adds and then
applies its activation (linear when the table names none); fc after a
global average pool.  The weights and the contraction precisions are those
of ``references.cnn``: ``"highest"`` is the reference, ``"high3"`` (three
bf16 passes) the control.  It imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Sequence

from jax import lax

from references.cnn import BN_EPS, _activate, _contract, init_params

__all__ = ["forward", "init_params"]


def forward(params, layers: Sequence[dict], x, precision: str = "highest"):
    """The reference forward of ``layers`` on an NHWC float32 batch."""
    hi = lax.Precision.HIGHEST
    outs = []
    cur = x
    for l, p in zip(layers, params):
        kind = l["kind"]
        if kind == "conv":
            k, s = l["kernel"], l["stride"]
            conv = functools.partial(
                lax.conv_general_dilated, window_strides=(s, s),
                padding=[(k // 2, k // 2)] * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
            cur = _contract(conv, cur, p["w"], precision)
            bn = p["bn"]
            cur = (cur - bn["mean"]) * lax.rsqrt(bn["var"] + BN_EPS)
            cur = _activate(cur * bn["gamma"] + bn["beta"], l["activation"])
        elif kind == "maxpool":
            size, s, pad = l["size"], l["stride"], l["pad"]
            cur = lax.reduce_window(cur, -float("inf"), lax.max,
                                    (1, size, size, 1), (1, s, s, 1),
                                    ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        elif kind == "route":
            (j,) = l["from_layers"]
            cur = outs[j]
        elif kind == "shortcut":
            cur = _activate(cur + outs[l["from_layers"][0]],
                            l.get("activation", "linear"))
        elif kind == "fc":
            dot = functools.partial(lax.dot, precision=hi)
            cur = _activate(
                _contract(dot, cur.mean(axis=(1, 2)), p["w"], precision) + p["b"],
                l["activation"])
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        outs.append(cur)
    return cur
