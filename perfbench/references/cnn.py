"""Plain float32 reference of a Darknet-style CNN layer table, and the
seeded weights that both the program and this reference run.

Straight ``jax.lax``: ``conv_general_dilated`` with (k // 2) padding,
inference batchnorm (eps 1e-5), relu / leaky (0.1) / linear, max pool
("SAME" windows), shortcut add, route concat, nearest upsample, and fc
after a global average pool.  Matmul precision is explicit on every
contraction: ``"highest"`` is the reference; ``"high3"`` is the control,
three bf16 passes (hi*hi + hi*lo + lo*hi), the next precision below, the
same arithmetic on every backend.  It imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def _layer_channels(layers: Sequence[dict], in_channels: int):
    """Input channels of each layer (after routes)."""
    cins, outs = [], []
    c = in_channels
    for l in layers:
        cins.append(c)
        if l["kind"] in ("conv", "fc"):
            c = l["out_channels"]
        elif l["kind"] == "route":
            c = sum(outs[j] for j in l["from_layers"])
        outs.append(c)
    return cins


def init_params(key, layers: Sequence[dict], in_channels: int):
    """Seeded raw parameters in the program's layout (conv: ``w`` HWIO
    plus a ``bn`` dict or a ``b`` bias; fc: ``w`` (in, out) and ``b``).
    He-scaled weights keep activations near unit scale through every
    layer; batchnorm statistics are random, so folding them is exercised."""
    cins = _layer_channels(layers, in_channels)
    keys = jax.random.split(key, len(layers))
    params = []
    for l, cin, k in zip(layers, cins, keys):
        ks = jax.random.split(k, 5)
        if l["kind"] == "conv":
            kk, co = l.get("kernel", 3), l["out_channels"]
            p = {"w": jax.random.normal(ks[0], (kk, kk, cin, co))
                 * (2.0 / (kk * kk * cin)) ** 0.5}
            if l.get("batch_norm", True):
                p["bn"] = {
                    "gamma": 1.0 + 0.1 * jax.random.normal(ks[1], (co,)),
                    "beta": 0.1 * jax.random.normal(ks[2], (co,)),
                    "mean": 0.1 * jax.random.normal(ks[3], (co,)),
                    "var": jax.random.uniform(ks[4], (co,), minval=0.5, maxval=1.5),
                }
            else:
                p["b"] = 0.1 * jax.random.normal(ks[1], (co,))
        elif l["kind"] == "fc":
            co = l["out_channels"]
            p = {"w": jax.random.normal(ks[0], (cin, co)) * (2.0 / cin) ** 0.5,
                 "b": 0.1 * jax.random.normal(ks[1], (co,))}
        else:
            p = {}
        params.append(p)
    return params


def _split_bf16(x):
    """x = hi + lo + (rounding), hi and lo each a bf16 value held in f32.
    ``reduce_precision`` rounds as a cast to bf16 would; unlike a cast pair
    it is never dropped as "excess precision" by the compiler."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _contract(f, a, b, precision: str):
    """``f(a, b)`` at ``precision``: 'highest' (full fp32) or 'high3' (three
    bf16 passes, each exact in fp32, summed)."""
    if precision == "highest":
        return f(a, b)
    if precision == "high3":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        return f(ah, bh) + f(ah, bl) + f(al, bh)
    raise ValueError(f"unknown precision {precision!r}")


def forward(params, layers: Sequence[dict], x, precision: str = "highest"):
    """The reference forward of ``layers`` on an NHWC float32 batch."""
    hi = lax.Precision.HIGHEST
    outs = []
    cur = x
    for l, p in zip(layers, params):
        kind = l["kind"]
        if kind == "conv":
            k, s = l.get("kernel", 3), l.get("stride", 1)
            pad = l.get("pad")
            pad = k // 2 if pad is None else pad
            conv = functools.partial(
                lax.conv_general_dilated, window_strides=(s, s),
                padding=[(pad, pad), (pad, pad)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
            cur = _contract(conv, cur, p["w"], precision)
            if "bn" in p:
                bn = p["bn"]
                cur = (cur - bn["mean"]) * lax.rsqrt(bn["var"] + BN_EPS)
                cur = cur * bn["gamma"] + bn["beta"]
            else:
                cur = cur + p["b"]
            cur = _activate(cur, l.get("activation", "leaky"))
        elif kind == "maxpool":
            size, s = l.get("size", 2), l.get("stride", 1)
            cur = lax.reduce_window(cur, -jnp.inf, lax.max, (1, size, size, 1),
                                    (1, s, s, 1), "SAME")
        elif kind == "avgpool":
            cur = cur.mean(axis=(1, 2))
        elif kind == "upsample":
            f = l.get("size", 2)
            cur = jnp.repeat(jnp.repeat(cur, f, axis=1), f, axis=2)
        elif kind == "shortcut":
            cur = cur + outs[l["from_layers"][0]]
        elif kind == "route":
            cur = jnp.concatenate([outs[j] for j in l["from_layers"]], axis=-1)
        elif kind == "fc":
            if cur.ndim == 4:
                cur = cur.mean(axis=(1, 2))
            dot = functools.partial(jnp.dot, precision=hi)
            cur = _activate(_contract(dot, cur, p["w"], precision) + p["b"],
                            l.get("activation", "linear"))
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        outs.append(cur)
    return cur


def _activate(x, kind: str):
    if kind == "relu":
        return jnp.maximum(x, 0)
    if kind == "leaky":
        return jnp.where(x > 0, x, 0.1 * x)
    if kind == "linear":
        return x
    raise ValueError(f"unknown activation {kind!r}")
