"""The work of a CNN forward, counted from its layer table's shapes.

Counted here, not read from the program, so that the same layer reads the
same work whatever algorithm, blocking or precision the program picks.

- Floor operations of a layer: the fewest multiplications (x2) that any
  algorithm in the planner's menu needs.  A 3x3 stride-1 conv counts as
  Winograd F(6,3): 2 * ceil(OH/6) * ceil(OW/6) * 64 * Cin * Cout per image.
  Any other conv counts directly: 2 * OH * OW * Cin * Cout * KH * KW.  An fc
  layer counts 2 * in * out.  No plan can then read above 100% of a peak.
- Floor bytes of a layer: its unpadded input, weights and output at the
  layer's dtype, each moved once (biases left out).
- Least time of a layer: max(ops / peak of its dtype, bytes / HBM rate).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

from harness.peaks import compute_peak

ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class LayerWork:
    index: int
    kind: str
    in_shape: Tuple[int, ...]        # per image: (H, W, C), or (C,) into fc
    out_shape: Tuple[int, ...]
    kernel: int = 0
    stride: int = 0

    @property
    def weight_elems(self) -> int:
        if self.kind == "conv":
            return self.kernel * self.kernel * self.in_shape[-1] * self.out_shape[-1]
        if self.kind == "fc":
            return self.in_shape[-1] * self.out_shape[-1]
        return 0

    @property
    def direct_ops(self) -> int:
        """2 * MACs of the direct algorithm, per image."""
        if self.kind == "conv":
            oh, ow, co = self.out_shape
            return 2 * oh * ow * co * self.in_shape[-1] * self.kernel ** 2
        if self.kind == "fc":
            return 2 * self.in_shape[-1] * self.out_shape[-1]
        return 0

    @property
    def floor_ops(self) -> int:
        """2 * the fewest multiplications of any algorithm, per image."""
        if self.kind == "conv" and self.kernel == 3 and self.stride == 1:
            oh, ow, co = self.out_shape
            return 2 * math.ceil(oh / 6) * math.ceil(ow / 6) * 64 * self.in_shape[-1] * co
        return self.direct_ops

    def floor_bytes(self, batch: int, dtype: str) -> int:
        """Unpadded input and output of ``batch`` images plus the weights,
        each moved once, at ``dtype``."""
        if self.kind not in ("conv", "fc"):
            return 0
        act = math.prod(self.in_shape) + math.prod(self.out_shape)
        return (batch * act + self.weight_elems) * ITEMSIZE[dtype]

    def least_time(self, batch: int, dtype: str, peaks: dict) -> float:
        """The least seconds the chip could take for this layer on ``batch``
        images: the larger of its compute and its memory bound."""
        return max(
            batch * self.floor_ops / compute_peak(peaks, dtype),
            self.floor_bytes(batch, dtype) / peaks["hbm_bytes_per_s"],
        )


def network_work(layers: Sequence[dict], input_hw: Sequence[int],
                 in_channels: int) -> List[LayerWork]:
    """One ``LayerWork`` per layer of a layer table (dicts as in the
    configuration files), shapes propagated as the network runs them."""
    out: List[LayerWork] = []
    h, w = input_hw
    c = in_channels
    flat: Optional[int] = None          # channels once pooled to a vector
    for i, l in enumerate(layers):
        kind = l["kind"]
        in_shape = (c,) if flat is not None else (h, w, c)
        k = s = 0
        if kind == "conv":
            k = l.get("kernel", 3)
            s = l.get("stride", 1)
            p = l.get("pad")
            p = k // 2 if p is None else p
            h = (h + 2 * p - k) // s + 1
            w = (w + 2 * p - k) // s + 1
            c = l["out_channels"]
        elif kind == "maxpool":
            st = l.get("stride", 1)
            h, w = -(-h // st), -(-w // st)
        elif kind == "upsample":
            h, w = h * l.get("size", 2), w * l.get("size", 2)
        elif kind == "route":
            srcs = [out[j].out_shape for j in l["from_layers"]]
            h, w = srcs[0][0], srcs[0][1]
            c = sum(sh[-1] for sh in srcs)
        elif kind == "avgpool":
            flat = c
        elif kind == "fc":
            flat = l["out_channels"]        # global average pool, then dense
            in_shape = (c,)
            c = flat
        elif kind != "shortcut":
            raise ValueError(f"layer {i}: unknown kind {kind!r}")
        out_shape = (c,) if flat is not None else (h, w, c)
        out.append(LayerWork(i, kind, in_shape, out_shape, k, s))
    return out


def forward_least_time(work: Sequence[LayerWork], batch: int,
                       dtypes: Sequence[str], peaks: dict,
                       kinds: Tuple[str, ...] = ("conv",)) -> float:
    """Sum of least times of the layers of ``kinds`` for one forward of
    ``batch`` images; ``dtypes`` is aligned with ``work``."""
    return sum(
        lw.least_time(batch, dt, peaks)
        for lw, dt in zip(work, dtypes) if lw.kind in kinds
    )


def image_peak_seconds(work: Sequence[LayerWork], dtypes: Sequence[str],
                       peaks: dict) -> float:
    """Seconds one image's floor operations take at the peak of each
    layer's dtype: the denominator-free half of MFU."""
    return sum(
        lw.floor_ops / compute_peak(peaks, dt)
        for lw, dt in zip(work, dtypes) if lw.floor_ops
    )
