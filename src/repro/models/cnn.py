"""Darknet-style CNNs (the paper's evaluation vehicle) on the core conv
dispatcher.

Re-implements the convolutional-layer kernel set the paper vectorizes
(§II.B): im2col+GEMM / Winograd (via core/conv2d.py), plus fill_cpu,
copy_cpu, normalize_cpu, add_bias, scale_bias, activate_array — here as
fused jnp ops.  Layer tables for VGG16 / YOLOv3(-tiny) / ResNet-50 live in
configs/.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.conv_spec import (
    ConvSpec,
    Epilogue,
    apply_activation,
    max_pool,
    max_pool_out_hw,
)
from repro.core.conv2d import conv2d
from repro.models.layers import normal_init


@dataclasses.dataclass(frozen=True)
class CNNLayer:
    kind: str                      # conv | maxpool | upsample | shortcut | route | avgpool | fc
    out_channels: int = 0
    kernel: int = 3
    stride: int = 1
    # conv: None -> kernel//2.  maxpool: None -> Darknet's "SAME" windows;
    # else a symmetric -inf pad of at most size//2 (torchvision's MaxPool2d).
    pad: Optional[int] = None
    batch_norm: bool = True
    # leaky | relu | linear; None resolves per kind: linear on a shortcut
    # (Darknet's parse_shortcut), leaky elsewhere.  A shortcut applies it
    # after the add.
    activation: Optional[str] = None
    from_layers: Tuple[int, ...] = ()  # shortcut/route sources (indices)
    size: int = 2                  # pool size / upsample factor

    def __post_init__(self) -> None:
        if self.activation is None:
            object.__setattr__(
                self, "activation",
                "linear" if self.kind == "shortcut" else "leaky")
        if (self.kind == "maxpool" and self.pad is not None
                and not 0 <= self.pad <= self.size // 2):
            raise ValueError(
                f"maxpool pad {self.pad} outside [0, size // 2 = "
                f"{self.size // 2}]: a window would hold no real element")


def _conv_spec(layer: CNNLayer, in_ch: int) -> ConvSpec:
    pad = layer.pad if layer.pad is not None else layer.kernel // 2
    return ConvSpec(
        in_channels=in_ch,
        out_channels=layer.out_channels,
        kernel_size=(layer.kernel, layer.kernel),
        stride=(layer.stride, layer.stride),
        padding=(pad, pad),
    )


def layer_ref_spans(layers: Sequence[CNNLayer]) -> Tuple[Tuple[int, int], ...]:
    """Every (source, consumer) ``from_layers`` dependency span.

    A route/shortcut at index j consuming layer r needs r's output resident
    wherever j runs; a pipeline-stage cut between them (r < cut <= j) is
    illegal.  Returned sorted by consumer for stable downstream iteration.
    """
    return tuple(
        (r, j)
        for j, l in enumerate(layers)
        for r in getattr(l, "from_layers", ())
    )


# --- The Darknet per-layer kernels (paper §II.B), vectorized -----------------


def activate_array(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind in ("leaky", "relu", "linear"):
        return apply_activation(x, kind)
    return x


def normalize(x, mean, var, eps=1e-5):
    return (x - mean) * jax.lax.rsqrt(var + eps)


def scale_bias(x, scales):
    return x * scales


def add_bias(x, bias):
    return x + bias


def batchnorm_inference(x, p):
    """normalize + scale_bias + add_bias, exactly Darknet's inference path."""
    return add_bias(scale_bias(normalize(x, p["mean"], p["var"]), p["gamma"]), p["beta"])


def fold_batchnorm(params: Sequence[Dict], layers: Sequence[CNNLayer],
                   eps: float = 1e-5) -> List[Dict]:
    """Fold inference-mode batchnorm into conv weights + bias.

    bn(conv(x, w)) = conv(x, w * s) + (beta - mean * s) with
    s = gamma / sqrt(var + eps), so every conv layer reduces to
    conv + bias (+ activation) — the precondition for fusing the whole
    epilogue into the conv kernel's output stage.  Layers without bn pass
    through unchanged; the returned params drop the ``bn`` dict in favor of
    a plain ``b`` bias and plug into ``cnn_forward`` / ``_cnn_infer``.
    """
    folded: List[Dict] = []
    for p, l in zip(params, layers):
        if l.kind == "conv" and "bn" in p:
            bn = p["bn"]
            s = bn["gamma"] * jax.lax.rsqrt(bn["var"] + eps)      # (O,)
            folded.append({
                "w": p["w"] * s,                                  # (kh,kw,C,O)
                "b": bn["beta"] - bn["mean"] * s,
            })
        else:
            folded.append(p)
    return folded


# --- Model init / forward ----------------------------------------------------


def init_cnn(rng, layers: Sequence[CNNLayer], in_channels: int = 3,
             dtype=jnp.float32, num_classes: int = 0) -> List[Dict]:
    params: List[Dict] = []
    ch: List[int] = []
    cur = in_channels
    keys = jax.random.split(rng, len(layers) + 1)
    for i, l in enumerate(layers):
        p: Dict = {}
        if l.kind == "conv":
            p["w"] = normal_init(
                keys[i], (l.kernel, l.kernel, cur, l.out_channels),
                scale=1.0 / (l.kernel * max(cur, 1) ** 0.5), dtype=dtype,
            )
            if l.batch_norm:
                p["bn"] = {
                    "gamma": jnp.ones((l.out_channels,), dtype),
                    "beta": jnp.zeros((l.out_channels,), dtype),
                    "mean": jnp.zeros((l.out_channels,), dtype),
                    "var": jnp.ones((l.out_channels,), dtype),
                }
            else:
                p["b"] = jnp.zeros((l.out_channels,), dtype)
            cur = l.out_channels
        elif l.kind == "route":
            cur = sum(ch[j] for j in l.from_layers)
        elif l.kind == "fc":
            p["w"] = normal_init(keys[i], (cur, l.out_channels),
                                 scale=1.0 / cur ** 0.5, dtype=dtype)
            p["b"] = jnp.zeros((l.out_channels,), dtype)
            cur = l.out_channels
        params.append(p)
        ch.append(cur)
    return params


def _plan_layers(
    layers: Sequence[CNNLayer],
    h: int,
    w: int,
    planner,
    in_channels: int = 3,
    batch: int = 1,
    dtype="float32",
) -> List[Optional[object]]:
    """Resolve a ConvPlan for every conv layer of a network ahead of time.

    Walks the layer table exactly like ``cnn_forward`` does (same shape
    propagation) and asks ``planner`` for each conv's plan at its actual
    input resolution.  Returns a list aligned with ``layers`` (None for
    non-conv layers) that plugs straight into ``cnn_forward(plans=...)``.
    """
    plans: List[Optional[object]] = []
    ch: List[Tuple[int, int, int]] = []
    cur_ch, cur_h, cur_w = in_channels, h, w
    for l in layers:
        plan = None
        if l.kind == "conv":
            spec = _conv_spec(l, cur_ch)
            plan = planner.plan(spec, cur_h, cur_w, batch=batch, dtype=dtype)
            cur_h, cur_w = spec.out_hw(cur_h, cur_w)
            cur_ch = l.out_channels
        elif l.kind == "maxpool":
            cur_h, cur_w = max_pool_out_hw(l, cur_h, cur_w)
        elif l.kind == "upsample":
            cur_h, cur_w = cur_h * l.size, cur_w * l.size
        elif l.kind == "route":
            cur_ch = sum(ch[j][0] for j in l.from_layers)
            cur_h, cur_w = ch[l.from_layers[0]][1], ch[l.from_layers[0]][2]
        elif l.kind == "fc":
            cur_ch = l.out_channels
        plans.append(plan)
        ch.append((cur_ch, cur_h, cur_w))
    return plans


def cnn_forward(
    params: Sequence[Dict],
    layers: Sequence[CNNLayer],
    x: jnp.ndarray,
    impl: str = "jax",
    interpret: Optional[bool] = None,
    planner=None,
    plans: Optional[Sequence[Optional[object]]] = None,
    fuse_epilogue: bool = False,
) -> jnp.ndarray:
    """x (B,H,W,C) NHWC.  ``impl``: 'jax' | 'pallas' | 'xla' (lax.conv).

    ``plans`` (from ``plan_layers``) or ``planner`` routes every conv through
    its cached co-design plan instead of per-call selection.  With
    ``fuse_epilogue`` every conv whose batchnorm has been folded (params
    carry a plain ``b`` bias — see ``fold_batchnorm``) runs bias +
    activation inside the conv kernel's output stage instead of as separate
    elementwise passes; a plan that records ``fused_epilogue`` opts its
    layer in as well.
    """
    outputs: List[jnp.ndarray] = []
    cur = x
    for i, l in enumerate(layers):
        p = params[i]
        if l.kind == "conv":
            spec = _conv_spec(l, cur.shape[-1])
            plan = plans[i] if plans is not None else None
            # bn-folded params carry "b" instead of "bn", regardless of the
            # layer table's batch_norm flag.
            has_bn = "bn" in p
            fuse = (fuse_epilogue or getattr(plan, "fused_epilogue", False))
            fuse = fuse and not has_bn and impl != "xla"
            if impl == "xla":
                from repro.core.conv2d import conv2d_reference

                cur = conv2d_reference(cur, p["w"], spec)
            else:
                epi = (
                    Epilogue(bias=p["b"], activation=l.activation)
                    if fuse else None
                )
                cur = conv2d(
                    cur, p["w"], spec, impl=impl, interpret=interpret,
                    plan=plan, planner=planner, epilogue=epi,
                )
            if fuse:
                outputs.append(cur)
                continue
            if has_bn:
                cur = batchnorm_inference(cur, p["bn"])
            else:
                cur = add_bias(cur, p["b"])
            cur = activate_array(cur, l.activation)
        elif l.kind == "maxpool":
            cur = max_pool(cur, l)
        elif l.kind == "avgpool":
            cur = cur.mean(axis=(1, 2))
        elif l.kind == "upsample":
            cur = jnp.repeat(jnp.repeat(cur, l.size, axis=1), l.size, axis=2)
        elif l.kind == "shortcut":
            cur = activate_array(cur + outputs[l.from_layers[0]], l.activation)
        elif l.kind == "route":
            cur = jnp.concatenate([outputs[j] for j in l.from_layers], axis=-1)
        elif l.kind == "fc":
            if cur.ndim == 4:
                # Global-average pool into the classifier (keeps FC weights
                # input-resolution independent, as Darknet's avgpool does).
                cur = cur.mean(axis=(1, 2))
            cur = activate_array(cur @ p["w"] + p["b"], l.activation)
        outputs.append(cur)
    return cur


@functools.partial(
    jax.jit,
    static_argnames=("layers", "impl", "interpret", "plans", "fuse_epilogue",
                     "fold_bn"),
)
def _cnn_infer(
    params,
    layers: Tuple[CNNLayer, ...],
    x: jnp.ndarray,
    impl: str = "jax",
    interpret: Optional[bool] = None,
    plans: Optional[Tuple[Optional[object], ...]] = None,
    fuse_epilogue: bool = True,
    fold_bn: bool = True,
) -> jnp.ndarray:
    """Jitted whole-network inference (the pre-facade deployment path).

    Rides the network executor (core/netplan.py): one compilation covers
    batchnorm folding (``fold_bn``), the whole-network layout resolution
    (inter-layer channel-padding persistence for planned pallas convs, row
    tiles snapped to divisors of OH), and every conv with its fused bias +
    activation epilogue.  ``layers`` and ``plans`` must be tuples (static,
    hashable; the configs' layer tables already are).  With
    ``fuse_epilogue=False`` — or unfolded batchnorm params, which the
    executor cannot fuse — it falls back to the per-layer ``cnn_forward``
    path.  Standing-process serving should prefer the facade
    (``repro.compile``): it additionally prepares parameters offline (block
    padding + Winograd weight pre-transform) and shards the batch over a
    device mesh.
    """
    if fold_bn:
        params = fold_batchnorm(params, layers)
    if not fuse_epilogue or any(
        l.kind == "conv" and "bn" in p for l, p in zip(layers, params)
    ):
        return cnn_forward(
            params, layers, x, impl=impl, interpret=interpret, plans=plans,
            fuse_epilogue=fuse_epilogue,
        )
    from repro.core.netplan import (
        build_network_plan,
        prepare_net_params,
        run_network,
    )

    netplan = build_network_plan(
        layers, x.shape[1], x.shape[2], in_channels=x.shape[3],
        batch=x.shape[0], plans=plans, impl=impl, dtype=x.dtype,
    )
    prepared = prepare_net_params(netplan, params)      # pretransform=False
    return run_network(netplan, prepared, x, interpret=interpret,
                       pretransformed=(False,) * len(netplan.steps))


def conv_layer_dims(layers: Sequence[CNNLayer], h: int, w: int, in_ch: int = 3):
    """Per-conv-layer (M, N, K) GEMM dims — drives the Table IV benchmark."""
    dims = []
    ch: List[int] = []
    cur_ch, cur_h, cur_w = in_ch, h, w
    for l in layers:
        if l.kind == "conv":
            spec = _conv_spec(l, cur_ch)
            m, n, k = spec.gemm_dims(cur_h, cur_w)
            oh, ow = spec.out_hw(cur_h, cur_w)
            dims.append({
                "layer": len(ch), "M": m, "N": n, "K": k,
                "kernel": l.kernel, "stride": l.stride,
                "h": cur_h, "w": cur_w, "cin": cur_ch, "cout": l.out_channels,
            })
            cur_ch, cur_h, cur_w = l.out_channels, oh, ow
        elif l.kind == "maxpool":
            cur_h, cur_w = max_pool_out_hw(l, cur_h, cur_w)
        elif l.kind == "upsample":
            cur_h, cur_w = cur_h * l.size, cur_w * l.size
        elif l.kind == "route":
            cur_ch = sum(ch[j][0] for j in l.from_layers)
            cur_h, cur_w = ch[l.from_layers[0]][1], ch[l.from_layers[0]][2]
        elif l.kind == "shortcut":
            pass
        ch.append((cur_ch, cur_h, cur_w))
    return dims
