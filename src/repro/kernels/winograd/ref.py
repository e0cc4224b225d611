"""Pure-jnp oracles for the Winograd kernels (reuse core/winograd.py)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.winograd import AT, BT, conv2d_winograd


def input_transform_ref(tiles: jnp.ndarray) -> jnp.ndarray:
    """(8, 8, T, C) -> (8, 8, T, C)."""
    bt = jnp.asarray(BT, tiles.dtype)
    return jnp.einsum("ai,bj,ijtc->abtc", bt, bt, tiles)


def tuple_multiply_ref(v: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """(64, T, C) x (64, C, O) -> (64, T, O), fp32 accumulation."""
    return jnp.matmul(v, u, preferred_element_type=jnp.float32).astype(v.dtype)


def output_transform_ref(m: jnp.ndarray) -> jnp.ndarray:
    """(8, 8, T, O) -> (6, 6, T, O)."""
    at = jnp.asarray(AT, m.dtype)
    return jnp.einsum("xa,yb,abto->xyto", at, at, m)


winograd_conv_ref = conv2d_winograd
