"""Flash-attention Pallas kernel (forward): online-softmax attention with
BlockSpec VMEM tiling.

Beyond-paper kernel targeting the LM cells' attention memory term (see
EXPERIMENTS.md §Perf, gemma2 next-levers): never materializes the (S, S)
score matrix.  Grid (batch*heads, q-blocks, kv-blocks), kv innermost
('arbitrary') with fp32 running max / sum / accumulator in VMEM scratch —
the same schedule as the pure-JAX `attention_chunked`, which doubles as its
oracle.  Supports causal masking, sliding windows, and logit softcap
(gemma2), so every attention arch in the zoo can route through it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, causal: bool, window: int,
                  logit_cap: float, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if logit_cap > 0:
        s = jnp.tanh(s / logit_cap) * logit_cap

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + p.sum(axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(kj == pl.num_programs(2) - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-37)[:, None]
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)[None]


def flash_attention_pallas(
    q: jnp.ndarray,  # (BH, S, hd)
    k: jnp.ndarray,  # (BH, Sk, hd)
    v: jnp.ndarray,
    bq: int = 256,
    bk: int = 256,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, s, hd = q.shape
    sk = k.shape[1]
    assert s % bq == 0 and sk % bk == 0
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        logit_cap=logit_cap, scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
