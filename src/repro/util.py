"""Tiny shared helpers used across core, kernels and benchmarks."""
from __future__ import annotations


def ceil_to(x: int, q: int) -> int:
    """Round ``x`` up to the next multiple of ``q``."""
    return -(-x // q) * q


def pad_bias_row(bias, n_padded: int):
    """(O,) bias -> (1, n_padded) kernel bias row, zero-padded on the tail.

    The single definition of the fused-epilogue bias layout contract, shared
    by the gemm / im2col / winograd wrappers and the layout-aware conv
    dispatch.  The pad is conditional on purpose: a zero-width jnp.pad still
    emits a pad eqn, which would break the network executor's
    no-interior-pad jaxpr guarantee (tests/test_netplan.py).
    """
    if bias is None:
        return None
    import jax.numpy as jnp

    n = bias.shape[0]
    return (jnp.pad(bias, (0, n_padded - n)) if n_padded != n
            else bias).reshape(1, n_padded)


def dot_precision(dtype):
    """The matmul precision for operands of ``dtype``, in the Pallas kernels
    and the XLA ops around them alike.

    float32 gets ``HIGHEST``: a TPU's default contracts fp32 in bf16
    passes, which on a v5e put planned VGG-16's logits 7.3e-3 (relative L2)
    from the fp32 reference, and the Winograd kernels alone 4.8e-2 from a
    float64 conv.  Narrower types (bf16, int8) contract natively: ``None``.
    """
    import jax
    import jax.numpy as jnp

    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
