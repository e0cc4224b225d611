"""Pallas TPU kernels for the paper's compute hot-spots.

- gemm/:        BLIS-like blocked GEMM (3-loop and 6-loop analogues)
- im2col_gemm/: fused patch-gather + GEMM convolution
- winograd/:    F(6,3) transforms + batched tuple GEMM
Each has ops.py (jitted wrapper) and ref.py (pure-jnp oracle); all are
validated in interpret mode on CPU and AOT-compiled for the v5e
(tests/test_tpu_compile.py).
"""
