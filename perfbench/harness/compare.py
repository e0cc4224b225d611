"""The comparison that decides ``correct``: each answer the timed path
produced, against the plain float32 reference run on the same image.

The number compared is the worst relative L2 error over the compared
answers, ``max_i ||y_i - r_i|| / ||r_i||`` in float64; its limit is the
cell's (``perfbench/cells/<cell>.json``), set from the readings that
``PERF.md`` lists.  An answer that never came is a failure, not an error.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def reference_rows(ref, params, layers, images: np.ndarray, block: int,
                   precision: str = "highest") -> np.ndarray:
    """The reference's output for every image, in blocks of ``block``
    images (one compiled program per block shape), on the host."""
    import jax

    fwd = jax.jit(lambda p, x: ref.forward(p, layers, x, precision))
    rows = []
    for a in range(0, len(images), block):
        rows.append(np.asarray(fwd(params, images[a:a + block])))
    return np.concatenate(rows)


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def worst_rel_l2(answers: Sequence[Tuple[int, np.ndarray]],
                 ref_rows: np.ndarray) -> float:
    """Worst relative L2 error over ``(image index, answer)`` pairs; inf
    for a non-finite answer or one of the wrong shape."""
    worst = 0.0
    for i, got in answers:
        want = ref_rows[i]
        if np.shape(got) != want.shape or not np.all(np.isfinite(got)):
            return float("inf")
        worst = max(worst, rel_l2(got, want))
    return worst
