"""Brute-force k-nearest-neighbour search, two ways.

1. :func:`paper_insertion_knn` — a literal port of the paper's Fig. 1 / Fig. 3
   per-thread algorithm (fixed k-buffer, bubble/insertion maintenance).  Used
   only as a test oracle documenting the original CUDA logic.

2. :func:`running_k_best` — the TPU-native adaptation: a *branch-free,
   vectorised k-pass min-extract merge* that folds a tile of candidate
   distances into a running (rows, k) best set.  This is the exact same
   O(k * m) work the paper's insertion sort does in the worst case, but
   expressed as dense vector ops (min / iota / select) that lower both in
   XLA and inside Pallas Mosaic kernels (no argmin, duplicate-safe).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def first_true(mask, axis: int):
    """Keep only the first True along ``axis``.

    An iota/min-index form rather than ``cumsum(mask) == 1``: Mosaic has no
    ``cumsum`` lowering, while iota and min-reductions lower in XLA and in
    Pallas kernels alike.  It only selects, so it is bitwise the cumsum form.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, mask.shape, axis)
    none = jnp.asarray(mask.shape[axis], jnp.int32)
    first = jnp.min(jnp.where(mask, idx, none), axis=axis, keepdims=True)
    return idx == first


def running_k_best(best, d2_tile, axis: int = 1):
    """Merge a tile of squared distances into the running k-best set.

    Args:
      best: (rows, k) current k smallest values per row (``(k, cols)`` for
        ``axis=0``), ascending not required, +inf for empty slots.
      d2_tile: (rows, t) new candidate values (``(t, cols)`` for ``axis=0``).

    Returns:
      the k smallest of ``concat([best, d2_tile], axis)`` per row (column),
      ascending along ``axis``.

    Implementation: k passes; each pass extracts the min and masks out
    exactly one occurrence (the first, via :func:`first_true` — duplicate-
    safe and free of argmin, which Mosaic TPU does not lower).  The Pallas
    kernels call it on in-VMEM tiles with ``axis`` their data axis.
    """
    k = best.shape[axis]
    c = jnp.concatenate([best, d2_tile], axis=axis)
    inf = jnp.asarray(jnp.inf, c.dtype)
    outs = []
    for _ in range(k):
        v = jnp.min(c, axis=axis, keepdims=True)
        outs.append(v)
        c = jnp.where(first_true(c == v, axis), inf, c)
    return jnp.concatenate(outs, axis=axis)


def k_smallest(values, k: int):
    """k smallest entries of the last axis, ascending (thin top_k wrapper)."""
    import jax

    neg, _ = jax.lax.top_k(-values, k)
    return -neg


def paper_insertion_knn(d: np.ndarray, k: int) -> np.ndarray:
    """Fig. 1 / Fig. 3 lines 11-32 of the paper, verbatim (numpy, one query).

    Args:
      d: (m,) squared distances from one interpolated point to all data points.
      k: neighbourhood size.

    Returns:
      (k,) the k smallest squared distances, ascending.
    """
    m = d.shape[0]
    buf = d[:k].copy()
    # "sort the first k distances in ascending order" (bubble sort, Fig. 3)
    for i in range(k - 1):
        for j in range(k - 1 - i):
            if buf[j] > buf[j + 1]:
                buf[j], buf[j + 1] = buf[j + 1], buf[j]
    # stream the remaining m-k candidates
    for i in range(k, m):
        dist = d[i]
        if dist < buf[k - 1]:
            buf[k - 1] = dist
            # neighbouring compare-and-swap back to sorted order
            for j in range(k - 2, -1, -1):
                if buf[j] > buf[j + 1]:
                    buf[j], buf[j + 1] = buf[j + 1], buf[j]
                else:
                    break
    return buf
