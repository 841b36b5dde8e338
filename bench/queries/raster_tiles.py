"""The cell centres of a ``cells x cells`` raster over the data extent, one
call per ``tile x tile`` block of cells, the cells of a block in
row-major order.  The raster is the same for every seed; the data under it
is not.

Blocks go in a fixed spread order, so that the first calls of every run
span the whole extent: ``T`` rounds of ``T`` blocks (``T = cells / tile``
blocks a side).  Round ``r`` takes one block in every block row, in
bit-reversed row order, at block column ``(r + order_shift + order_step *
row) mod T``: a Latin square, so each round holds every block row and
every block column once, and a cycle of the ``T`` rounds covers the raster
once.
"""

from __future__ import annotations

import math

import numpy as np


def _bit_reversed(n: int) -> list[int]:
    bits = max(1, (n - 1).bit_length())
    return sorted(range(n), key=lambda i: int(format(i, f"0{bits}b")[::-1], 2))


def block_order(blocks: int, step: int, shift: int) -> list[tuple[int, int]]:
    """``(block_column, block_row)`` in visiting order."""
    if math.gcd(step, blocks) != 1:
        raise ValueError(f"raster_tiles: order_step {step} shares a factor with {blocks} blocks a side")
    rows = _bit_reversed(blocks)
    return [((r + shift + step * row) % blocks, row) for r in range(blocks) for row in rows]


def make(spec: dict, config: dict, seed: int):
    del seed
    cells, tile = int(spec["cells"]), int(spec["tile"])
    if cells % tile:
        raise ValueError(f"raster_tiles: {cells} cells do not split into tiles of {tile}")
    x0, x1, y0, y1 = config["extent"]
    cw, ch = (x1 - x0) / cells, (y1 - y0) / cells
    local = np.arange(tile, dtype=np.float64)
    out = []
    for bx, by in block_order(cells // tile, int(spec["order_step"]), int(spec["order_shift"])):
        cx = x0 + (bx * tile + local + 0.5) * cw
        cy = y0 + (by * tile + local + 0.5) * ch
        gy, gx = np.meshgrid(cy, cx, indexing="ij")
        out.append((gx.ravel().astype(np.float32), gy.ravel().astype(np.float32)))
    return out
