"""Data sets and query streams for the benchmark, made from a seed.

One general generator: a configuration's ``data_kind`` names a data kind,
``bench/data/<kind>.py``, whose parameters are the configuration's
numbers; a traffic file's ``queries`` block names a query kind,
``bench/queries/<kind>.py``, and its parameters.  Each kind module has one
function ``make``.  A new kind is a new file; nothing here changes.
Everything is numpy on the host, in bulk.

Static shapes do not depend on the seed.  The grid plan under test sizes
arrays from the data (the densest cell, the densest candidate window), so
a data set drawn afresh per seed would give each seed its own programs to
compile.  A data kind therefore draws its point positions once, from the
configuration's ``base_seed``, on a lattice on which the eight symmetries
of the square are exact in float32; ``--seed`` picks one of the
configuration's ``orientations`` (how many of the symmetries it allows),
the order of the points, the z field where the kind has one, and the
queries.
"""

from __future__ import annotations

import numpy as np

from bench import manifest

U64 = (1 << 64) - 1


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A generator for ``seed`` (any int, also negative or above 2**63) and
    a fixed salt, so the streams of one run do not overlap."""
    return np.random.default_rng([int(seed) & U64, *salt])


def dihedral(ix: np.ndarray, iy: np.ndarray, n: int, which: int):
    """One of the eight symmetries of the lattice square ``[0, n-1]^2`` on
    integer coordinates: bit 0 mirrors x, bit 1 mirrors y, bit 2 swaps the
    axes.  Integer arithmetic, so it is exact."""
    if which & 1:
        ix = (n - 1) - ix
    if which & 2:
        iy = (n - 1) - iy
    if which & 4:
        ix, iy = iy, ix
    return ix, iy


def seeded_layout(ix, iy, lattice: int, seed: int, orientations: int):
    """Apply the seed's symmetry, one of the first ``orientations`` (1 keeps
    the base orientation), and the seed's point order to base lattice
    points."""
    rng = rng_for(seed, 1)
    which = int(rng.integers(0, orientations))
    ix, iy = dihedral(ix, iy, lattice, which)
    order = rng.permutation(ix.shape[0])
    return ix[order], iy[order], which


def make_data(config: dict, seed: int):
    """``(x, y, z, info)``: float32 arrays of the configuration's data set,
    whose kind its ``data_kind`` names, and what the seed chose."""
    return manifest.data_kind(config["data_kind"]).make(config, seed)


def make_batches(traffic: dict, config: dict, seed: int):
    """The list of ``(qx, qy)`` float32 batches the window cycles over."""
    spec = traffic["queries"]
    return manifest.query_kind(spec["kind"]).make(spec, config, seed)
