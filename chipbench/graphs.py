"""Graph inputs of the cells: Kronecker graphs, relabelled from the run's seed.

``kronecker`` is the Graph500 R-MAT recursion (Chakrabarti et al. 2004)
that the HPEC Graph Challenge builds its graphs with: ``edge_factor * 2**scale``
samples, each choosing one quadrant per bit with probabilities a, b, c and
1 - a - b - c; loops and repeats are dropped and the rest made undirected.
It draws the same numbers in the same order as the program's own
generator, so a structure seed names one graph in both.

A configuration fixes the structure (scale, edge factor, quadrant
probabilities, structure seed).  The run's seed only relabels it: the
active vertices get a random permutation of ``0 .. n-1``, the rows are
shuffled and each row's endpoints may swap.  Trussness does not depend on
labels, so every seed asks for the same work in another order, and the
vertex count, edge count and table sizes the program compiles for stay
the same from seed to seed.
"""

from __future__ import annotations

import numpy as np


def kronecker(scale: int, edge_factor: int, a: float, b: float, c: float,
              seed: int) -> np.ndarray:
    """Canonical (u < v) unique edges of one R-MAT graph, sorted."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    samples = edge_factor * n
    src = np.zeros(samples, np.int64)
    dst = np.zeros(samples, np.int64)
    ab = a + b
    a_norm, c_norm = a / ab, c / (1.0 - ab)
    for _ in range(scale):
        r1 = rng.random(samples)
        r2 = rng.random(samples)
        down = r1 >= ab
        right = np.where(down, r2 >= c_norm, r2 >= a_norm)
        src = 2 * src + down
        dst = 2 * dst + right
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep])
    hi = np.maximum(src[keep], dst[keep])
    key = np.unique(lo * n + hi)
    return np.stack([key // n, key % n], axis=1)


def relabelling(E: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Vertex map of ``E``'s active vertices onto a random order of 0..n-1."""
    active = np.unique(E)
    perm = np.full(int(E.max()) + 1, -1, np.int64)
    perm[active] = rng.permutation(active.shape[0])
    return perm


def shuffled_rows(E: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``E``'s rows in random order, each row's endpoints swapped at random."""
    rows = E[rng.permutation(E.shape[0])]
    swap = rng.random(rows.shape[0]) < 0.5
    rows[swap] = rows[swap][:, ::-1]
    return rows


def graph_from_config(cfg: dict) -> np.ndarray:
    """The configuration's fixed structure."""
    return kronecker(cfg["scale"], cfg["edge_factor"], cfg["a"], cfg["b"],
                     cfg["c"], cfg["structure_seed"])
