"""Graph500's Kronecker graph as a column-normalised transition matrix.

Graph500 specification, section "Graph Generation": M = edgefactor * 2**scale
edges, each placed bit by bit into one of four quadrants with probabilities
A, B, C and 1 - A - B - C, vertex labels then randomly permuted.  For PageRank
and SpMV serving the edges are made undirected (both directions), self-loops
and duplicates are dropped, and column j is divided by the degree of j, so
every column of a vertex with edges sums to 1 and isolated vertices leave an
empty row and column.
"""
from __future__ import annotations

import numpy as np


def edges(params: dict, seed: int):
    """The raw Kronecker edge list ``(u, v)``, labels permuted by ``seed``."""
    scale = int(params["scale"])
    n = 1 << scale
    m = int(params["edgefactor"]) * n
    a, b, c = float(params["A"]), float(params["B"]), float(params["C"])
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(params["structure_seed"]), 0x6b726f6e]))
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        u_bit = rng.random(m) > ab
        v_bit = rng.random(m) > np.where(u_bit, c_norm, a_norm)
        u += u_bit.astype(np.int64) << bit
        v += v_bit.astype(np.int64) << bit
    perm = np.random.default_rng(np.random.SeedSequence(
        [seed, 0x7065726d])).permutation(n)
    return perm[u], perm[v], n


def triplets(params: dict, seed: int):
    """Return ``(rows, cols, values, shape)``: int64, int64, float64, tuple.

    Triplets come sorted by row, then column, and carry no duplicates.
    """
    u, v, n = edges(params, seed)
    r = np.concatenate([u, v])
    c = np.concatenate([v, u])
    keep = r != c
    key = np.unique(r[keep] * n + c[keep])
    r, c = key // n, key % n
    degree = np.bincount(c, minlength=n)
    return r, c, 1.0 / degree[c], (n, n)
