"""Graph500's Kronecker graph with edge weights, for kernel 3 (SSSP).

Graph500 specification, "Graph Generation" and "Kernel 3 – Single Source
Shortest Paths": the Kronecker edge list of ``kronecker.py`` (the same
structure, the same labels for a seed), each generated edge given a weight
uniform on [0, 1).  The weights are drawn in float64 from the fixed
``structure_seed``, one per generated edge in the order the structure
generates them, so every seed runs the same weighted graph up to labels.
The graph is made undirected with the same weight both ways; self-loops are
dropped, and of parallel edges the lightest is kept (the only one a
shortest path can take).

:func:`search_keys` draws Graph500's search keys from those triplets:
vertices with at least one edge that is not a self-loop, uniform without
replacement from the fixed ``structure_seed``, among those vertices ordered
by the sum of their edge weights.  The weights are the structure's, so that
order is the same under every seed's labels, and every seed searches from
the same vertices.
"""
from __future__ import annotations

import numpy as np

from bench.harness import manifest as M

WEIGHTS, KEYS = 0x77656967, 0x6b657973  # streams of the structure seed


def _stream(params: dict, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(params["structure_seed"]), stream]))


def triplets(params: dict, seed: int):
    """Return ``(rows, cols, weights, shape)``: int64, int64, float64, tuple.

    Triplets come sorted by row, then column, each coordinate once; a
    weight may be 0, and is then an edge of weight 0.
    """
    u, v, n = M.generator("kronecker").edges(params, seed)
    w = _stream(params, WEIGHTS).random(len(u))
    r, c = np.concatenate([u, v]), np.concatenate([v, u])
    w = np.concatenate([w, w])
    keep = r != c
    key, w = r[keep] * n + c[keep], w[keep]
    order = np.lexsort((w, key))
    key, w = key[order], w[order]
    first = np.ones(len(key), bool)
    first[1:] = key[1:] != key[:-1]  # the lightest of parallel edges
    key, w = key[first], w[first]
    return key // n, key % n, w, (n, n)


def search_keys(params: dict, rows, weights) -> np.ndarray:
    """``params["keys"]`` search keys (int64) of the graph whose triplets
    :func:`triplets` gave as ``rows`` and ``weights``."""
    order = np.lexsort((weights, rows))  # each vertex's sum in weight order
    total = np.bincount(rows[order], weights=weights[order])
    touched = np.unique(rows)  # symmetric: every vertex with an edge
    touched = touched[np.argsort(total[touched], kind="stable")]
    pick = _stream(params, KEYS).choice(len(touched), int(params["keys"]),
                                        replace=False)
    return touched[pick]
