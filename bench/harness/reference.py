"""The plain float64 reference: SciPy on the configuration's own triplets.

It imports nothing of the program under test and takes nothing the program
made.  Every comparison here is in float64.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


class Reference:
    """A float64 CSR matrix built from the generator's triplets."""

    def __init__(self, rows, cols, values, shape):
        self.a = sp.csr_matrix(
            (np.asarray(values, np.float64), (rows, cols)), shape=shape)
        self.shape = tuple(shape)


def rel_residual(ref: Reference, x, b) -> np.ndarray:
    """||b - A x||_2 / ||b||_2 per column (x, b: (n,) or (n, k))."""
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    r = b - ref.a @ x
    return np.linalg.norm(r, axis=0) / np.linalg.norm(b, axis=0)


def max_rel_err(got, want) -> np.ndarray:
    """max|got - want| / max|want| per column, in float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.abs(got - want).max(axis=0) / np.abs(want).max(axis=0)
