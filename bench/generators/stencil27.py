"""HPCG's 27-point stencil as COO triplets.

HPCG technical specification (Heroux, Dongarra and Luszczek, SAND2013-8752),
section 2: each grid point of an nx x ny x nz box is coupled to itself with
26 and to each of its (up to 26) neighbours with -1, and rows are numbered
``ix + nx * (iy + ny * iz)``.  The matrix is symmetric positive definite.
The stencil carries no randomness, so the seed is not used.

The matrix is 27 I - Tz (x) Ty (x) Tx, with T the tridiagonal matrix of ones
on each axis, so its eigenvectors are products of the sine modes
sin(pi j k / (m + 1)) of the three axes.  :func:`solutions` uses them to
give solver traffic the same work on every seed.
"""
from __future__ import annotations

import numpy as np

DIAGONAL = 26.0
OFF_DIAGONAL = -1.0


def sine_modes(m: int) -> np.ndarray:
    """The orthonormal eigenvectors of the m x m tridiagonal ones, by column."""
    k = np.arange(1, m + 1)
    return np.sqrt(2.0 / (m + 1)) * np.sin(np.outer(k, k) * np.pi / (m + 1))


def solutions(params: dict, rng: np.random.Generator, count: int):
    """``count`` solutions x*, float64 (count, n), with weight +1 or -1 on
    every eigenvector of the matrix, the signs drawn from ``rng``.

    That is the spectrum a Gaussian x* has in expectation; with it fixed, CG
    on b = A x* takes the same steps to a relative tolerance for every x*,
    so the seed changes the vectors and not the work.
    """
    nx, ny, nz = int(params["nx"]), int(params["ny"]), int(params["nz"])
    signs = rng.choice((-1.0, 1.0), size=(count, nz, ny, nx))
    x = np.einsum("az,by,cx,nzyx->nabc", sine_modes(nz), sine_modes(ny),
                  sine_modes(nx), signs, optimize=True)
    return x.reshape(count, nx * ny * nz)


def triplets(params: dict, seed: int):
    """Return ``(rows, cols, values, shape)``: int64, int64, float64, tuple.

    Triplets come sorted by row, then column, and carry no duplicates.
    """
    del seed
    nx, ny, nz = int(params["nx"]), int(params["ny"]), int(params["nz"])
    iz, iy, ix = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                             indexing="ij")
    ix, iy, iz = ix.ravel(), iy.ravel(), iz.ravel()
    rows, cols, vals = [], [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                jx, jy, jz = ix + dx, iy + dy, iz + dz
                ok = ((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
                      & (jz >= 0) & (jz < nz))
                rows.append((ix + nx * (iy + ny * iz))[ok])
                cols.append((jx + nx * (jy + ny * jz))[ok])
                centre = dx == 0 and dy == 0 and dz == 0
                vals.append(np.full(int(ok.sum()),
                                    DIAGONAL if centre else OFF_DIAGONAL))
    r = np.concatenate(rows).astype(np.int64)
    c = np.concatenate(cols).astype(np.int64)
    v = np.concatenate(vals)
    order = np.lexsort((c, r))
    n = nx * ny * nz
    return r[order], c[order], v[order], (n, n)
