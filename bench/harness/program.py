"""The few calls the benchmark makes into the program under test.

The benchmark hands the program COO triplets and never densifies a matrix
itself.  ``SpmvEngine.register`` takes a dense array, or re-registers a name
from the ``SparseMatrix`` its registry entry holds; the benchmark enters its
matrix through that second path, so that what the program does with the
triplets (statistics, fingerprint, containers, placement) is the program's
own set-up cost.
"""
from __future__ import annotations


def sparse_matrix(rows, cols, values, shape, dtype):
    from repro.api import SparseMatrix

    return SparseMatrix.from_parts(rows, cols, values, shape, dtype=dtype)


def engine(devices):
    from repro.engine import SpmvEngine

    return SpmvEngine(devices=devices)


def stage(eng, scoped_name: str, sm) -> None:
    """File ``sm`` in the engine's registry under ``scoped_name``, so that
    ``register(name, None)`` plans, places and compiles it."""
    from repro.engine.registry import RegisteredMatrix

    eng.registry.add(RegisteredMatrix(
        name=scoped_name, fingerprint="", shape=tuple(sm.shape),
        dtype=sm.dtype.str, stats=None, plan=None, cache_key=None,
        matrix=sm))


def register(eng, name: str, sm):
    """Plan, place and compile ``sm`` in ``eng`` under ``name``."""
    stage(eng, name, sm)
    return eng.register(name, None, warmup=False)
