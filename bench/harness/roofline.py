"""The least bytes a float32 SpMV must move, from nnz and shape.

``nnz * 4`` for the values, ``cols * 4`` for x and ``rows * 4`` for y.  Index bytes are left out on purpose: a format that compresses or infers
its indices must not read above 100%, and the count must be the same
whatever container or kernel the program picks.
"""
from __future__ import annotations

from .peaks import peaks


def spmv_bytes(nnz: int, shape, itemsize: int = 4) -> int:
    """Least bytes of one y = A x."""
    rows, cols = shape
    return int(itemsize * (nnz + rows + cols))


def roofline_pct(bytes_moved: float, busy_s: float, device_kind: str):
    """Least time for ``bytes_moved`` at peak HBM bandwidth over the device's
    busy time, in percent; None where nothing ran on the device."""
    if not busy_s or busy_s <= 0 or not bytes_moved:
        return None
    least_s = bytes_moved / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
