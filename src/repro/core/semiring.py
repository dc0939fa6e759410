"""The semirings an SpMV runs in, each defined once.

``"plus_times"`` is y = A x.  ``"min_plus"`` is y_i = min_j (A_ij + x_j),
the relaxation step of shortest paths.  A semiring's *zero* is the value of
a missing entry: the identity of its sum and the annihilator of its
product (0 under (+, x), +inf under (min, +)).  The row-slice kernel pads
with it, and the 1D boundary merge and the row assembly start from it and
combine parts with the semiring's sum.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Semiring", "SEMIRINGS", "PLUS_TIMES", "MIN_PLUS", "lookup"]


@dataclass(frozen=True)
class Semiring:
    """One semiring's zero, sum and product, on device and on the host."""

    name: str
    zero: float
    times: Callable  # (a, b) -> elementwise product, jnp
    reduce: Callable  # (a, axis) -> sum along axis, jnp
    segment: Callable  # jax.ops.segment_* of the sum
    scatter: Callable  # (y.at[i], v) -> y with v summed into y[i]
    host_plus: np.ufunc  # elementwise sum on the host (takes ``out=``)

    def fill(self, shape, dtype) -> jax.Array:
        """An array of the semiring's zero."""
        return jnp.full(shape, self.zero, dtype)


PLUS_TIMES = Semiring(
    name="plus_times", zero=0.0, times=jnp.multiply, reduce=jnp.sum,
    segment=jax.ops.segment_sum, scatter=lambda at, v: at.add(v),
    host_plus=np.add)
MIN_PLUS = Semiring(
    name="min_plus", zero=float("inf"), times=jnp.add, reduce=jnp.min,
    segment=jax.ops.segment_min, scatter=lambda at, v: at.min(v),
    host_plus=np.minimum)
SEMIRINGS = {s.name: s for s in (PLUS_TIMES, MIN_PLUS)}


def lookup(name: str) -> Semiring:
    """The semiring called ``name``.

    Raises:
      ValueError: for a name not in :data:`SEMIRINGS`.
    """
    try:
        return SEMIRINGS[name]
    except KeyError:
        raise ValueError(f"unknown semiring {name!r}; one of "
                         f"{tuple(SEMIRINGS)}") from None
