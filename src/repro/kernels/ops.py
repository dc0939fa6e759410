"""Public jit'd SpMV entry points and format dispatch.

Two implementations per format:
  * ``impl="xla"``    — the pure-jnp oracle path (kernels/ref.py).  Lowers on
    every backend; used inside shard_map for the multi-pod dry-run and as the
    CPU production path.
  * ``impl="pallas"`` — the TPU kernels (compiled on TPU, interpreted on
    CPU for validation; :func:`~repro.kernels.mode.interpret_mode`).

`spmv` takes the container formats from core/formats.py; `spmv_local_coo`
is the flat-argument variant the distributed layer calls per shard.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import formats as F
from . import ref
from .bcsr_spmv import bcoo_spmv_pallas
from .coo_spmv import coo_spmv_pallas, plan_chunks
from .csr_spmv import csr_plan_chunks, csr_spmv_pallas
from .ell_spmv import ell_spmv_pallas

__all__ = ["spmv", "spmm", "pallas_program", "spmv_local_coo",
           "spmv_local_block", "ell_spmv_pallas", "kernel_scope"]


def kernel_scope(fmt: str, impl: str, semiring: str = "plus_times"):
    """``jax.named_scope("spmv.<fmt>.<impl>")``, ``spmv.coo.xla``: the name a
    device profile gives a local SpMV kernel's ops (metadata only; the
    compiled program does not change).  A kernel of another semiring than
    (+, x) gets its name as a last part: ``spmv.coo.xla.min_plus``."""
    suffix = "" if semiring == "plus_times" else f".{semiring}"
    return jax.named_scope(f"spmv.{fmt}.{impl}{suffix}")


def _require_concrete(m) -> None:
    """Fail fast when a traced container reaches the Pallas path.

    The (static) chunk plan is built host-side from concrete index arrays;
    a traced container would otherwise fail deep inside chunk planning with
    an opaque numpy-on-Tracer error.
    """
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree_util.tree_leaves(m)):
        raise ValueError(
            "spmv(impl='pallas') requires concrete (non-traced) matrix "
            "arrays: the chunk plan is built host-side from the index "
            "arrays (matrices are preprocessing artifacts, paper §3.1). "
            "Build the plan outside jit/vmap/grad, or use impl='xla' "
            "inside traced code."
        )


def spmv(m, x: jax.Array, impl: str = "xla") -> jax.Array:
    """y = m @ x for any SparseP container format (single device).

    For ``impl="pallas"`` on the scalar formats the (static) chunk plan is
    built host-side from concrete index arrays — matrices are preprocessing
    artifacts (paper §3.1 excludes matrix load/plan time), so `m` must hold
    concrete arrays in that mode.  The kernel's ops sit under
    :func:`kernel_scope`, as on a mesh.
    """
    with kernel_scope(type(m).__name__.lower(), impl):
        return _spmv(m, x, impl)


def _spmv(m, x, impl):
    if impl == "xla":
        if isinstance(m, F.CSR):
            return ref.csr_spmv_ref(m.rowptr, m.colind, m.values, x, m.rows)
        if isinstance(m, F.COO):
            return ref.coo_spmv_ref(m.rowind, m.colind, m.values, x, m.rows, m.nnz)
        if isinstance(m, F.BCSR):
            return ref.bcsr_spmv_ref(m.browptr, m.bcolind, m.bvalues, x, m.rows)
        if isinstance(m, F.BCOO):
            return ref.bcoo_spmv_ref(
                m.browind, m.bcolind, m.bvalues, x, m.rows, m.nblocks
            )
        raise TypeError(type(m))
    if impl == "pallas":
        return pallas_program(m)(x)
    raise ValueError(f"unknown impl {impl!r}")


def pallas_program(m, batch_tile: int | None = None):
    """Build the Pallas SpMV/SpMM callable for a container (plan once).

    The host-side preprocessing (chunk planning for COO/CSR, browptr
    expansion for BCSR) runs exactly once here; the returned callable takes
    x of shape (cols,) or (cols, B) and runs only the kernel.  This is what
    ``repro.api``'s SingleDeviceExecutor compiles at build time so repeated
    ``exe(x)`` / ``exe.batch(X)`` calls pay no per-call planning.

    Args:
      m: a concrete CSR/COO/BCSR/BCOO container (``core.formats``).
      batch_tile: SpMM lane tile override (see the kernel modules).

    Returns:
      ``f(x) -> y`` with y in the kernel accumulation dtype.

    Raises:
      ValueError: if ``m`` holds traced arrays (the plan is host-side).
      TypeError: for an unknown container type.
    """
    import numpy as np

    _require_concrete(m)
    if isinstance(m, F.CSR):
        plan = csr_plan_chunks(
            np.asarray(m.rowptr), np.asarray(m.colind), np.asarray(m.values),
            m.rows,
        )
        return partial(csr_spmv_pallas, plan, batch_tile=batch_tile)
    if isinstance(m, F.COO):
        nnz = int(m.nnz)
        plan = plan_chunks(
            np.asarray(m.rowind)[:nnz],
            np.asarray(m.colind)[:nnz],
            np.asarray(m.values)[:nnz],
            m.rows,
        )
        return partial(coo_spmv_pallas, plan, batch_tile=batch_tile)
    if isinstance(m, (F.BCSR, F.BCOO)):
        browind = (_bcsr_to_bcoo_indices(m) if isinstance(m, F.BCSR)
                   else m.browind)

        def run(x):
            return bcoo_spmv_pallas(
                browind, m.bcolind, m.bvalues, x, m.rows, m.nblocks,
                batch_tile=batch_tile,
            )

        return run
    raise TypeError(type(m))


def spmm(m, X: jax.Array, impl: str = "xla") -> jax.Array:
    """Multi-RHS SpMV: Y = m @ X with X of shape (cols, B) -> (rows, B).

    For ``impl="xla"`` the batch dimension threads through every oracle in
    kernels/ref.py (their gathers/scatters are written over ``x.shape[1:]``).
    For ``impl="pallas"`` each format's kernel runs its lane-tiled SpMM grid
    (the batch axis becomes a grid dimension; the matrix stream is reused
    across batch tiles) — the same kernels the engine's micro-batched path
    compiles, so coalesced requests stay on the Pallas path end to end.

    Args:
      m: any container format from core/formats.py.
      X: (cols, B) right-hand sides.
      impl: "xla" or "pallas" (concrete containers only, like ``spmv``).

    Returns:
      Y (rows, B); for "pallas" in the kernel accumulation dtype.

    Raises:
      ValueError: if X is not 2D, or the impl is unknown, or impl="pallas"
        gets a traced container.
    """
    X = jnp.asarray(X)
    if X.ndim != 2:
        raise ValueError(f"spmm expects X of shape (cols, B); got {X.shape}")
    return spmv(m, X, impl=impl)


def _bcsr_to_bcoo_indices(m: F.BCSR) -> jax.Array:
    k = jnp.arange(m.bcapacity, dtype=jnp.int32)
    browind = jnp.searchsorted(m.browptr, k, side="right").astype(jnp.int32) - 1
    return jnp.clip(browind, 0, m.block_rows - 1)


# ---------------------------------------------------------------------------
# Flat per-shard entry points (called inside shard_map by core/distributed.py)
# ---------------------------------------------------------------------------


def spmv_local_coo(
    rowind: jax.Array,
    colind: jax.Array,
    values: jax.Array,
    nnz: jax.Array,
    x_local: jax.Array,
    out_rows: int,
) -> jax.Array:
    """Local tile SpMV in COO normal form (XLA path; shard-safe)."""
    return ref.coo_spmv_ref(rowind, colind, values, x_local, out_rows, nnz=nnz)


def spmv_local_block(
    browind: jax.Array,
    bcolind: jax.Array,
    bvalues: jax.Array,
    nblocks: jax.Array,
    x_local: jax.Array,
    out_rows: int,
) -> jax.Array:
    """Local tile SpMV in blocked normal form (XLA path; shard-safe)."""
    return ref.bcoo_spmv_ref(
        browind, bcolind, bvalues, x_local, out_rows, nblocks=nblocks
    )
