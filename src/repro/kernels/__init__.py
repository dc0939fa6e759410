"""SparseP Pallas TPU kernels (+ pure-jnp oracles and jit'd wrappers).

All kernels run single-RHS SpMV and lane-tiled multi-RHS SpMM through the
same grid (docs/kernels.md).

Modules:
  ref.py         pure-jnp oracles (also the portable XLA production path)
  bcsr_spmv.py   flagship MXU block kernel (BCSR/BCOO), scalar-prefetch windows
  coo_spmv.py    element-granular windowed kernel, one-hot MXU merge (lock-free)
  csr_spmv.py    row-granular planner over the windowed kernel
  ell_spmv.py    padded-row gather kernel (beyond-paper TPU-native format)
  row_slices.py  row-slice layout + XLA tile kernel (COO/CSR on a mesh)
  ops.py         public dispatch (impl="xla" | "pallas"), spmv/spmm
  instrument.py  trace-time kernel-build counters (test observability)
  mode.py        interpret_mode: compiled on TPU, interpreted on CPU
"""
from . import ref  # noqa: F401
from .mode import interpret_mode  # noqa: F401
from .ops import (  # noqa: F401
    pallas_program,
    spmm,
    spmv,
    spmv_local_block,
    spmv_local_coo,
)
