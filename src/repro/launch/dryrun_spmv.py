import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ must precede jax imports (device count locks at first init)
"""Production-mesh dry-run for the paper's own workload: distributed SpMV.

Lowers + compiles the 1D (broadcast-x), 1D-ring (overlapped) and 2D
(equally-sized / psum_scatter) SpMV programs for a paper-scale synthetic
scale-free matrix on the single-pod (16,16) and multi-pod (2,16,16) meshes,
and prints memory/cost/collective numbers — the SpMV rows of EXPERIMENTS.md
§Dry-run and the substrate for the SpMV §Perf iterations.

    PYTHONPATH=src python -m repro.launch.dryrun_spmv \
        [--rows 1048576] [--nnz-per-row 16]
"""
import argparse
import json

import numpy as np
import jax
import jax.numpy as jnp

from repro import compat
from repro.analysis import roofline as R
from repro.api import plan_from_partitioned
from repro.core import distributed as D
from repro.core.partition import PartitionedMatrix
from repro.launch.mesh import make_production_mesh


def synth_partition_1d(rows, cols, nnz_per_row, parts, seed=0):
    """Build a pre-partitioned scale-free COO directly in partitioned form
    (paper-scale matrices never materialize densely)."""
    rng = np.random.default_rng(seed)
    per_part_rows = rows // parts
    nnz_pp = per_part_rows * nnz_per_row
    # Zipf columns (hub structure), already row-sorted within parts
    ranks = np.arange(1, cols + 1, dtype=np.float64)
    p = ranks ** -1.2
    p /= p.sum()
    colind = rng.choice(cols, size=(parts, nnz_pp), p=p).astype(np.int32)
    rowind = np.repeat(
        np.arange(per_part_rows, dtype=np.int32), nnz_per_row
    )[None].repeat(parts, 0)
    values = rng.standard_normal((parts, nnz_pp)).astype(np.float32)
    return PartitionedMatrix(
        rowind=jnp.asarray(rowind),
        colind=jnp.asarray(colind),
        values=jnp.asarray(values),
        nnz=jnp.full((parts,), nnz_pp, jnp.int32),
        row_start=jnp.arange(parts, dtype=jnp.int32) * per_part_rows,
        col_start=jnp.zeros((parts,), jnp.int32),
        row_extent=jnp.full((parts,), per_part_rows, jnp.int32),
        col_extent=jnp.full((parts,), cols, jnp.int32),
        shape=(rows, cols),
        grid=(parts, 1),
        fmt="coo",
        scheme="1d.nnz",
        block=(1, 1),
        h_pad=per_part_rows,
        w_pad=cols,
    )


def lower_1d(mat, mesh, ring=False):
    if ring:
        # ring plan offsets are host-side preprocessing in production; for
        # the dry-run every bucket is equal-sized by construction
        counts = np.full((mat.n_parts, mat.n_parts),
                         int(mat.nnz[0]) // mat.n_parts, np.int32)
        plan = plan_from_partitioned(mat, mesh, ring=True, ring_counts=counts)
    else:
        plan = plan_from_partitioned(mat, mesh)
    fn = plan.program(mat)  # shard_map call object; lowered against avals
    arrs = {**D._arrays(mat), **(plan.placed_extra(mat) or {})}
    arrs_aval = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), arrs
    )
    x_aval = jax.ShapeDtypeStruct((mat.shape[1],), jnp.float32)
    with jax.set_mesh(mesh):
        lowered = fn.jitted.lower(arrs_aval, x_aval)
    return lowered, lowered.compile()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--nnz-per-row", type=int, default=16)
    ap.add_argument("--out", default="experiments/dryrun_spmv.json")
    args = ap.parse_args(argv)
    from repro.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    recs = []
    for multi_pod in (False, True):
        mesh = make_production_mesh(multi_pod=multi_pod)
        # the partition axis is the full mesh: every chip is a PIM core
        devs = mesh.devices.size
        flat = compat.make_mesh((devs,), ("data",))
        mat = synth_partition_1d(args.rows, args.rows, args.nnz_per_row, devs)
        for ring in (False, True):
            pod = "multipod512" if multi_pod else "pod256"
            label = f"spmv.1d{'.ring' if ring else ''}.{pod}"
            lowered, compiled = lower_1d(mat, flat, ring=ring)
            mem = compiled.memory_analysis()
            ca = compiled.cost_analysis()
            coll = R.collective_bytes(compiled.as_text())
            rec = {
                "name": label,
                "chips": devs,
                "temp_bytes": mem.temp_size_in_bytes,
                "flops": float(ca.get("flops", 0.0)),
                "bytes": float(ca.get("bytes accessed", 0.0)),
                "collectives": coll,
            }
            recs.append(rec)
            print(f"[ok] {label}: coll(B/dev)={coll['total']:,} "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
                  f"flops={rec['flops']:.3g}")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(recs, f, indent=1)


if __name__ == "__main__":
    main()
