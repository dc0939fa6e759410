"""Subprocess body for distributed tests (needs 8 fake devices, so it must
own the process: XLA_FLAGS is set before jax imports).  Invoked by
tests/test_distributed.py; prints sentinel lines the test asserts on."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp

from repro import compat
from jax.sharding import PartitionSpec as P
from repro.core.partition import partition_1d, partition_2d
from repro.core import distributed as D


def main():
    print(f"DEVICES {jax.device_count()}")
    if jax.device_count() < 8:
        # the forced fake-device count did not take (e.g. non-CPU backend):
        # signal the caller to skip rather than report scheme failures
        print("DISTRIBUTED SKIP")
        return
    rng = np.random.default_rng(0)
    a = ((rng.random((192, 256)) < 0.05)
         * rng.standard_normal((192, 256))).astype(np.float32)
    a[11] = rng.standard_normal(256)  # dense row (scale-free-ish)
    x = rng.standard_normal(256).astype(np.float32)
    want = a @ x

    mesh1 = compat.make_mesh((8,), ("data",))
    for fmt, balance in [("coo", "rows"), ("coo", "nnz-rgrn"), ("coo", "nnz"),
                         ("bcoo", "nnz")]:
        kw = dict(block=(4, 8)) if fmt == "bcoo" else {}
        part = partition_1d(a, 8, fmt=fmt, balance=balance, **kw)
        extra = D.slice_arrays(part).arrays if fmt == "coo" else None
        arrs = D.place_1d(part, mesh1, "data", extra=extra)
        xs = jax.device_put(jnp.asarray(x), jax.NamedSharding(mesh1, P("data")))
        out = D.spmv_1d(part, mesh1, "data")(arrs, xs)
        got = D.assemble_rows(out)
        ok = np.allclose(got, want, rtol=1e-3, atol=1e-4)
        print(f"1D {fmt}.{balance}: {'OK' if ok else 'FAIL'}")

    mesh2 = compat.make_mesh((4, 2), ("data", "model"))
    for scheme, merge in [("equally-sized", "psum"),
                          ("equally-sized", "psum_scatter"),
                          ("equally-wide", "global"),
                          ("variable-sized", "global")]:
        part = partition_2d(a, (4, 2), fmt="coo", scheme=scheme)
        arrs = D.place_2d(part, mesh2, ("data", "model"),
                          extra=D.slice_arrays(part).arrays)
        xs = jax.device_put(jnp.asarray(x), jax.NamedSharding(mesh2, P("model")))
        out = D.spmv_2d(part, mesh2, ("data", "model"), merge=merge)(arrs, xs)
        got = D.assemble_rows(out)
        ok = np.allclose(got, want, rtol=1e-3, atol=1e-4)
        print(f"2D {scheme}.{merge}: {'OK' if ok else 'FAIL'}")

    # ring-pipelined 1D (beyond-paper overlap schedule)
    part = partition_1d(a, 8, fmt="coo", balance="nnz")
    part_r, counts = D.bucket_by_source_shard(part, 8)
    arrs = D.place_1d(part_r, mesh1, "data")
    xs = jax.device_put(jnp.asarray(x), jax.NamedSharding(mesh1, P("data")))
    out = D.spmv_1d_ring(part_r, counts, mesh1, "data")(arrs, xs)
    ok = np.allclose(D.assemble_rows(out), want, rtol=1e-3, atol=1e-4)
    print(f"1D ring: {'OK' if ok else 'FAIL'}")

    # SpMM through the distributed path (batch of vectors)
    X = rng.standard_normal((256, 4)).astype(np.float32)
    part = partition_1d(a, 8, fmt="coo", balance="nnz")
    arrs = D.place_1d(part, mesh1, "data", extra=D.slice_arrays(part).arrays)
    xs = jax.device_put(jnp.asarray(X), jax.NamedSharding(mesh1, P("data", None)))
    out = D.spmv_1d(part, mesh1, "data")(arrs, xs)
    ok = np.allclose(D.assemble_rows(out), a @ X, rtol=1e-3, atol=1e-4)
    print(f"1D spmm: {'OK' if ok else 'FAIL'}")

    print("DISTRIBUTED DONE")


if __name__ == "__main__":
    main()
