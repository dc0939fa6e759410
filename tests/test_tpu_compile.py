"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than present, so these tests see what the interpret-mode
suite cannot: a kernel block Mosaic refuses, or a program that does not
lower for the chip.  Nothing runs, so they say nothing about results.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and under several pytest
workers only the worker that runs this file may do so.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.api import SparseMatrix
from repro.core import distributed as D
from repro.data.matrices import block_matrix
from repro.kernels import ref
from repro.kernels.bcsr_spmv import bcoo_spmv_pallas

COLS = 32768  # the width chip_smoke.py runs at
NNZ_PER_ROW = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def block_sm():
    return SparseMatrix.from_dense(
        block_matrix(512, 1024, block=(8, 16), block_density=0.05, seed=5))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batch", [1, 8, 128])
def test_block_kernel_compiles(one_chip, batch):
    nb = 4096  # (8, 16) blocks
    x_shape = (COLS,) if batch == 1 else (COLS, batch)

    def run(browind, bcolind, bvalues, x):
        return bcoo_spmv_pallas(browind, bcolind, bvalues, x, COLS, nb,
                                interpret=False)

    compiled = jax.jit(run).lower(
        _spec((nb,), jnp.int32, one_chip),
        _spec((nb,), jnp.int32, one_chip),
        _spec((nb, 8, 16), jnp.float32, one_chip),
        _spec(x_shape, jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_single_device_csr_xla_compiles(one_chip):
    rows, nnz = COLS, COLS * NNZ_PER_ROW

    def run(rowptr, colind, values, x):
        return ref.csr_spmv_ref(rowptr, colind, values, x, rows)

    compiled = jax.jit(run).lower(
        _spec((rows + 1,), jnp.int32, one_chip),
        _spec((nnz,), jnp.int32, one_chip),
        _spec((nnz,), jnp.float32, one_chip),
        _spec((COLS,), jnp.float32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= nnz * 8  # colind + values


@pytest.mark.parametrize("fmt,impl", [("bcoo", "xla"), ("bcoo", "pallas"),
                                      ("bcsr", "pallas")])
def test_2d_program_compiles_on_the_mesh(topo, block_sm, fmt, impl):
    from repro.topo import FakeTopology

    placement = FakeTopology.pim_like((2, 2), devices=topo.devices)
    plan = block_sm.plan(scheme="2d.equally-sized", fmt=fmt, impl=impl,
                         topology=placement)
    assert plan.grid == (2, 2) and plan.fmt == fmt
    part = plan.partition()
    arrs = D._arrays(part)
    R, C = part.grid
    tiles = NamedSharding(plan.mesh, P(*plan.axes))
    avals = {k: _spec((R, C) + np.shape(v)[1:], np.asarray(v).dtype, tiles)
             for k, v in arrs.items()}
    x = _spec((plan._x_pad(part),), jnp.float32,
              NamedSharding(plan.mesh, plan._x_spec()))
    compiled = jax.jit(plan.program(part).jitted).lower(avals, x).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (impl == "pallas")
    assert {d.platform for d in plan.mesh.devices.flat} == {"tpu"}


@pytest.mark.parametrize("fmt", ["coo", "csr"])
def test_scalar_pallas_is_refused_at_plan_time(topo, block_sm, fmt):
    with pytest.raises(ValueError, match=r"\(1, 512\) chunk blocks"):
        block_sm.plan(scheme="2d.equally-sized", fmt=fmt, impl="pallas",
                      devices=topo.devices)


def test_auto_and_tune_offer_only_block_formats(topo, block_sm):
    from repro.tune import CandidateGenerator

    auto = block_sm.plan(impl="pallas", devices=topo.devices)
    assert auto.fmt in ("bcoo", "bcsr")
    cands = CandidateGenerator(impls=("pallas",)).plans(
        block_sm, devices=topo.devices)
    assert cands and {p.fmt for p in cands} <= {"bcoo", "bcsr"}


@pytest.mark.parametrize("scheme,width,slices,nnz", [
    ("1d.nnz", 8, 123356, 881178),  # graph500-kron-s15: merged slices
    ("2d.equally-sized", 27, COLS, 830584),  # hpcg-27pt-32: one a row
])
def test_xla_scalar_tile_kernel_compiles(topo, scheme, width, slices, nnz):
    """The row-slice tile program at the benchmark cells' sizes compiles for
    one v5e chip with every kernel op under ``spmv.coo.xla``; one slice a
    row leaves no scatter in the program."""
    from jax.sharding import Mesh

    from repro.core.partition import PartitionedMatrix

    one_slice = slices == COLS
    one = jnp.zeros((1,), jnp.int32)
    ext = jnp.full((1,), COLS, jnp.int32)
    part = PartitionedMatrix(
        rowind=one[None], colind=one[None], values=one[None].astype(float),
        nnz=one, row_start=one, col_start=one, row_extent=ext,
        col_extent=ext, shape=(COLS, COLS), grid=(1, 1), fmt="coo",
        scheme=scheme, block=(1, 1), h_pad=COLS, w_pad=COLS)
    shapes = {"rowind": ((nnz,), jnp.int32), "colind": ((nnz,), jnp.int32),
              "values": ((nnz,), jnp.float32), "nnz": ((), jnp.int32),
              "row_start": ((), jnp.int32), "col_start": ((), jnp.int32),
              "slice_colind": ((width, slices), jnp.int32),
              "slice_values": ((width, slices), jnp.float32)}
    if not one_slice:
        shapes["slice_row"] = ((slices,), jnp.int32)
    if scheme.startswith("1d"):
        mesh = Mesh(np.array(topo.devices[:1]), ("data",))
        program, lead, x_spec = D.spmv_1d(part, mesh), (1,), P("data")
    else:
        mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                    ("data", "model"))
        program, lead, x_spec = D.spmv_2d(part, mesh), (1, 1), P("model")
    tiles = NamedSharding(mesh, P(*mesh.axis_names))
    avals = {k: _spec(lead + s, dt, tiles) for k, (s, dt) in shapes.items()}
    x = _spec((COLS,), jnp.float32, NamedSharding(mesh, x_spec))
    text = jax.jit(program.jitted).lower(avals, x).compile().as_text()
    assert ("scatter" in text) != one_slice
    named = re.findall(r"= \S+ (gather|scatter|reduce|fusion)\(.*?"
                       r'op_name="([^"]*)"', text)
    assert any(op == "gather" for op, _ in named)
    # a relayout of a placed input to the shard's view keeps the input's
    # name (arrs[...]), as the partition arrays' did before row slices
    outside = [n for n in named
               if "spmv.coo.xla" not in n[1] and not n[1].startswith("arrs[")]
    assert not outside, outside
