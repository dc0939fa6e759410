#!/usr/bin/env python
"""Run the SpMV main path on a TPU and check every result against float64.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # 1D/2D across four chips only

The default run drives the path a user calls, at 32768 x 32768 with 64
nonzeros per row (about 2.1M nonzeros), matrices generated from ``--seed``:

  * ``SparseMatrix`` -> ``plan`` -> ``compile`` -> ``Executor``: csr and coo
    on a regular matrix and bcsr and bcoo on a block matrix of the same
    size with ``impl="xla"``, then bcsr and bcoo with ``impl="pallas"``
    compiled for the chip (the lowered program must hold
    ``tpu_custom_call``); each runs SpMV and SpMM with B = 8;
  * ``SpmvEngine``: ``register``, then eight ``MicroBatcher`` submits that
    coalesce into one SpMM, for an XLA plan and a Pallas plan;
  * ``AsyncSpmvService``: 16 concurrent requests;
  * one CG ``solve`` on a symmetric, diagonally dominant matrix;
  * ``ClusterRouter`` refusing a worker while this process holds the chip.

``--four-chips`` runs only the partitioned path across four chips: 1D and
2D plans with ``impl="xla"`` and with bcoo ``impl="pallas"``, each compared
with the one-chip result and the float64 reference, its shards checked to
sit on four distinct devices.

Each phase prints one JSON line: wall time including compilation, the
largest normwise relative error ``max|y - y64| / max|y64|`` against SciPy
in float64, the device's ``peak_bytes_in_use`` and the host's peak RSS.
The last line of standard output is ``{"ok": true, "device": {...}}``.
The script exits non-zero, with no such line, when any phase fails, when
JAX finds no TPU, or when the repository's ``src/`` is not next to it.
"""
import argparse
import asyncio
import json
import resource
import sys
import time
import traceback
from pathlib import Path

import numpy as np

N = 32768  # rows and columns of every matrix
NNZ_PER_ROW = 64
BLOCK = (8, 16)
BATCH = 8  # SpMM width, and the MicroBatcher's coalescing width
REQUESTS = 16  # served through AsyncSpmvService
# float32 sums of 64 products stay far inside this; a single bf16 pass
# (the TPU's default f32 matmul precision) lands near 1e-3
RTOL = 1e-5
CG_TOL = 1e-5  # CG stops at ||r|| <= CG_TOL * ||b|| (float32 recurrence)
CG_CHECK = 1e-4  # the float64 residual ||b - A x|| / ||b|| must be below


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def rel_err(got, want) -> float:
    """Normwise relative error max|got - want| / max|want|, in float64."""
    got = np.asarray(got, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


class Phases:
    """Runs named phases, prints one JSON line each, remembers failures."""

    def __init__(self, device):
        self.device = device
        self.failed = []

    def run(self, name: str, fn, tol: float = RTOL) -> None:
        t0 = time.perf_counter()
        line = {"phase": name}
        try:
            err = fn()
            line["max_rel_err"] = err
            line["tol"] = tol
            if not err <= tol:
                raise AssertionError(f"error {err:.3e} above {tol:.0e}")
        except Exception as e:  # report the phase, run the others
            traceback.print_exc()
            line["error"] = f"{type(e).__name__}: {e}"
            self.failed.append(name)
        line["wall_s"] = time.perf_counter() - t0
        stats = self.device.memory_stats() or {}
        line["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        # ru_maxrss is in KiB on Linux: the host side of set-up
        line["host_max_rss_bytes"] = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024)
        print(json.dumps(line), flush=True)


def regular(n: int, seed: int):
    """The regular matrix as (float32 dense, float64 SciPy CSR)."""
    import scipy.sparse as sp

    from repro.data import regular_matrix

    a = regular_matrix(n, n, NNZ_PER_ROW, seed=seed)
    return a, sp.csr_matrix(a).astype(np.float64)


def blocked(n: int, seed: int):
    """A block matrix of the same size and about the same nonzeros."""
    import scipy.sparse as sp

    from repro.data import block_matrix

    density = NNZ_PER_ROW / BLOCK[1] / (n // BLOCK[1])  # blocks per block-row
    a = block_matrix(n, n, block=BLOCK, block_density=density, seed=seed)
    return a, sp.csr_matrix(a).astype(np.float64)


def spd_from(ref64):
    """Symmetric, strictly diagonally dominant: (A + A^T) / 2 + diag."""
    import scipy.sparse as sp

    s = (ref64 + ref64.T) * 0.5
    s = s + sp.diags(np.asarray(abs(s).sum(axis=1)).ravel() + 1.0)
    s32 = s.astype(np.float32)
    return s32.toarray(), s32.astype(np.float64).tocsr()


def check_executor(exe, ref64, x, X) -> float:
    return max(rel_err(exe(x), ref64 @ x.astype(np.float64)),
               rel_err(exe.batch(X), ref64 @ X.astype(np.float64)))


def one_chip(phases: Phases, n: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import SparseMatrix
    from repro.engine import MicroBatcher, SpmvEngine
    from repro.kernels import ops
    from repro.serve import AsyncSpmvService

    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    X = rng.standard_normal((n, BATCH)).astype(np.float32)

    a, a64 = regular(n, seed)
    b, b64 = blocked(n, seed + 1)
    sm_a = SparseMatrix.from_dense(a, stats_block=BLOCK)
    sm_b = SparseMatrix.from_dense(b, stats_block=BLOCK)
    print(json.dumps({"matrices": {"regular_nnz": int(a64.nnz),
                                   "block_nnz": int(b64.nnz), "n": n}}),
          flush=True)

    cases = [("csr", "xla", sm_a, a64), ("coo", "xla", sm_a, a64),
             ("bcsr", "xla", sm_b, b64), ("bcoo", "xla", sm_b, b64),
             ("bcsr", "pallas", sm_b, b64), ("bcoo", "pallas", sm_b, b64)]
    for fmt, impl, sm, ref64 in cases:
        def api(fmt=fmt, impl=impl, sm=sm, ref64=ref64):
            exe = sm.plan(fmt=fmt, impl=impl, block=BLOCK).compile()
            if impl == "pallas":
                program = ops.pallas_program(exe.container)
                text = jax.jit(program).lower(jnp.asarray(x)).as_text()
                if "tpu_custom_call" not in text:
                    raise AssertionError("no tpu_custom_call: the Pallas "
                                         "kernel did not compile for the chip")
            return check_executor(exe, ref64, x, X)

        phases.run(f"api.{fmt}.{impl}", api)
    del sm_b

    engine = SpmvEngine(block=BLOCK)

    def batched(name: str, a_dense, ref64, impl: str) -> float:
        engine.register(name, a_dense, impl=impl)
        before = len(engine.telemetry.records)
        batcher = MicroBatcher(engine, max_batch=BATCH)
        futures = [batcher.submit(name, X[:, j]) for j in range(BATCH)]
        got = np.stack([f.result(timeout=600) for f in futures], axis=1)
        recs = engine.telemetry.records[before:]
        if [r.batch for r in recs] != [BATCH]:
            raise AssertionError(f"expected one SpMM of width {BATCH}; "
                                 f"ran batches {[r.batch for r in recs]}")
        return rel_err(got, ref64 @ X.astype(np.float64))

    phases.run("engine.multiply.xla", lambda: batched("A", a, a64, "xla"))
    phases.run("engine.multiply.pallas",
               lambda: batched("B", b, b64, "pallas"))
    del b, b64

    def served() -> float:
        service = AsyncSpmvService(engine)
        xs = rng.standard_normal((REQUESTS, n)).astype(np.float32)

        async def fire():
            async with service:
                return await asyncio.gather(*(
                    service.multiply(f"tenant{i % 2}", "A", xs[i])
                    for i in range(REQUESTS)))

        got = np.stack(asyncio.run(fire()))
        if service.served != REQUESTS:
            raise AssertionError(f"served {service.served} of {REQUESTS}")
        return rel_err(got, (a64 @ xs.T.astype(np.float64)).T)

    phases.run("serve.multiply", served)

    def solve() -> float:
        s, s64 = spd_from(a64)
        engine.register("S", s)
        rhs = rng.standard_normal(n).astype(np.float32)
        scale = float(np.linalg.norm(rhs))
        res = engine.solve("S", np.zeros(n, np.float32), combine="cg", b=rhs,
                           tol=CG_TOL * scale, max_steps=500)
        if not res.converged:
            raise AssertionError(f"CG did not converge in {res.steps} steps "
                                 f"(residual {res.residual:.3e})")
        true = s64 @ res.x.astype(np.float64) - rhs.astype(np.float64)
        print(json.dumps({"cg": {"steps": res.steps,
                                 "seconds": res.seconds}}), flush=True)
        return float(np.linalg.norm(true) / scale)

    phases.run("engine.solve.cg", solve, tol=CG_CHECK)

    def refuses() -> float:
        from repro.cluster import ClusterRouter

        try:
            ClusterRouter(workers=1).close()
        except ValueError as e:
            print(json.dumps({"cluster_refusal": str(e)}), flush=True)
            return 0.0
        raise AssertionError("a worker was started beside the process "
                             "that holds the chip")

    phases.run("cluster.refuses_held_chip", refuses)


def four_chips(phases: Phases, n: int, seed: int) -> None:
    import jax

    from repro.api import SparseMatrix
    from repro.topo import detect_topology

    devices = jax.devices()[:4]
    print(json.dumps({"topology": repr(detect_topology(devices))}),
          flush=True)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    X = rng.standard_normal((n, BATCH)).astype(np.float32)
    a, a64 = regular(n, seed)
    b, b64 = blocked(n, seed + 1)
    sm = {"regular": SparseMatrix.from_dense(a, stats_block=BLOCK),
          "block": SparseMatrix.from_dense(b, stats_block=BLOCK)}
    refs = {"regular": a64, "block": b64}
    cases = [("regular", "coo", "xla"), ("block", "bcoo", "pallas")]
    for name, fmt, impl in cases:
        single = sm[name].plan(fmt=fmt, impl=impl, block=BLOCK).compile()
        y1, Y1 = single(x), single.batch(X)
        for scheme in ("1d", "2d.equally-sized"):
            def sharded(name=name, fmt=fmt, impl=impl, scheme=scheme):
                exe = sm[name].plan(scheme=scheme, fmt=fmt, impl=impl,
                                    block=BLOCK, devices=devices).compile()
                held = {d for leaf in jax.tree_util.tree_leaves(exe.arrays)
                        for d in leaf.sharding.device_set}
                if len(held) != 4:
                    raise AssertionError(f"shards on {len(held)} devices")
                y, Y = exe(x), exe.batch(X)
                print(json.dumps({"plan": exe.plan.scheme_id,
                                  "grid": list(exe.plan.grid),
                                  "vs_one_chip": max(rel_err(y, y1),
                                                     rel_err(Y, Y1))}),
                      flush=True)
                return max(rel_err(y, y1), rel_err(Y, Y1),
                           check_executor(exe, refs[name], x, X))

            phases.run(f"four_chips.{scheme.split('.')[0]}.{fmt}.{impl}",
                       sharded)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1D/2D partitioned path on 4 chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(json.dumps({"platform": dev.platform, "device_kind":
                      dev.device_kind, "count": len(devices)}), flush=True)
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r}); this check "
             "runs on the chip and never falls back")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"--four-chips needs 4 TPU chips; JAX found {len(devices)}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.runtime.cache import enable_compilation_cache
    except ImportError as e:
        fail(f"the repository's src/ is not next to this script ({e})")
    print(json.dumps({"compilation_cache": enable_compilation_cache()}),
          flush=True)

    phases = Phases(dev)
    if args.four_chips:
        four_chips(phases, N, args.seed)
    else:
        one_chip(phases, N, args.seed)
    if phases.failed:
        fail(f"phases failed: {phases.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
