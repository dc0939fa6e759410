import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: prove the distribution config is coherent.

For each (architecture x input shape x mesh) cell:

    with jax.set_mesh(mesh):
        lowered  = jax.jit(step, in_shardings=..., donate...).lower(*input_specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits per device
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

Meshes: single-pod (16, 16) = 256 chips and multi-pod (2, 16, 16) = 512
(launch/mesh.py).  Shape cells follow ArchConfig.skip_shapes (DESIGN.md §4).

Modes:
  full   (default)  lower+compile the production (scanned) step — the
                    required dry-run artifact; records memory + cost + HLO
                    collective bytes of the compiled module.
  probe  (--probe)  additionally lower unrolled L=1/L=2 probes and emit
                    scan-corrected roofline terms (analysis/roofline.py).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k [--multipod] [--probe] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.analysis import roofline as R
from repro.configs import get_config, list_configs
from repro.configs.base import SHAPES
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim import AdamWConfig
from repro.runtime import make_shardings


#: The chip the pod meshes model (its peaks come from analysis/roofline.py).
TARGET_KIND = "TPU v5 lite"


def _mesh(multi_pod: bool):
    return make_production_mesh(multi_pod=multi_pod)


def _opt_cfg(cfg):
    # 8-bit Adam moments for the largest model: 671B params leave no room for
    # f32 m/v on the 256-chip pod (4 TB HBM total; DESIGN.md §5).
    big = cfg.name == "deepseek-v3-671b"
    return AdamWConfig(quantized_v=big, quantized_m=big)


def _microbatches(cfg, shape_name: str) -> int:
    # Bound the MoE dispatch working set (tokens*top_k slots) per device.
    if cfg.name == "deepseek-v3-671b" and shape_name == "train_4k":
        return 8
    if cfg.name == "mixtral-8x22b" and shape_name == "train_4k":
        return 2
    return 1


def lower_cell(cfg, shape_name: str, mesh, donate=True, microbatches=None):
    """Lower + compile one cell. Returns (lowered, compiled)."""
    from repro.runtime.elastic import sanitize_shardings

    kind = SHAPES[shape_name]["kind"]
    ins = S.input_specs(cfg, shape_name)
    in_sh = sanitize_shardings(
        make_shardings(mesh, S.input_spec_shardings(cfg, shape_name)), ins
    )
    if microbatches is None:
        microbatches = _microbatches(cfg, shape_name)
    with jax.set_mesh(mesh):
        if kind == "train":
            opt_cfg = _opt_cfg(cfg)
            params, opt = S.abstract_state(cfg, opt_cfg)
            psp, osp = S.state_specs(cfg, opt_cfg)
            p_sh = sanitize_shardings(make_shardings(mesh, psp), params)
            o_sh = sanitize_shardings(make_shardings(mesh, osp), opt)
            fn = S.make_train_step(cfg, opt_cfg, microbatches=microbatches)
            jitted = jax.jit(
                fn,
                in_shardings=(p_sh, o_sh, in_sh),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = jitted.lower(params, opt, ins)
        elif kind == "prefill":
            params = lm.abstract_params(cfg)
            p_sh = sanitize_shardings(
                make_shardings(mesh, lm.param_specs(cfg)), params
            )
            sh = SHAPES[shape_name]
            caches_aval = jax.eval_shape(
                lambda: lm.init_caches(cfg, sh["global_batch"], sh["seq_len"])
            )
            c_sh = sanitize_shardings(
                make_shardings(mesh, S.cache_specs(cfg)), caches_aval
            )
            fn = S.make_prefill_step(cfg, shape_name)
            jitted = jax.jit(
                fn, in_shardings=(p_sh, in_sh),
                out_shardings=(None, c_sh),
            )
            lowered = jitted.lower(params, ins)
        else:  # decode
            params = lm.abstract_params(cfg)
            p_sh = sanitize_shardings(
                make_shardings(mesh, lm.param_specs(cfg)), params
            )
            fn = S.make_decode_step(cfg, shape_name)
            jitted = jax.jit(
                fn, in_shardings=(p_sh, in_sh),
                out_shardings=(None, in_sh["caches"]),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(params, ins)
    compiled = lowered.compile()
    return lowered, compiled


def probe_cell(cfg, shape_name: str, mesh):
    """Unrolled L=1 / L=2 probes -> scan-corrected CostTerms."""
    pre = len(cfg.prefix_pattern)
    pat = len(cfg.block_pattern)
    results = []
    for nr in (1, 2):
        probe_cfg = dataclasses.replace(
            cfg, n_layers=pre + pat * nr, unroll_layers=True
        )
        # probes run un-microbatched: same total tokens => same total costs,
        # and the accumulation scan would otherwise hide per-layer work
        _, compiled = lower_cell(probe_cfg, shape_name, mesh, donate=False,
                                 microbatches=1)
        results.append(R.CostTerms.from_compiled(compiled))
    total = R.extrapolate(results[0], results[1], cfg.n_repeats)
    chips = mesh.devices.size
    for name, corr in (
        ("slstm", R.slstm_scan_correction(cfg, shape_name)),
        ("gla", R.gla_scan_correction(cfg, shape_name)),
    ):
        if corr:
            total = total.plus(R.CostTerms(
                flops=corr / chips,
                notes=[f"{name} analytic +{corr:.3e} flops"]))
    return total


def run_cell(arch: str, shape_name: str, multi_pod: bool, probe: bool,
             out_dir: str | None):
    cfg = get_config(arch)
    if shape_name in cfg.skip_shapes:
        print(f"[skip] {arch} x {shape_name}: inapplicable (DESIGN.md §4)")
        return {"arch": arch, "shape": shape_name, "skipped": True}
    mesh = _mesh(multi_pod)
    chips = mesh.devices.size
    label = f"{arch} x {shape_name} x {'multipod512' if multi_pod else 'pod256'}"
    t0 = time.monotonic()
    lowered, compiled = lower_cell(cfg, shape_name, mesh)
    dt = time.monotonic() - t0
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    coll = R.collective_bytes(compiled.as_text())
    print(f"[ok] {label} compiled in {dt:.1f}s")
    print(f"     memory_analysis: {mem}")
    print(f"     cost_analysis: flops={ca.get('flops', 0):.4g} "
          f"bytes={ca.get('bytes accessed', 0):.4g}")
    print(f"     collectives(bytes/device): { {k: v for k, v in coll.items() if v} }")
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multipod512" if multi_pod else "pod256",
        "chips": chips,
        "compile_s": dt,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost_raw": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
    }
    if probe:
        total = probe_cell(cfg, shape_name, mesh)
        rec["roofline"] = R.roofline_report(cfg, shape_name, chips, total,
                                            TARGET_KIND)
        rl = rec["roofline"]
        print(f"     roofline: compute={rl['compute_s']:.3e}s "
              f"memory={rl['memory_s']:.3e}s coll={rl['collective_s']:.3e}s "
              f"dominant={rl['dominant']} frac={rl['roofline_fraction']:.2%}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = f"{arch}_{shape_name}_{rec['mesh']}.json"
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose JSON (incl. probe, if requested) exists")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)
    from repro.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    def _have(arch, shape_name, mp):
        fn = os.path.join(
            args.out,
            f"{arch}_{shape_name}_{'multipod512' if mp else 'pod256'}.json")
        if not os.path.exists(fn):
            return False
        if args.probe and not mp:
            with open(fn) as f:
                return "roofline" in json.load(f)
        return True

    if args.all:
        failures = []
        for arch in list_configs():
            cfg = get_config(arch)
            for shape_name in cfg.shapes():
                for mp in (False, True):
                    if args.skip_existing and _have(arch, shape_name, mp):
                        continue
                    try:
                        run_cell(arch, shape_name, mp, args.probe and not mp,
                                 args.out)
                    except Exception as e:  # noqa: BLE001
                        failures.append((arch, shape_name, mp, repr(e)))
                        traceback.print_exc()
        if failures:
            print(f"FAILURES ({len(failures)}):")
            for f in failures:
                print("  ", f)
            raise SystemExit(1)
        print("ALL CELLS GREEN")
        return
    run_cell(args.arch, args.shape, args.multipod, args.probe, args.out)


if __name__ == "__main__":
    main()
