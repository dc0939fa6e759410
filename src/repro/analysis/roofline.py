"""Roofline analysis from compiled dry-run artifacts (TPU v5e target).

Per (arch x shape x mesh) cell, three terms, over the peaks of the
target chip's ``device_kind`` (:data:`PEAKS`):

    compute_s    = HLO_FLOPs_per_chip    / peak bf16 FLOP/s
    memory_s     = HLO_bytes_per_chip    / HBM bytes/s
    collective_s = coll_bytes_per_chip   / ICI bytes/s per link

Sources and the scan-undercount correction:
  * XLA counts a `while` (scan) body ONCE in cost_analysis.  We therefore
    lower two *unrolled probes* — the same arch at n_repeats=1 and 2 with
    cfg.unroll_layers=True (which also unrolls the chunked-attention maps and
    the GLA chunk recurrence) — and extrapolate:
        per_layer = cost(L2) - cost(L1);  total = cost(L1) + (NR-1)*per_layer
    This captures remat recompute exactly (the probes remat like production).
  * collective bytes are not in cost_analysis: we parse the compiled HLO and
    sum output-shape bytes of all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute ops, with the same L1/L2 extrapolation.
  * sLSTM's per-token recurrence stays a lax.scan even in probes (4096 steps
    cannot unroll); its recurrent FLOPs are added analytically
    (S * B * H * dh * 4dh * 2 per sLSTM layer) — noted per cell.

MODEL_FLOPS (usefulness denominator) = 6*N*D for training (2*N*D forward),
N = active params; attention/SSM terms added analytically.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from repro.configs.base import SHAPES, ArchConfig

__all__ = [
    "PEAKS",
    "peaks",
    "collective_bytes",
    "CostTerms",
    "roofline_report",
    "model_flops",
]

#: Published per-chip peaks by ``jax.Device.device_kind``.  TPU v5e (Google
#: Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, and
#: 1,600 Gbit/s of ICI over four links, i.e. 50 GB/s per link.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peak ``flops`` / ``hbm_bw`` / ``link_bw`` of one chip of that kind.

    Raises:
      KeyError: for a device kind without published peaks in :data:`PEAKS`.
    """
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

# matches e.g.  "%all-reduce.5 = f32[128,1024]{1,0} all-reduce("
#          or   "... = (f32[8,4]{...}, f32[8]{...}) all-gather("
_OP_RE = re.compile(
    r"=\s*(\(?[a-z0-9\[\],{}\s]*\)?)\s*(" + "|".join(_COLL_OPS) + r")[\.\(]"
)
_SHAPE_RE = re.compile(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes per collective kind from compiled (post-SPMD) HLO."""
    out = {k: 0 for k in _COLL_OPS}
    for m in _OP_RE.finditer(hlo_text):
        out[m.group(2)] += _shape_bytes(m.group(1))
    out["total"] = sum(out[k] for k in _COLL_OPS)
    return out


@dataclass
class CostTerms:
    flops: float = 0.0  # per device
    bytes_hbm: float = 0.0  # per device
    coll_bytes: float = 0.0  # per device
    notes: list = field(default_factory=list)

    @classmethod
    def from_compiled(cls, compiled) -> "CostTerms":
        ca = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
        return cls(
            flops=float(ca.get("flops", 0.0)),
            bytes_hbm=float(ca.get("bytes accessed", 0.0)),
            coll_bytes=float(coll["total"]),
        )

    def scaled(self, k: float) -> "CostTerms":
        return CostTerms(self.flops * k, self.bytes_hbm * k, self.coll_bytes * k)

    def plus(self, other: "CostTerms") -> "CostTerms":
        return CostTerms(
            self.flops + other.flops,
            self.bytes_hbm + other.bytes_hbm,
            self.coll_bytes + other.coll_bytes,
            self.notes + other.notes,
        )


def extrapolate(l1: CostTerms, l2: CostTerms, n_repeats: int) -> CostTerms:
    """total = outside + NR * per_layer, from unrolled L=1 / L=2 probes."""
    per_layer = CostTerms(
        max(l2.flops - l1.flops, 0.0),
        max(l2.bytes_hbm - l1.bytes_hbm, 0.0),
        max(l2.coll_bytes - l1.coll_bytes, 0.0),
    )
    outside = CostTerms(
        max(l1.flops - per_layer.flops, 0.0),
        max(l1.bytes_hbm - per_layer.bytes_hbm, 0.0),
        max(l1.coll_bytes - per_layer.coll_bytes, 0.0),
    )
    return outside.plus(per_layer.scaled(n_repeats))


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS
# ---------------------------------------------------------------------------


def _attn_flops_per_layer(cfg: ArchConfig, S: int, B: int, kind: str,
                          window: int | None) -> float:
    """Score+AV matmul flops (fwd), causal halving, optional window."""
    eff = S if window is None else min(S, window)
    per_q = eff / 2 if window is None else eff  # causal triangle vs band
    return 4.0 * B * S * per_q * cfg.n_heads * cfg.head_dim


def _layer_counts(cfg: ArchConfig) -> dict:
    counts: dict = {}
    for k in cfg.prefix_pattern:
        counts[k] = counts.get(k, 0) + 1
    for k in cfg.block_pattern:
        counts[k] = counts.get(k, 0) + cfg.n_repeats
    return counts


def model_flops(cfg: ArchConfig, shape_name: str) -> float:
    """Analytic useful FLOPs for the cell (6ND train / 2ND forward + attn)."""
    sh = SHAPES[shape_name]
    S, B, kind = sh["seq_len"], sh["global_batch"], sh["kind"]
    n_active = cfg.active_params()
    if kind == "train":
        tokens = S * B
        total = 6.0 * n_active * tokens
        mult = 3.0  # fwd + bwd
    elif kind == "prefill":
        tokens = S * B
        total = 2.0 * n_active * tokens
        mult = 1.0
    else:  # decode: one token per sequence
        tokens = B
        total = 2.0 * n_active * tokens
        mult = 1.0

    counts = _layer_counts(cfg)
    for k, n in counts.items():
        if k in ("attn", "attn_global", "moe", "shared_attn", "cross_attn",
                 "mla_dense", "mla_moe"):
            w = None
        elif k == "attn_local":
            w = cfg.sliding_window
        else:
            continue
        if k in ("attn", "moe", "cross_attn") and cfg.sliding_window:
            w = cfg.sliding_window
        if kind == "decode":
            eff = S if w is None else min(S, w)
            total += mult * n * 4.0 * B * eff * cfg.n_heads * cfg.head_dim
        else:
            total += mult * n * _attn_flops_per_layer(cfg, S, B, kind, w)
    # GLA/SSD chunked linear attention: ~ 2 * (C + 2*dk) per (token, head, dv)
    for k, n in counts.items():
        if k in ("mamba", "mlstm"):
            H = cfg.ssm_heads if k == "mamba" else cfg.n_heads
            dk = cfg.ssm_state if k == "mamba" else cfg.d_model // cfg.n_heads
            dv = (cfg.ssm_d_inner // cfg.ssm_heads) if k == "mamba" else (
                cfg.d_model // cfg.n_heads
            )
            C = 256
            if kind == "decode":
                total += mult * n * 2.0 * B * H * dk * dv * 2
            else:
                total += mult * n * 2.0 * B * S * H * dv * (C + 2 * dk)
        if k == "slstm":
            dh = cfg.d_model // cfg.n_heads
            steps = 1 if kind == "decode" else S
            total += mult * n * 2.0 * B * steps * cfg.n_heads * dh * 4 * dh
    return total


def slstm_scan_correction(cfg: ArchConfig, shape_name: str) -> float:
    """FLOPs the probes miss because the sLSTM time scan cannot unroll."""
    counts = _layer_counts(cfg)
    n = counts.get("slstm", 0)
    if not n:
        return 0.0
    sh = SHAPES[shape_name]
    if sh["kind"] == "decode":
        return 0.0
    S, B = sh["seq_len"], sh["global_batch"]
    dh = cfg.d_model // cfg.n_heads
    mult = 3.0 if sh["kind"] == "train" else 1.0
    return mult * n * (S - 1) * 2.0 * B * cfg.n_heads * dh * 4 * dh


GLA_CHUNK = 256  # matches models/linear_attn.py default


def gla_scan_correction(cfg: ArchConfig, shape_name: str) -> float:
    """FLOPs the probes miss in the GLA inter-chunk recurrence (mamba/mlstm).

    The recurrence stays a lax.scan even in probe mode (unrolling NC chunks
    made XLA compile times pathological), so cost_analysis counts its body
    once; the remaining (NC-1) iterations are added analytically:
      body ~ 2*B*H*dk*(C*dv + C + 3*dv)   (inter + normalizer + state update)
    """
    counts = _layer_counts(cfg)
    sh = SHAPES[shape_name]
    if sh["kind"] == "decode":
        return 0.0
    S, B = sh["seq_len"], sh["global_batch"]
    C = min(GLA_CHUNK, S)
    NC = max(1, S // C)
    mult = 3.0 if sh["kind"] == "train" else 1.0
    total = 0.0
    for kind, n in counts.items():
        if kind == "mamba":
            H, dk = cfg.ssm_heads, cfg.ssm_state
            dv = cfg.ssm_d_inner // max(cfg.ssm_heads, 1)
        elif kind == "mlstm":
            H = cfg.n_heads
            dk = dv = cfg.d_model // cfg.n_heads
        else:
            continue
        body = 2.0 * B * H * dk * (C * dv + C + 3 * dv)
        total += mult * n * (NC - 1) * body
    return total


# ---------------------------------------------------------------------------
# report assembly
# ---------------------------------------------------------------------------


def roofline_report(cfg: ArchConfig, shape_name: str, chips: int,
                    total: CostTerms, device_kind: str) -> dict:
    """Roofline terms of one cell on ``chips`` chips of ``device_kind``."""
    peak = peaks(device_kind)
    compute_s = total.flops / peak["flops"]
    memory_s = total.bytes_hbm / peak["hbm_bw"]
    coll_s = total.coll_bytes / peak["link_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape_name)
    hlo_total_flops = total.flops * chips
    bound = max(compute_s, memory_s, coll_s)
    return {
        "arch": cfg.name,
        "shape": shape_name,
        "chips": chips,
        **terms,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_total": hlo_total_flops,
        "useful_ratio": mf / hlo_total_flops if hlo_total_flops else 0.0,
        # fraction of roofline: useful work per sec achievable / peak
        "roofline_fraction": (
            (mf / chips / peak["flops"]) / bound if bound > 0 else 0.0
        ),
        "per_device": {
            "flops": total.flops,
            "bytes_hbm": total.bytes_hbm,
            "coll_bytes": total.coll_bytes,
        },
        "notes": total.notes,
    }
