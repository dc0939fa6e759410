"""Shared model components: norms, embeddings, RoPE, MLPs, sharding helpers.

Functional style throughout: ``init_*`` builds param pytrees (nested dicts of
arrays), ``*_apply`` consumes them.  Every parameter has a matching
PartitionSpec produced by the sibling ``*_spec`` helpers, so the launcher can
build in_shardings for jit without a framework dependency (MaxText-style
"specs mirror params" convention).

Sharding axes (launch/mesh.py):
  data axis   "data"   — batch / FSDP
  model axis  "model"  — tensor / expert / sequence parallel
  pod axis    "pod"    — pure data parallel across pods (multi-pod mesh only)
"""
from __future__ import annotations

import math
from typing import Any

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "shard",
    "batch_axes",
    "Param",
    "dense_init",
    "dense_spec",
    "rmsnorm_init",
    "rmsnorm",
    "embed_init",
    "rope",
    "softcap",
    "swiglu_init",
    "swiglu_apply",
    "swiglu_spec",
    "cross_entropy",
]

# Merged batch axes: filtered to the ambient mesh's axes at trace time.
_BATCH_AXES = ("pod", "data")


def batch_axes(mesh=None) -> tuple:
    """The mesh axes the batch dimension shards over."""
    names = mesh.axis_names if mesh is not None else _mesh_axis_names()
    return tuple(a for a in _BATCH_AXES if a in names)


def _mesh_axis_names():
    m = jax.sharding.get_abstract_mesh()
    return m.axis_names if m is not None and m.axis_names else ()


def shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint against the ambient mesh; no-op without one.

    Robustness rules (same spirit as runtime.elastic.sanitize_shardings):
      * axis names not in the ambient mesh are dropped (single-pod vs
        multi-pod vs 1-device meshes share the model code);
      * entries whose mesh extent does not divide the dimension are dropped —
        e.g. 8 KV heads on a 16-way model axis would otherwise make GSPMD
        subdivide the spare factor onto neighboring dims and pay involuntary
        full rematerializations (64 GiB/layer score all-gathers observed on
        llama's GQA in the roofline probes).
    """
    m = jax.sharding.get_abstract_mesh()
    names = m.axis_names if m is not None and m.axis_names else ()
    if not names:
        return x
    sizes = dict(m.shape)

    def _filter(entry, dim):
        if entry is None:
            return None
        axes = tuple(a for a in (entry if isinstance(entry, (tuple, list))
                                 else (entry,)) if a in names)
        if not axes:
            return None
        extent = 1
        for a in axes:
            extent *= sizes.get(a, 1)
        if extent == 0 or dim % extent != 0:
            return None
        return axes if isinstance(entry, (tuple, list)) else axes[0]

    cleaned = P(*(_filter(e, d) for e, d in zip(spec, x.shape)))
    return jax.lax.with_sharding_constraint(x, cleaned)


def batch_shard(x: jax.Array) -> jax.Array:
    """Shard the leading (batch) dim over pod+data."""
    axes = batch_axes()
    if not axes:
        return x
    return shard(x, axes, *([None] * (x.ndim - 1)))


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

Param = Any  # nested dict pytree of jax.Array


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), dtype)
    return {"w": w * jnp.asarray(scale, dtype)}


def dense_bias_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16):
    p = dense_init(key, d_in, d_out, dtype)
    p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_spec(kind: str = "col") -> dict:
    """Megatron-style TP specs: col-parallel (out dim on model), row-parallel
    (in dim on model); the other dim carries FSDP over data."""
    if kind == "col":
        return {"w": P("data", "model")}
    if kind == "row":
        return {"w": P("model", "data")}
    if kind == "replicated":
        return {"w": P(None, None)}
    raise ValueError(kind)


def dense_apply(p, x):
    y = jnp.einsum("...d,df->...f", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm_init(d: int, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-6, gemma_style: bool = False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    norm = xf * jax.lax.rsqrt(var + eps)
    scale = p["scale"].astype(jnp.float32)
    scale = (1.0 + scale) if gemma_style else scale  # gemma2 stores (w - 1)
    return (norm * scale).astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16):
    return {"emb": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def embed_spec() -> dict:
    # vocab-parallel only: gathering a (vocab:model, d:data)-sharded table
    # with batch-sharded indices forces XLA SPMD into a full-rematerialization
    # reshard on the multi-pod mesh; keeping d replicated yields the clean
    # masked-local-gather + psum(model) lowering. Tables are <= 2GB anyway.
    return {"emb": P("model", None)}


# ---------------------------------------------------------------------------
# positional / activation helpers
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0):
    """Rotary embedding. x: (..., S, H, Dh), positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float | None):
    """Gemma-2 logit soft capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (jnp.tanh(x / cap) * cap).astype(x.dtype)


def swiglu_init(key, d: int, d_ff: int, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, d_ff, dtype),
        "w_up": dense_init(k2, d, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d, dtype),
    }


def swiglu_spec() -> dict:
    return {
        "w_gate": dense_spec("col"),
        "w_up": dense_spec("col"),
        "w_down": dense_spec("row"),
    }


def swiglu_apply(p, x, act=jax.nn.silu):
    h = act(dense_apply(p["w_gate"], x)) * dense_apply(p["w_up"], x)
    h = shard(h, batch_axes(), *([None] * (h.ndim - 2)), "model")
    return dense_apply(p["w_down"], h)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, labels: jax.Array, mask=None):
    """Token cross-entropy in f32; vocab dim may be sharded (GSPMD reduces)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()
