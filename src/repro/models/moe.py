"""Mixture-of-Experts layers with SparseP-style sparse dispatch.

The token->expert assignment of an MoE layer *is* a sparse matrix: rows are
tokens, columns experts, with top_k nonzeros per row.  Dispatch (gathering
each expert's tokens) and combine (scattering weighted outputs back) are the
two SpMM halves of that matrix — so the paper's machinery applies directly
(DESIGN.md §4.1):

  * the dispatch permutation is built exactly like SparseP's element-granular
    COO partitioning: sort assignment triplets by expert (the "row"), then
    slot tokens into equal-capacity expert buffers — the same equal-capacity
    padding that UPMEM's equal-transfer-size constraint forces (Obs. 10/14).
    Capacity overflow = dropped tokens (reported as padding efficiency).
  * expert FFNs run as one batched GEMM over the expert axis, sharded over
    the ``model`` mesh axis (expert parallelism); GSPMD inserts the
    all-to-all for token movement.
  * the combine step is the transpose SpMM: a weighted scatter-add — the
    paper's lock-free merge.

Two routers: Mixtral (softmax over 8, top-2 — arXiv:2401.04088) and
DeepSeek-V3 (sigmoid scores + per-expert bias, group-limited top-8 over 256
routed + 1 shared expert — arXiv:2412.19437).
"""
from __future__ import annotations

from typing import NamedTuple

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import dense_init, shard

__all__ = ["moe_init", "moe_spec", "moe_apply"]


def moe_init(key, cfg, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (E, d, f), dtype) * scale.astype(dtype),
        "w_up": jax.random.normal(ks[2], (E, d, f), dtype) * scale.astype(dtype),
        "w_down": jax.random.normal(ks[3], (E, f, d), dtype)
        * (1.0 / jnp.sqrt(jnp.asarray(f, dtype))),
    }
    if cfg.moe_router == "deepseek":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)  # aux-loss-free balance
    if cfg.n_shared_experts:
        from .common import swiglu_init

        p["shared"] = swiglu_init(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts, dtype)
    return p


EP_AXES = ("pod", "data")  # expert-parallel axes == the batch-shard axes, so
# the dispatch reshard is a same-axis all-to-all (the canonical MoE pattern;
# a (pod,data)<->model exchange makes XLA SPMD fall back to full replication).


def moe_spec(cfg) -> dict:
    if cfg.n_experts >= 64:
        # many small experts (deepseek 256e): EP over the batch axes; the
        # model axis shards d on the up-projections (so dispatch buffers and
        # their all-to-all stay d-sharded — 16x less per-device traffic) and
        # d on the down-projection output (combine stays d-sharded too); the
        # only TP reduction is in f-space (f=2048 << d=7168).  §Perf cell 2.
        expert_specs = {
            "w_gate": P(EP_AXES, "model", None),
            "w_up": P(EP_AXES, "model", None),
            "w_down": P(EP_AXES, None, "model"),
        }
    else:
        # few large experts (mixtral 8e): experts replicated in compute
        # (tokens never move); weights sharded for storage — d over the
        # batch axes (gathered per layer, ~100 MB), f over model.
        expert_specs = {
            "w_gate": P(None, EP_AXES, "model"),
            "w_up": P(None, EP_AXES, "model"),
            # d sharded over the batch axes for STORAGE (w_down + its f32
            # optimizer moments are 90 GB at mixtral scale — 16-way sharding
            # alone blows per-device HBM); GSPMD gathers d per layer at
            # compute time (~100 MB/device/layer)
            "w_down": P(None, "model", EP_AXES),
        }
    sp = {"router": {"w": P(None, None)}, **expert_specs}
    if cfg.moe_router == "deepseek":
        sp["router_bias"] = P(None)
    if cfg.n_shared_experts:
        from .common import swiglu_spec

        sp["shared"] = swiglu_spec()
    return sp


class Routing(NamedTuple):
    expert: jax.Array  # (T, k) int32 expert ids        — COO column indices
    weight: jax.Array  # (T, k) f32 combine gates       — COO values
    # (token index = COO row index, implicit by position)


def _router_logits(p, x):
    """f32 router logits without materializing an f32 activation copy."""
    return jnp.einsum("...d,de->...e", x, p["router"]["w"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _route_mixtral(p, x, k):
    logits = _router_logits(p, x)  # (..., E) f32
    w, idx = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(w, axis=-1)
    return Routing(idx.astype(jnp.int32), w)


def _route_deepseek(p, x, k):
    """Sigmoid affinity + bias-adjusted selection, gates from raw affinities
    normalized over the selected set (DeepSeek-V3 §2.2, no aux loss)."""
    aff = jax.nn.sigmoid(_router_logits(p, x))  # (..., E) f32
    sel_score = aff + p["router_bias"][None, :]
    _, idx = jax.lax.top_k(sel_score, k)
    g = jnp.take_along_axis(aff, idx, axis=-1)
    w = g / jnp.maximum(g.sum(-1, keepdims=True), 1e-9)
    return Routing(idx.astype(jnp.int32), w)


def _group_axes(cfg) -> tuple:
    """Dispatch-group mesh axes == the batch-shard axes: tokens are grouped
    exactly as they are already sharded, so dispatch is collective-free."""
    return EP_AXES


def _n_batch_shards(axes) -> int:
    """Shard-group count over ``axes`` from the ambient mesh (1 without)."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return 1
    sizes = dict(m.shape)
    g = 1
    for a in axes:
        g *= sizes.get(a, 1)
    return g


def _local_dispatch(xg, eid, gate, E, cap):
    """Slot one shard-group's tokens into per-expert buffers (gather form).

    xg: (T_loc, d); eid/gate: (T_loc*k,).  Pure per-group function (vmapped
    over groups) — this keeps the SparseP row-sort LOCAL to a device, exactly
    like the paper's per-core slices, so GSPMD never gathers activations.
    Formulated as a slot->token GATHER (the inverse permutation) rather than
    a token->slot scatter: gathers lower to cheap dynamic fetches and their
    VJP is a single scatter-add (the lock-free merge).
    """
    T_k = eid.shape[0]
    k = T_k // xg.shape[0]  # assignments per token
    order = jnp.argsort(eid, stable=True)  # row-sort (format invariant)
    eid_s = eid[order]
    gate_s = gate[order]
    tok_s = (order // k).astype(jnp.int32)
    first = jnp.searchsorted(eid_s, jnp.arange(E, dtype=jnp.int32), side="left")
    nxt = jnp.concatenate([first[1:], jnp.array([T_k], jnp.int32)])
    # slot (e, c) <- sorted assignment first[e] + c (valid while < next[e])
    pos = first[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]  # (E,cap)
    slot_valid = pos < nxt[:, None]
    src_tok = jnp.take(tok_s, jnp.clip(pos, 0, T_k - 1).reshape(-1), axis=0)
    xbuf = jnp.take(xg, src_tok, axis=0)  # (E*cap, d)
    xbuf = jnp.where(slot_valid.reshape(-1, 1), xbuf, 0).reshape(E, cap, -1)
    # assignment -> its slot (for the combine gather); dropped -> E*cap
    slot_of = jnp.arange(T_k, dtype=jnp.int32) - jnp.take(first, eid_s,
                                                          mode="clip")
    keep = slot_of < cap  # capacity overflow -> dropped (padding efficiency)
    asg_slot = jnp.where(keep, eid_s * cap + slot_of, E * cap)
    return xbuf, (asg_slot, tok_s, gate_s, keep)


def _local_combine(ybuf, meta, T_loc, d_shard):
    asg_slot, tok_s, gate_s, keep = meta
    E_cap = ybuf.shape[0] * ybuf.shape[1]
    flat = ybuf.reshape(E_cap, d_shard)
    contrib = jnp.take(flat, jnp.clip(asg_slot, 0, E_cap - 1), axis=0)
    contrib = contrib * jnp.where(keep, gate_s, 0.0)[:, None].astype(contrib.dtype)
    return jnp.zeros((T_loc, d_shard), contrib.dtype).at[tok_s].add(
        contrib, mode="drop"
    )


def moe_apply(p, x, cfg):
    """x: (B, S, d) -> (B, S, d).

    SparseP COO dispatch, kept LOCAL per batch-shard group (the paper's
    per-core partitioning): routing + slotting run vmapped over G groups
    (G = batch shards from the ambient mesh), so the only collectives are the
    G<->E reshard around the expert GEMMs — the canonical MoE all-to-all —
    and the combine scatter (the paper's lock-free merge).
    """
    B, S, d = x.shape
    T = B * S
    k = cfg.moe_top_k
    E = cfg.n_experts
    gaxes = _group_axes(cfg)
    G = _n_batch_shards(gaxes)
    if T % G or (T // G) < 8:  # tiny smoke runs: single group
        G = 1
    T_loc = T // G
    cap = cfg.moe_capacity(T_loc)

    xg = x.reshape(G, T_loc, d)
    xg = shard(xg, gaxes, None, None)

    route = (
        _route_deepseek(p, xg, k)
        if cfg.moe_router == "deepseek"
        else _route_mixtral(p, xg, k)
    )
    eid = route.expert.reshape(G, T_loc * k)
    gate = route.weight.reshape(G, T_loc * k)

    x_dispatch = xg
    if cfg.n_experts >= 64:
        # d-shard tokens before dispatch so slot buffers are BORN d-sharded
        x_dispatch = shard(xg, gaxes, None, "model")
    xbuf, meta = jax.vmap(
        lambda xgi, ei, gi: _local_dispatch(xgi, ei, gi, E, cap)
    )(x_dispatch, eid, gate)  # xbuf: (G, E, cap, d), sharded over G

    if cfg.n_experts >= 64:
        # ---- many small experts (deepseek): reshard G-sharded -> E-sharded
        # over the SAME axes — a clean transpose all-to-all, carried out on
        # d-SHARDED buffers (16x less per-device A2A traffic; §Perf cell 2,
        # iteration 5).
        e_axes = gaxes
        xbuf = shard(xbuf.transpose(1, 0, 2, 3), e_axes, None, None, "model")
        # up-projections contract the d:model shards -> f-space partials;
        # the only TP reduction is over f (2048) instead of d (7168)
        h = jnp.einsum("egcd,edf->egcf", xbuf, p["w_gate"])
        u = jnp.einsum("egcd,edf->egcf", xbuf, p["w_up"])
        h = jax.nn.silu(h) * u
        h = shard(h, e_axes, None, None, None)  # psum(model) of f-partials
        # down-projection: d lands model-sharded with no further reduction;
        # bf16 output keeps the boundary in bf16 not the f32 accumulator
        ybuf = jnp.einsum("egcf,efd->egcd", h, p["w_down"],
                          preferred_element_type=x.dtype)
        ybuf = shard(ybuf, e_axes, None, None, "model")
        # reshard back E-sharded -> G-sharded (combine all-to-all, d-sharded)
        ybuf = shard(ybuf.transpose(1, 0, 2, 3), gaxes, None, None, "model")
    else:
        # ---- few large experts (mixtral): tokens NEVER move — each group
        # computes all E experts on its own slots; only the d-sharded expert
        # weights are gathered per layer (~100 MB), vs replicating the slot
        # buffers (GiBs) that a G<->E reshard forces when E does not divide
        # the expert axes (observed: 279 s collective term, §Perf).
        xbuf = shard(xbuf, gaxes, None, None, None)
        h = jnp.einsum("gecd,edf->gecf", xbuf, p["w_gate"])
        u = jnp.einsum("gecd,edf->gecf", xbuf, p["w_up"])
        h = jax.nn.silu(h) * u
        h = shard(h, gaxes, None, None, "model")
        ybuf = jnp.einsum("gecf,efd->gecd", h, p["w_down"],
                          preferred_element_type=x.dtype)
        ybuf = shard(ybuf, gaxes, None, None, None)  # psum over model (f)

    # ---- SparseP combine: transpose SpMM (weighted lock-free scatter-add)
    d_shard = ybuf.shape[-1]
    y = jax.vmap(lambda yb, m: _local_combine(yb, m, T_loc, d_shard))(ybuf, meta)
    y = shard(y, gaxes, None, None)  # all-gather d over model (token-sized)

    if cfg.n_shared_experts:
        from .common import swiglu_apply

        y = y + swiglu_apply(p["shared"], xg)
    return y.reshape(B, S, d).astype(x.dtype)
