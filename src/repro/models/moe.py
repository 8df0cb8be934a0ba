"""Mixture-of-Experts FFN: top-k router + sort-based capacity dispatch.

Dispatch is MegaBlocks-style but with a static per-expert capacity so the
whole layer jits with fixed shapes: token-expert assignments are sorted by
expert id, each expert processes up to C = ceil(T*K/E * capacity_factor)
tokens, overflow drops (standard GShard semantics). Shared experts (the
DeepSeek fine-grained design) always run densely.

Sharding intent (see configs): routed expert weights are laid out [E, ...]
and sharded on the "model" axis (expert parallelism); tokens are sharded on
the data axes, so GSPMD materializes the dispatch as all-to-alls.
"""
from __future__ import annotations

import dataclasses
import math
import jax
import jax.numpy as jnp

from repro.models.common import dense_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert_ff: int
    n_shared: int = 0
    d_shared_ff: int = 0          # defaults to d_expert_ff * n_shared
    capacity_factor: float = 1.25
    router_norm_topk: bool = True  # normalize top-k probs (Qwen3/DeepSeek)
    # --- distribution knobs (populated by launch/cells.py from the mesh;
    # all default to the mesh-free no-op so smoke tests never see them) ---
    n_groups: int = 1              # dispatch groups per sequence (EP grain)
    hint_batch_axes: tuple = ()    # mesh axes carrying the batch dim
    hint_expert_axis: object = None  # mesh axis carrying the expert dim (EP)
    ep_mesh: object = None         # mesh for the explicit shard_map EP path


def init_moe(key, cfg: MoEConfig, d_model: int):
    ks = jax.random.split(key, 6)
    e, f = cfg.n_experts, cfg.d_expert_ff
    params = {
        "router": dense_init(ks[0], (d_model, e)),
        "w_gate": dense_init(ks[1], (e, d_model, f)),
        "w_up": dense_init(ks[2], (e, d_model, f)),
        "w_down": dense_init(ks[3], (e, f, d_model)),
    }
    if cfg.n_shared:
        fs = cfg.d_shared_ff or cfg.d_expert_ff * cfg.n_shared
        params["shared_gate"] = dense_init(ks[4], (d_model, fs))
        params["shared_up"] = dense_init(ks[5], (d_model, fs))
        params["shared_down"] = dense_init(ks[4], (fs, d_model))
    return params


def _dispatch_group(xt: jnp.ndarray, top_e: jnp.ndarray, top_p: jnp.ndarray,
                    e: int, cap: int):
    """Group-local sort dispatch: xt [T, d], top_e/p [T, k] ->
    (dispatched [e, cap, d], slot [T*k], keep [T*k], token [T*k], prob [T*k]).

    One group = one sequence, so the argsort never crosses devices when the
    batch is data-sharded (GShard-style grouping).
    """
    t, d = xt.shape
    k = top_e.shape[1]
    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_p = top_p.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sp = flat_e[order], flat_t[order], flat_p[order]
    start = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
    rank = jnp.arange(t * k, dtype=jnp.int32) - start[se].astype(jnp.int32)
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)  # overflow -> dump slot
    buf_tok = jnp.full((e * cap + 1,), t, dtype=jnp.int32)
    buf_tok = buf_tok.at[slot].set(jnp.where(keep, st, t))
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    dispatched = xt_pad[buf_tok[:-1]].reshape(e, cap, d)
    return dispatched, slot, keep, st, sp


def _combine_group(y: jnp.ndarray, slot, keep, st, sp, t: int) -> jnp.ndarray:
    """Weighted scatter back: y [e, cap, d] -> [T, d] (f32 accumulate)."""
    e, cap, d = y.shape
    y_flat = y.reshape(e * cap, d)
    gathered = y_flat[jnp.minimum(slot, e * cap - 1)]
    gathered = jnp.where(keep[:, None],
                         gathered.astype(jnp.float32) * sp[:, None], 0.0)
    src = jnp.where(keep, st, t)
    return jnp.zeros((t + 1, d), jnp.float32).at[src].add(gathered)[:t]


def moe_ffn(params, x: jnp.ndarray, cfg: MoEConfig) -> jnp.ndarray:
    """x: [B, S, d] -> [B, S, d]. Routing/dispatch run per group (a
    contiguous S/n_groups token chunk of one sequence); expert weights are
    shared and [E, ...]-stacked (expert-shardable).

    Distribution (when the hint_* fields are set): the flattened group axis
    is sharded over (batch_axes, expert_axis) — tokens of different groups
    live on different chips — while ``dispatched``/``y`` are constrained to
    expert sharding on the EP axis, so GSPMD realizes the dispatch/combine
    as the canonical MoE all-to-all pair (tokens·top_k·d per chip) instead
    of replicating the [G, E, cap, d] buffers.
    """
    from repro.models.common import hint

    b, s, d = x.shape
    k, e = cfg.top_k, cfg.n_experts
    ng = cfg.n_groups if s % max(cfg.n_groups, 1) == 0 else 1
    sg = s // ng
    ba = tuple(cfg.hint_batch_axes)
    ep = cfg.hint_expert_axis

    # --- routing (f32 for numerics) ---
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)           # [B, S, k]
    if cfg.router_norm_topk:
        top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-9)

    # group axis stays a SEPARATE tensor dim (B -> data, G -> EP axis):
    # flattened (data, model) shardings trigger GSPMD's involuntary-full-
    # rematerialization path (measured 137 GB all-reduces — §Perf log)
    xg = hint(x.reshape(b, ng, sg, d), ba, ep, None, None)
    te = top_e.reshape(b, ng, sg, k)
    tp = top_p.reshape(b, ng, sg, k).astype(jnp.float32)

    cap = max(1, math.ceil(sg * k / e * cfg.capacity_factor))
    dispatch = jax.vmap(jax.vmap(
        lambda xt, tei, tpi: _dispatch_group(xt, tei, tpi, e, cap)))
    dispatched, slot, keep, st, sp = dispatch(xg, te, tp)  # [B, G, e, cap, d]

    wg = params["w_gate"].astype(x.dtype)
    wu = params["w_up"].astype(x.dtype)
    wd = params["w_down"].astype(x.dtype)

    def _experts(d_in, wg_, wu_, wd_):
        g = jnp.einsum("bgecd,edf->bgecf", d_in, wg_)
        u = jnp.einsum("bgecd,edf->bgecf", d_in, wu_)
        return jnp.einsum("bgecf,efd->bgecd", jax.nn.silu(g) * u, wd_)

    if cfg.ep_mesh is not None and ep is not None:
        # Explicit EP: dispatch/combine all-to-alls + FSDP weight gather in
        # a shard_map. GSPMD's auto choice for the same program all-gathers
        # the [B,G,e,cap,d] buffers through the backward pass (10.7 GB/layer
        # measured — §Perf log); the explicit form moves exactly
        # tokens·top_k·cf·d per chip per direction.
        from jax.sharding import PartitionSpec as P
        mesh_ = cfg.ep_mesh
        dfs_ = tuple(a for a in mesh_.axis_names if a != ep)

        def body(d_loc, wg_, wu_, wd_):
            wg_f = jax.lax.all_gather(wg_, dfs_, axis=2, tiled=True)
            wu_f = jax.lax.all_gather(wu_, dfs_, axis=2, tiled=True)
            wd_f = jax.lax.all_gather(wd_, dfs_, axis=1, tiled=True)
            d_ep = jax.lax.all_to_all(d_loc, ep, split_axis=2,
                                      concat_axis=1, tiled=True)
            y_ = _experts(d_ep, wg_f, wu_f, wd_f)
            return jax.lax.all_to_all(y_, ep, split_axis=1, concat_axis=2,
                                      tiled=True)

        espec = P(ep, None, dfs_ if len(dfs_) > 1 else dfs_[0])
        dspec = P(ep, dfs_ if len(dfs_) > 1 else dfs_[0], None)
        y = jax.shard_map(
            body, mesh=mesh_,
            in_specs=(P(ba if len(ba) != 1 else ba[0], ep, None, None, None),
                      espec, espec, dspec),
            out_specs=P(ba if len(ba) != 1 else ba[0], ep, None, None, None),
            check_vma=False)(dispatched, wg, wu, wd)
    else:
        # EP resharding point: group-sharded -> expert-sharded (hint form)
        dispatched = hint(dispatched, ba, None, ep, None, None)
        y = _experts(dispatched, wg, wu, wd)
        # combine resharding point: expert-sharded -> group-sharded
        y = hint(y, ba, ep, None, None, None)

    combine = jax.vmap(jax.vmap(
        lambda yi, sl, kp, sti, spi: _combine_group(yi, sl, kp, sti, spi,
                                                    sg)))
    out = combine(y, slot, keep, st, sp)
    out = hint(out, ba, ep, None, None).reshape(b, s, d).astype(x.dtype)

    if cfg.n_shared:
        gs = jnp.einsum("bsd,df->bsf", x, params["shared_gate"].astype(x.dtype))
        us = jnp.einsum("bsd,df->bsf", x, params["shared_up"].astype(x.dtype))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * us,
                               params["shared_down"].astype(x.dtype))
    return out


def router_aux_loss(params, x: jnp.ndarray, cfg: MoEConfig) -> jnp.ndarray:
    """Switch-style load-balance auxiliary loss (mean fraction * mean prob)."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32),
                    axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac * mean_p)
