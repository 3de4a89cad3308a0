"""Mixture-of-experts FFN: top-k routing, capacity-based dispatch.

Design for SPMD (DESIGN.md §6): tokens stay batch-sharded ('data'/'pod');
the dispatch buffer [B, E, C, D] is built with *per-row* (per-batch-element)
positions so construction is local to the data shard; the expert GEMM is
sharded over experts on the 'model' axis (expert parallelism).  GSPMD
inserts the dispatch/combine resharding (the all-to-all analogue) at the
einsum boundaries.  Active-FLOP accounting is exact: expert GEMMs process
E*C = top_k * capacity_factor * S slots per row, never the dense E-fold
blowup.

Router aux (load-balance) loss follows Switch/GShard: E * sum_e f_e * P_e.
Overflowed tokens (pos >= C) are dropped by scatter mode='drop' — their
residual path still carries them (standard capacity semantics).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ShardingCtx, dense_init
from repro.models.mlp import mlp_apply, mlp_params


def moe_params(key, cfg: ArchConfig):
    m = cfg.moe
    D = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "wr": dense_init(ks[0], D, m.num_experts, dtype=jnp.float32),
        "wi": jax.vmap(lambda k: dense_init(k, D, m.d_expert))(
            jax.random.split(ks[1], m.num_experts)),
        "wg": jax.vmap(lambda k: dense_init(k, D, m.d_expert))(
            jax.random.split(ks[2], m.num_experts)),
        "wo": jax.vmap(lambda k: dense_init(k, m.d_expert, D))(
            jax.random.split(ks[3], m.num_experts)),
    }
    if m.num_shared:
        p["shared"] = mlp_params(ks[4], D, m.num_shared * m.d_expert, act="silu")
    return p


def capacity(S: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(S * m.top_k * m.capacity_factor / m.num_experts) + 1
    return max(8, -(-c // 8) * 8)


def moe_apply(p, x, *, cfg: ArchConfig, ctx: ShardingCtx):
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar).

    On a mesh, token routing runs inside a shard_map island: MANUAL over the
    data axes (the dispatch scatter/combine gather are token-local, so GSPMD
    never sees a data-dependent scatter to replicate — §Perf: it replicated
    the [B_global, S*k, D] dispatch updates, 275 GB/layer on olmoe), AUTO
    over the model axis (expert GEMMs stay EP-sharded by GSPMD).
    """
    if ctx.active and ctx.mesh is not None and ctx.batch and ctx.model:
        from jax.sharding import PartitionSpec as P_

        mesh = ctx.mesh
        dp, mx = ctx.batch, ctx.model

        def inner(x_loc, p_loc):
            y_partial, aux = _moe_apply_manual(p_loc, x_loc, cfg=cfg,
                                               model_axis=mx)
            y = jax.lax.psum(y_partial, mx)          # combine across experts
            return y, jax.lax.pmean(aux, dp)

        wspec = {
            "wr": P_(),                              # router replicated
            "wi": P_(mx, None, None),                # experts EP-sharded
            "wg": P_(mx, None, None),
            "wo": P_(mx, None, None),
        }
        if "shared" in p:
            wspec["shared"] = {"wi": P_(None, mx),   # shared experts TP-split
                               "wg": P_(None, mx),
                               "wo": P_(mx, None)}
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P_(dp, None, None), wspec),
                             out_specs=(P_(dp, None, None), P_()),
                             check_vma=False)(x, p)
    return _moe_apply_local(p, x, cfg=cfg, ctx=ctx)


def _moe_apply_manual(p, x, *, cfg: ArchConfig, model_axis: str):
    """Manual EP: runs per (data, model) shard.  Tokens are replicated over
    the model axis; each model shard dispatches to ITS E_loc experts and
    produces a partial [B, S, D] (the caller psums over the model axis).
    Identical math to _moe_apply_local (tested)."""
    B, S, D = x.shape
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    C = capacity(S, cfg)
    E_loc = p["wi"].shape[0]
    midx = jax.lax.axis_index(model_axis)
    lo = midx * E_loc * C

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["wr"])
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    me = jnp.mean(gates, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(topi[..., 0], E), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    choice_e = topi.reshape(B, S * k)
    onehot = jax.nn.one_hot(choice_e, E, dtype=jnp.int32)
    pos = jnp.einsum("bte,bte->bt", jnp.cumsum(onehot, axis=1) - 1, onehot)
    keep = pos < C
    slot = jnp.where(keep, choice_e * C + pos, E * C)       # global slots
    slot_loc = jnp.where(
        jnp.logical_and(slot >= lo, slot < lo + E_loc * C),
        slot - lo, E_loc * C)                               # mine or drop

    xt = jnp.repeat(x.reshape(B, S, 1, D), k, axis=2).reshape(B, S * k, D)
    disp = jnp.zeros((B, E_loc * C + 1, D), x.dtype)
    disp = disp.at[jnp.arange(B)[:, None], slot_loc].add(xt, mode="drop")
    disp = disp[:, : E_loc * C].reshape(B, E_loc, C, D)

    h = jnp.einsum("becd,edf->becf", disp, p["wi"])
    g = jax.nn.silu(jnp.einsum("becd,edf->becf", disp, p["wg"]))
    y_e = jnp.einsum("becf,efd->becd", h * g, p["wo"])

    y_flat = y_e.reshape(B, E_loc * C, D)
    y_flat = jnp.concatenate([y_flat, jnp.zeros((B, 1, D), y_e.dtype)], 1)
    picked = jnp.take_along_axis(y_flat, slot_loc[..., None], axis=1)
    picked = picked.reshape(B, S, k, D)
    y = jnp.einsum("bskd,bsk->bsd", picked, topv.astype(x.dtype))

    if m.num_shared:
        from repro.models.mlp import mlp_apply
        from repro.models.common import NULL_CTX
        y = y + mlp_apply(p["shared"], x, act="silu", ctx=NULL_CTX)
    return y, aux.astype(jnp.float32)


def _moe_apply_local(p, x, *, cfg: ArchConfig, ctx: ShardingCtx):
    B, S, D = x.shape
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    C = capacity(S, cfg)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["wr"])
    gates = jax.nn.softmax(logits, axis=-1)                     # [B, S, E]
    topv, topi = jax.lax.top_k(gates, k)                        # [B, S, k]
    topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (Switch form) ----
    me = jnp.mean(gates, axis=(0, 1))                           # P_e
    ce = jnp.mean(jax.nn.one_hot(topi[..., 0], E), axis=(0, 1)) # f_e (top-1)
    aux = E * jnp.sum(me * ce)

    # ---- per-row positions in each expert queue (local to the shard) ----
    choice_e = topi.reshape(B, S * k)                           # row-major choices
    onehot = jax.nn.one_hot(choice_e, E, dtype=jnp.int32)       # [B, S*k, E]
    pos = jnp.einsum("bte,bte->bt", jnp.cumsum(onehot, axis=1) - 1, onehot)
    keep = pos < C
    slot = jnp.where(keep, choice_e * C + pos, E * C)           # OOR -> dropped

    # ---- dispatch: [B, E*C(+pad), D] scatter, then expert GEMMs ----
    xt = jnp.repeat(x.reshape(B, S, 1, D), k, axis=2).reshape(B, S * k, D)
    disp = jnp.zeros((B, E * C + 1, D), x.dtype)
    disp = disp.at[jnp.arange(B)[:, None], slot].add(xt, mode="drop")
    disp = disp[:, : E * C].reshape(B, E, C, D)
    disp = ctx.ct(disp, ctx.batch, ctx.model, None, None)       # EP layout

    h = jnp.einsum("becd,edf->becf", disp, p["wi"])
    g = jax.nn.silu(jnp.einsum("becd,edf->becf", disp, p["wg"]))
    y_e = jnp.einsum("becf,efd->becd", h * g, p["wo"])          # [B, E, C, D]
    y_e = ctx.ct(y_e, ctx.batch, None, None, None)              # combine layout

    # ---- combine ----
    if m.combine == "scatter":
        # slots scatter-add back into token order.  y_e stays EP-sharded, so
        # each model shard contributes its own (disjoint) slots and GSPMD
        # emits partial-[T,D] + all-reduce — k*cf/2 x fewer bytes than
        # all-gathering [B,E,C,D] (§Perf, MoE cells).
        # slots are unique per (token, choice) by construction, so .set is
        # race-free; dropped entries write index E*C which is sliced away.
        gate_of_slot = jnp.zeros((B, E * C + 1), jnp.float32)
        gate_of_slot = gate_of_slot.at[jnp.arange(B)[:, None], slot].set(
            topv.reshape(B, S * k))
        tok_of_slot = jnp.full((B, E * C + 1), S, jnp.int32)
        tok_of_slot = tok_of_slot.at[jnp.arange(B)[:, None], slot].set(
            jnp.broadcast_to(jnp.arange(S)[:, None], (S, k)).reshape(S * k))
        y_flat = y_e.reshape(B, E * C, D)
        weighted = y_flat * gate_of_slot[:, : E * C, None].astype(y_e.dtype)
        y = jnp.zeros((B, S + 1, D), y_e.dtype).at[
            jnp.arange(B)[:, None], tok_of_slot[:, : E * C]].add(
            weighted, mode="drop")[:, :S]
    else:
        y_flat = y_e.reshape(B, E * C, D)
        y_flat = jnp.concatenate([y_flat, jnp.zeros((B, 1, D), y_e.dtype)],
                                 axis=1)
        picked = jnp.take_along_axis(y_flat, slot[..., None], axis=1)
        picked = picked.reshape(B, S, k, D)
        y = jnp.einsum("bskd,bsk->bsd", picked, topv.astype(x.dtype))

    if m.num_shared:
        y = y + mlp_apply(p["shared"], x, act="silu", ctx=ctx)
    return y, aux.astype(jnp.float32)
