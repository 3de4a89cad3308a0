"""Fixed-phi inference core — the ONE token-major fold-in body shared by
serving, evaluation and the training driver's held-out hook (DESIGN.md §11).

The paper's deployment protocol (Eq. 20, §4) estimates theta for incoming
documents by BP fold-in with phi frozen.  This module is that inner loop as
a production artifact:

  - **token-major carry** (`TokenLayout`, DESIGN.md §2): messages live as
    [T, Kl] flat token streams; the fixed phi is gathered to [T, Kl] ONCE
    per batch (it never changes), so every sweep is pure elementwise work
    plus one per-doc reduction — no [D, L, K] rewrite per iteration;
  - **residual-based early exit per document**: each sweep carries the
    per-doc message residual r_d = sum_l c |mu' - mu|, whose sweep-over-
    sweep decay rho estimates the document's REMAINING movement as the
    geometric tail r_d * rho / (1 - rho).  A document freezes once that
    tail drops below ``residual_tol`` per token (its tokens stop updating,
    so its theta never moves again — and would have moved at most ~tol had
    it kept running); the loop ends when every document is frozen or
    ``iters`` is reached — the serving analogue of Fig. 4 line 26;
  - **kernel reuse with the phi update disabled**: the Pallas path runs
    the carry-resident `power_sweep_carry` megakernel with
    ``update_phi=False`` (the training-side packed delta/residual
    accumulation is dead; the per-doc theta delta and |delta| residual
    accumulate in-kernel instead) and the full vocabulary as the "power"
    rows, with frozen tokens routed to the guard row so the freeze
    happens in-kernel;
  - **topic sharding**: the renormalization and residual reductions go
    through a `Reducer` ("model"-axis psums, byte-metered), so the same
    body serves a topic-sharded phi — the init draws the random field at
    the GLOBAL K and slices the local columns (the K-axis analogue of
    ``LDAConfig.init_pad_len``), keeping sharded and unsharded fold-ins
    numerically aligned.

`fold_in_dense_reference` keeps the seed's dense [D, L, K] scan as the
semantics oracle and the BENCH_serve baseline; no production path calls it.

**W-capacity note** (DESIGN.md §12): the body is W-shape-agnostic — phi
arrives as an argument and tokens only ever gather their own rows — so a
capacity-laddered phi (guard rows above the live vocabulary) folds in
unchanged.  The live-W masking lives entirely in how phi_norm is built
(``perplexity.normalize_phi(..., live_w=...)``): guard rows carry the
beta-prior mass, which is what makes serving's OOV admission exact.
The Pallas path derives its row tables and guard-row index from phi's
own row count, so no part of the body depends on ``cfg.vocab_size``
matching the (possibly capacity-grown) phi it serves.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sync import CommMeter, LocalReducer, MeshReducer, Reducer
from repro.core.types import HIGHEST, LDAConfig, MiniBatch


@dataclasses.dataclass
class FoldInResult:
    """Device-resident fold-in diagnostics (a jax pytree).

    theta:  [D, Kl] normalized topic mixture (local topic shard)
    iters:  int32 scalar — sweeps actually run (early exit included)
    mean_r: final mean residual per token (the Fig. 4 line 26 quantity)
    r_doc:  [D] final per-document residual (the early-exit signal)
    """

    theta: jnp.ndarray
    iters: jnp.ndarray
    mean_r: jnp.ndarray
    r_doc: jnp.ndarray


jax.tree_util.register_dataclass(
    FoldInResult, data_fields=("theta", "iters", "mean_r", "r_doc"),
    meta_fields=())


def _init_messages(key: jax.Array, batch: MiniBatch, cfg: LDAConfig,
                   kl: int, model_reducer: Reducer) -> jnp.ndarray:
    """Random init, invariant to both the L bucket and the topic shard.

    Drawn at [D, max(init_pad_len, L), K_global] and sliced to this batch's
    L and this shard's topic columns, so the same document produces the
    same theta whichever bucket admitted it and however phi is sharded.
    """
    D, L = batch.word_ids.shape
    K = cfg.num_topics
    Lpad = L if cfg.init_pad_len is None else max(cfg.init_pad_len, L)
    u = jax.random.uniform(key, (D, Lpad, K), minval=0.01, maxval=1.0)[:, :L]
    if kl != K:
        idx = jax.lax.axis_index(model_reducer.axis_name)
        u = jax.lax.dynamic_slice_in_dim(u, idx * kl, kl, axis=2)
    norm = model_reducer.psum(jnp.sum(u, -1, keepdims=True), "model_norm",
                              compress=False)
    return u / norm


def _fold_in_carry(cfg: LDAConfig, impl: str, kl: int, n_docs: int,
                   local_topics: bool) -> str:
    """The fold-in formulation: 'xla' for the jnp impl, for a topic-sharded
    phi (the kernel normalizes over the whole topic axis in-kernel; the
    bypass is logged) and for shapes the carry kernel cannot take; else
    the full-K ('dense_layout') or K-blocked carry kernel
    (`core.sweep_dispatch.resolve_fold_in`, the same VMEM-fit dispatch as
    training — DESIGN.md §13)."""
    from repro.core.sweep_dispatch import note_bypass, resolve_fold_in
    if impl != "pallas":
        return "xla"
    if not local_topics:
        note_bypass("fold_in", dict(D=n_docs, Kl=kl))
        return "xla"
    return resolve_fold_in(kl, n_docs, cfg.sweep_policy,
                           cfg.vmem_budget_bytes)


def fold_in_tokens(key: jax.Array, batch: MiniBatch, phi_norm_wk: jnp.ndarray,
                   cfg: LDAConfig, iters: int = 30,
                   residual_tol: float = 0.0,
                   model_reducer: Optional[Reducer] = None,
                   impl: Optional[str] = None) -> FoldInResult:
    """Token-major BP fold-in with phi fixed (the shared inference body).

    `phi_norm_wk` [W, Kl] is the NORMALIZED topic-word matrix (this shard's
    topic columns when the model axis is sharded).  ``residual_tol == 0``
    disables early exit (every document sweeps all `iters` — the protocol
    `fold_in_dense_reference` implements); a positive tolerance freezes
    each document once its per-token residual drops below it and ends the
    loop when all have.  Returns a `FoldInResult` of device values.
    """
    model_reducer = model_reducer or LocalReducer()
    impl = cfg.impl if impl is None else impl
    D, L = batch.word_ids.shape
    Kl = phi_norm_wk.shape[1]
    layout = batch.token_layout()
    T = layout.num_slots
    c = layout.counts                                           # [T, 1]
    tok_d = c.reshape(D, L).sum(axis=1)                         # [D]
    total = jnp.maximum(jnp.sum(tok_d), 1.0)

    mu_t = _init_messages(key, batch, cfg, Kl, model_reducer).reshape(T, Kl)
    phi_tok = jnp.take(phi_norm_wk, layout.word_ids, axis=0)    # [T, Kl], once
    theta0 = (c * mu_t).reshape(D, L, Kl).sum(axis=1)           # [D, Kl]

    carry = _fold_in_carry(cfg, impl, Kl, D,
                           isinstance(model_reducer, LocalReducer))
    use_pallas = carry != "xla"
    if use_pallas:
        from repro.kernels.power_sweep.ops import power_sweep_carry
        # constant phi row table for the carry megakernel, built once per
        # fold-in: every phi row is a "power" row over all topics (the
        # kernel's update_phi=False mode needs no mask table — selection
        # is one compare against the appended guard row, which freezes
        # tokens in-kernel).  Everything derives from phi's OWN row count
        # so a capacity-grown phi folds in correctly whatever
        # cfg.vocab_size the caller holds.
        w_rows = phi_norm_wk.shape[0]
        phi_rows = jnp.concatenate(
            [phi_norm_wk, jnp.zeros((1, Kl), phi_norm_wk.dtype)], axis=0)
        mask_dummy = jnp.zeros((1, Kl), jnp.float32)
        pt_zero = jnp.zeros((Kl,), jnp.float32)
        serve_kblocked = carry == "kblocked"

    def active_docs(r_doc, r_prev):
        # geometric-tail bound on the theta movement still to come: with
        # per-sweep decay rho = r/r_prev, the remaining total is about
        # r * rho / (1 - rho).  The measured rho is floored at a
        # pessimistic 0.8 (fold-in decay slows as it converges, so the
        # instantaneous ratio understates the tail) and capped below 1 so
        # plateauing documents stay active until the iteration cap.
        rho = jnp.clip(r_doc / jnp.maximum(r_prev, 1e-30), 0.8, 0.95)
        tail = r_doc * rho / (1.0 - rho)
        return tail > residual_tol * tok_d

    def cond(carry):
        _, _, r_doc, r_prev, t = carry
        return jnp.logical_and(t < iters,
                               jnp.any(active_docs(r_doc, r_prev)))

    def body(carry):
        mu_t, theta, r_doc, r_prev, t = carry
        act_tok = active_docs(r_doc, r_prev)[layout.doc_ids]    # [T]
        if use_pallas:
            # carry-resident megakernel with the phi update disabled
            # (update_phi=False, kernels/power_sweep): one grid pass does
            # the theta gather, the pure update u = (theta - c*mu + alpha)
            # * phi_norm (beta = 0 passes phi through bit-exactly; the
            # zero pt argument and unit wbeta make the denominator exactly
            # 1), the fold-back, the per-doc theta delta AND the per-doc
            # |delta| residual.  Frozen tokens hit the guard row so the
            # freeze happens in-kernel; the packed delta/residual outputs
            # are dead on this path.
            p_tok = jnp.where(act_tok, layout.word_ids,
                              w_rows).astype(jnp.int32)
            mu_new, th_delta, _, _, r_local = power_sweep_carry(
                p_tok, layout.doc_ids, c, mu_t, theta, pt_zero,
                phi_rows, mask_dummy, alpha=cfg.alpha, beta=0.0, wbeta=1.0,
                update_phi=False, kblocked=serve_kblocked,
                vmem_budget_bytes=cfg.vmem_budget_bytes)
            theta = theta + th_delta
        else:
            th = theta[layout.doc_ids] - c * mu_t + cfg.alpha
            unnorm = th * phi_tok
            norm = model_reducer.psum(
                jnp.sum(unnorm, -1, keepdims=True), "model_norm_loop",
                compress=False)
            mu_new = unnorm / jnp.maximum(norm, 1e-30)
            mu_new = jnp.where(act_tok[:, None], mu_new, mu_t)
            delta = mu_new - mu_t
            theta = theta + (c * delta).reshape(D, L, Kl).sum(axis=1)
            r_local = (c * jnp.abs(delta)).reshape(D, L, Kl).sum(axis=(1, 2))
        r_new = model_reducer.psum(r_local, "model_rw_loop", compress=False)
        return mu_new, theta, r_new, r_doc, t + 1

    # r_doc starts at inf (everything active), r_prev at 1 so the first
    # rho is a clean clipped value rather than inf/inf
    carry0 = (mu_t, theta0, jnp.full((D,), jnp.inf, jnp.float32),
              jnp.ones((D,), jnp.float32), jnp.asarray(0, jnp.int32))
    _, theta, r_doc, _, t = jax.lax.while_loop(cond, body, carry0)

    th = theta + cfg.alpha
    denom = model_reducer.psum(jnp.sum(th, -1, keepdims=True), "theta_norm",
                               compress=False)
    return FoldInResult(theta=th / denom, iters=t,
                        mean_r=jnp.sum(r_doc) / total, r_doc=r_doc)


def make_fold_in_step(cfg: LDAConfig, fold_iters: int = 30,
                      residual_tol: float = 0.0, topic_shards: int = 1,
                      sync_dtype=jnp.float32, donate: bool = True,
                      impl: Optional[str] = None
                      ) -> Tuple[object, CommMeter]:
    """The production serving step: one jitted fixed-phi fold-in batch.

    Returns (step, meter) with ``step(phi_norm, key, word_ids, counts) ->
    (theta [D, K], iters, mean_r)``.  `phi_norm` is an argument (not a
    closure constant) so the engine keeps ONE device-resident copy across
    every bucket shape; with ``topic_shards > 1`` it is [N, W, K/N] stacked
    and the body runs under ``jax.vmap(axis_name="model")`` with psum'd
    renormalization — bit-identical collectives to a real model-axis mesh,
    byte-metered per request batch.  The batch buffers (key, word_ids,
    counts) are donated: per-request device allocations are recycled
    step-over-step.  Compiles once per distinct (D, L); feed it bucketed
    shapes (`data/batching.bucket_len`) to bound the compile count.
    """
    meter = CommMeter()
    if topic_shards == 1:
        reducer: Reducer = LocalReducer(meter=meter, sync_dtype=sync_dtype)
    else:
        reducer = MeshReducer("model", meter=meter, sync_dtype=sync_dtype)

    def body(phi_norm, key, word_ids, counts):
        res = fold_in_tokens(key, MiniBatch(word_ids, counts), phi_norm, cfg,
                             iters=fold_iters, residual_tol=residual_tol,
                             model_reducer=reducer, impl=impl)
        return res.theta, res.iters, res.mean_r

    def step(phi_norm, key, word_ids, counts):
        if topic_shards == 1:
            theta, it, mean_r = body(phi_norm, key, word_ids, counts)
        else:
            theta, it, mean_r = jax.vmap(
                body, in_axes=(0, None, None, None), axis_name="model")(
                    phi_norm, key, word_ids, counts)
            # [N, D, K/N] local shards -> [D, K] global mixture; the scalar
            # diagnostics are shard-identical by construction
            theta = jnp.transpose(theta, (1, 0, 2)).reshape(
                theta.shape[1], -1)
            it, mean_r = it[0], mean_r[0]
        return theta, it, mean_r

    donate_argnums = (1, 2, 3) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums), meter


# --------------------------------------------------------------------------
# continuous-batching slab step (DESIGN.md §16)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SlabState:
    """Persistent in-flight fold-in slab (a jax pytree, donated step-over-step).

    A fixed [B, L] grid of request slots: each live slot holds one
    document mid-fold-in.  All per-slot state advances together in
    `make_slab_step`'s jitted step; retirement/refill swaps individual
    slots from the host without ever changing a compiled shape.

    word_rows: int32 [B, L]   phi rows per token slot (0 when empty)
    counts:    f32   [B, L]   token counts (0 when empty / padding)
    mu:        f32   [B*L,Kl] token-major messages ([N, B*L, Kl] sharded)
    theta:     f32   [B, Kl]  doc-topic statistic  ([N, B, Kl] sharded)
    r_doc:     f32   [B]      last per-doc residual (early-exit signal)
    r_prev:    f32   [B]      previous residual (the geometric-tail rho)
    it:        int32 [B]      fold-in sweeps this slot's document has run
    live:      bool  [B]      slot holds an un-retired request
    """

    word_rows: jnp.ndarray
    counts: jnp.ndarray
    mu: jnp.ndarray
    theta: jnp.ndarray
    r_doc: jnp.ndarray
    r_prev: jnp.ndarray
    it: jnp.ndarray
    live: jnp.ndarray


jax.tree_util.register_dataclass(
    SlabState,
    data_fields=("word_rows", "counts", "mu", "theta", "r_doc", "r_prev",
                 "it", "live"),
    meta_fields=())


def make_slab_step(cfg: LDAConfig, *, slots: int, slot_len: int,
                   refill_cap: Optional[int] = None,
                   sweeps_per_step: int = 2, fold_iters: int = 30,
                   residual_tol: float = 1e-2, topic_shards: int = 1,
                   sync_dtype=jnp.float32, donate: bool = True,
                   impl: Optional[str] = None):
    """Continuous-batching serving step: advance every in-flight slot a few
    fold-in sweeps, retire the converged, refill mid-flight (DESIGN.md §16).

    Replaces bucket-barrier admission: instead of a batch that lives and
    dies together, a persistent [B = slots, L = slot_len] slab carries one
    live document per slot.  Each call to the returned ``step``:

      1. **refills**: scatters up to ``refill_cap`` freshly admitted
         documents into the slot indices the host picked (a retired or
         never-used slot; index ``slots`` marks an unused refill lane and
         is scatter-dropped), drawing each new document's random message
         init — or a warm-start init from a cached theta — in-step;
      2. **iterates**: runs ``sweeps_per_step`` token-major fold-in sweeps
         over the whole slab (the exact `fold_in_tokens` update; frozen /
         empty slots are masked, and on the Pallas path routed to the
         carry megakernel's guard row);
      3. **retires**: recomputes each live slot's geometric-tail residual
         bound; a slot whose remaining theta movement clears
         ``residual_tol`` per token (or that hit ``fold_iters``) comes
         back in the ``retired`` mask with its normalized theta.

    Compiles ONCE for the slab geometry — request shapes never reach the
    compiler, so admission is barrier-free: no request waits for a bucket
    to fill and no converged document holds its slot while stragglers
    finish.

    Returns ``(init_state, step, meter)`` where

      init_state() -> SlabState (all slots empty)
      step(phi_norm, state, refill_rows [R, L], refill_cnt [R, L],
           refill_slot [R], warm_theta [R, K], warm_mask [R], key)
        -> (state', retired [B] bool, theta_out [B, K], iters [B] int32,
            r_doc [B])

    ``phi_norm`` is an argument (one device-resident copy, swap-friendly);
    with ``topic_shards > 1`` it is the [N, W, K/N] stack from
    `split_topic_shards` and the body runs under ``jax.vmap`` with psum'd
    renormalization, byte-metered — the same simulation contract as
    `make_fold_in_step`.  ``state`` is donated: the slab never reallocates.
    """
    B, L = int(slots), int(slot_len)
    R = B if refill_cap is None else int(refill_cap)
    if not 0 < R <= B:
        raise ValueError(f"refill_cap={R} outside [1, slots={B}]")
    if sweeps_per_step < 1:
        raise ValueError(f"sweeps_per_step must be >= 1: {sweeps_per_step}")
    K = cfg.num_topics
    if K % topic_shards:
        raise ValueError(f"num_topics={K} does not divide over "
                         f"{topic_shards} topic shards")
    Kl = K // topic_shards
    meter = CommMeter()
    if topic_shards == 1:
        reducer: Reducer = LocalReducer(meter=meter, sync_dtype=sync_dtype)
    else:
        reducer = MeshReducer("model", meter=meter, sync_dtype=sync_dtype)
    carry = _fold_in_carry(cfg, cfg.impl if impl is None else impl, Kl, B,
                           topic_shards == 1)
    use_pallas = carry != "xla"
    doc_ids = jnp.repeat(jnp.arange(B, dtype=jnp.int32), L)       # [B*L]
    tol = float(residual_tol)

    def init_state() -> SlabState:
        lead = () if topic_shards == 1 else (topic_shards,)
        return SlabState(
            word_rows=jnp.zeros((B, L), jnp.int32),
            counts=jnp.zeros((B, L), jnp.float32),
            mu=jnp.zeros(lead + (B * L, Kl), jnp.float32),
            theta=jnp.zeros(lead + (B, Kl), jnp.float32),
            r_doc=jnp.zeros((B,), jnp.float32),
            r_prev=jnp.ones((B,), jnp.float32),
            it=jnp.zeros((B,), jnp.int32),
            live=jnp.zeros((B,), bool))

    def active_slots(r_doc, r_prev, it, live, tok_d):
        # the fold_in_tokens geometric-tail bound, per slot: remaining
        # theta movement ~ r * rho / (1 - rho) with rho the sweep-over-
        # sweep decay (pessimistic floor 0.8, capped below 1)
        rho = jnp.clip(r_doc / jnp.maximum(r_prev, 1e-30), 0.8, 0.95)
        tail = r_doc * rho / (1.0 - rho)
        return live & (it < fold_iters) & (tail > tol * tok_d)

    def body(phi_norm, state: SlabState, refill_rows, refill_cnt,
             refill_slot, warm_theta, warm_mask, key):
        # named scopes give the step's ops an owner on a device trace
        with jax.named_scope("slab.refill"):
            valid = refill_slot < B                                    # [R]
            wid = state.word_rows.at[refill_slot].set(refill_rows, mode="drop")
            cnt = state.counts.at[refill_slot].set(refill_cnt, mode="drop")
            live = state.live.at[refill_slot].set(valid, mode="drop")

            # ---- fresh init for refilled slots (in-step, per-slot random) --
            # drawn at the GLOBAL K and sliced per topic shard, the same
            # K-invariant contract as _init_messages; warm-started slots seed
            # their messages from the cached theta instead (one BP half-step:
            # m_l ∝ theta_cached * phi_w_l), which restarts the fold-in near
            # the cached posterior so the residual bound clears in fewer sweeps
            u = jax.random.uniform(key, (R, L, K), minval=0.01, maxval=1.0)
            if Kl != K:
                idx = jax.lax.axis_index("model")
                u = jax.lax.dynamic_slice_in_dim(u, idx * Kl, Kl, axis=2)
                warm_theta = jax.lax.dynamic_slice_in_dim(
                    warm_theta, idx * Kl, Kl, axis=1)
            phi_new = jnp.take(phi_norm, refill_rows.reshape(-1),
                               axis=0).reshape(R, L, Kl)
            warm_u = warm_theta[:, None, :] * phi_new             # [R, L, Kl]
            u = jnp.where(warm_mask[:, None, None], warm_u, u)
            norm0 = reducer.psum(jnp.sum(u, -1, keepdims=True),
                                 "slab_init_norm", compress=False)
            mu0 = u / jnp.maximum(norm0, 1e-30)
            c_new = refill_cnt[..., None]                         # [R, L, 1]
            theta0 = jnp.sum(c_new * mu0, axis=1)                     # [R, Kl]

            mu = state.mu.reshape(B, L, Kl).at[refill_slot].set(
                mu0, mode="drop").reshape(B * L, Kl)
            theta = state.theta.at[refill_slot].set(theta0, mode="drop")
            r_doc = state.r_doc.at[refill_slot].set(
                jnp.where(valid, jnp.inf, 0.0), mode="drop")
            r_prev = state.r_prev.at[refill_slot].set(1.0, mode="drop")
            it = state.it.at[refill_slot].set(0, mode="drop")

        # ---- iterate: sweeps_per_step token-major fold-in sweeps -------
        with jax.named_scope("slab.sweeps"):
            c = cnt.reshape(B * L, 1)
            tok_d = cnt.sum(axis=1)                                    # [B]
            wid_t = wid.reshape(B * L)
            phi_tok = jnp.take(phi_norm, wid_t, axis=0)            # [T, Kl]
            if use_pallas:
                from repro.kernels.power_sweep.ops import power_sweep_carry
                w_rows = phi_norm.shape[0]
                phi_rows = jnp.concatenate(
                    [phi_norm, jnp.zeros((1, Kl), phi_norm.dtype)], axis=0)
                mask_dummy = jnp.zeros((1, Kl), jnp.float32)
                pt_zero = jnp.zeros((Kl,), jnp.float32)
                kblocked = carry == "kblocked"
            for _ in range(sweeps_per_step):
                act_d = active_slots(r_doc, r_prev, it, live, tok_d)   # [B]
                act_tok = act_d[doc_ids]                               # [T]
                if use_pallas:
                    p_tok = jnp.where(act_tok, wid_t, w_rows).astype(jnp.int32)
                    mu_new, th_delta, _, _, r_local = power_sweep_carry(
                        p_tok, doc_ids, c, mu, theta, pt_zero,
                        phi_rows, mask_dummy, alpha=cfg.alpha, beta=0.0,
                        wbeta=1.0, update_phi=False, kblocked=kblocked,
                        vmem_budget_bytes=cfg.vmem_budget_bytes)
                    theta = theta + th_delta
                else:
                    th = theta[doc_ids] - c * mu + cfg.alpha
                    unnorm = th * phi_tok
                    norm = reducer.psum(jnp.sum(unnorm, -1, keepdims=True),
                                        "slab_norm_loop", compress=False)
                    mu_new = unnorm / jnp.maximum(norm, 1e-30)
                    mu_new = jnp.where(act_tok[:, None], mu_new, mu)
                    delta = mu_new - mu
                    theta = theta + (c * delta).reshape(B, L, Kl).sum(axis=1)
                    r_local = (c * jnp.abs(delta)).reshape(B, L, Kl).sum(
                        axis=(1, 2))
                r_new = reducer.psum(r_local, "slab_rw_loop", compress=False)
                r_prev = jnp.where(act_d, r_doc, r_prev)
                r_doc = jnp.where(act_d, r_new, r_doc)
                it = it + act_d.astype(jnp.int32)
                mu = mu_new

        # ---- retire: live slots whose residual bound cleared -----------
        with jax.named_scope("slab.retire"):
            still = active_slots(r_doc, r_prev, it, live, tok_d)
            retired = live & ~still
            th_out = theta + cfg.alpha
            denom = reducer.psum(jnp.sum(th_out, -1, keepdims=True),
                                 "slab_theta_norm", compress=False)
            theta_out = th_out / denom                              # [B, Kl]
            state = SlabState(word_rows=wid, counts=cnt, mu=mu, theta=theta,
                              r_doc=r_doc, r_prev=r_prev, it=it, live=still)
        return state, retired, theta_out, it, r_doc

    def step(phi_norm, state, refill_rows, refill_cnt, refill_slot,
             warm_theta, warm_mask, key):
        if topic_shards == 1:
            return body(phi_norm, state, refill_rows, refill_cnt,
                        refill_slot, warm_theta, warm_mask, key)
        in_state = SlabState(word_rows=None, counts=None, mu=0, theta=0,
                             r_doc=None, r_prev=None, it=None, live=None)
        out_st, retired, theta_out, it, r_doc = jax.vmap(
            body, in_axes=(0, in_state, None, None, None, None, None, None),
            axis_name="model")(phi_norm, state, refill_rows, refill_cnt,
                               refill_slot, warm_theta, warm_mask, key)
        # shared fields come back shard-replicated: keep shard 0; the
        # sharded mu/theta keep their leading [N] axis
        state = SlabState(word_rows=out_st.word_rows[0],
                          counts=out_st.counts[0], mu=out_st.mu,
                          theta=out_st.theta, r_doc=out_st.r_doc[0],
                          r_prev=out_st.r_prev[0], it=out_st.it[0],
                          live=out_st.live[0])
        # [N, B, K/N] local mixtures -> [B, K] global
        theta_out = jnp.transpose(theta_out, (1, 0, 2)).reshape(B, -1)
        return state, retired[0], theta_out, it[0], r_doc[0]

    donate_argnums = (1,) if donate else ()
    return init_state, jax.jit(step, donate_argnums=donate_argnums), meter


def split_topic_shards(phi_norm_wk: jnp.ndarray, topic_shards: int
                       ) -> jnp.ndarray:
    """[W, K] -> [N, W, K/N] contiguous topic shards (the layout
    `make_fold_in_step`'s vmap simulation consumes)."""
    if topic_shards == 1:
        return phi_norm_wk
    W, K = phi_norm_wk.shape
    if K % topic_shards:
        raise ValueError(f"num_topics={K} does not divide over "
                         f"{topic_shards} topic shards")
    return jnp.transpose(
        phi_norm_wk.reshape(W, topic_shards, K // topic_shards), (1, 0, 2))


def fold_in_dense_reference(key: jax.Array, batch: MiniBatch,
                            phi_norm_wk: jnp.ndarray, cfg: LDAConfig,
                            iters: int = 30) -> jnp.ndarray:
    """SEED-LAYOUT ORACLE: the dense [D, L, K] fold-in scan.

    Kept only as the semantics oracle for tests/test_serve.py and the
    BENCH_serve dense baseline — every production path (serve, eval, the
    driver's held-out hook) routes through `fold_in_tokens`.  Fixed-count
    scan, no early exit, whole-tensor rewrite per iteration.
    """
    D, L = batch.word_ids.shape
    K = phi_norm_wk.shape[1]
    Lpad = L if cfg.init_pad_len is None else max(cfg.init_pad_len, L)
    u = jax.random.uniform(key, (D, Lpad, K), minval=0.01, maxval=1.0)[:, :L]
    mu = u / jnp.sum(u, -1, keepdims=True)
    phi_tok = jnp.take(phi_norm_wk, batch.word_ids, axis=0)      # [D, L, K]
    c = batch.counts[..., None]

    def body(mu, _):
        theta = jnp.einsum("dl,dlk->dk", batch.counts, mu,
                           precision=HIGHEST)
        th = theta[:, None, :] - c * mu + cfg.alpha
        unnorm = th * phi_tok
        mu = unnorm / jnp.maximum(jnp.sum(unnorm, -1, keepdims=True), 1e-30)
        return mu, None

    mu, _ = jax.lax.scan(body, mu, None, length=iters)
    theta = jnp.einsum("dl,dlk->dk", batch.counts, mu,
                       precision=HIGHEST) + cfg.alpha
    return theta / jnp.sum(theta, -1, keepdims=True)
