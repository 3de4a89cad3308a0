"""POBP — parallel online belief propagation for LDA (the paper's Fig. 4).

One code path serves every execution mode:

  - **real mesh**: the per-shard functions below run under ``shard_map``
    with documents sharded over the ``data`` (and ``pod``) mesh axes and,
    optionally, topics sharded over the ``model`` axis
    (``launch/mesh.py`` + ``launch/dryrun.py``);
  - **simulation**: the same functions run under ``jax.vmap(axis_name=...)``
    with a leading shard axis — bit-identical collectives on one CPU device
    (tests, paper-figure benchmarks);
  - **OBP** (N=1): a ``LocalReducer`` degenerates every psum to identity —
    "If N = 1, POBP reduces to the OBP algorithm" (§3.2);
  - **batch BP** (M=1): one mini-batch covering the corpus — "If M = 1,
    POBP reduces to the parallel batch BP algorithm" (§3.2).

Sync modes:
  - ``power``  — the paper's communication-efficient MPA: dense sync at
    t=1, packed [P, Pk] power-submatrix sync for t>=2 (Eq. 6);
  - ``dense``  — the classic MPA baseline (Newman et al.; Eq. 4/5):
    full phi matrix every iteration.  Implemented for the paper's
    before/after comparison.

The power inner loop is **token-major and packed** (DESIGN.md §2): the
padded-CSR [D, L] batch flattens to a [T, K] token layout once per
mini-batch, each selective iteration works on flat token streams plus the
[P, Pk] sync buffers, and the word-residual convergence signal is carried
and updated incrementally in packed form.  The selective iteration has
two algebraically identical formulations — the [T, Pk] **packed** stream
with a fold-back chain, and the one-pass [T, K] **dense-layout** masked
update (the jnp mirror of the carry-resident `power_sweep` megakernel) —
chosen per shape by ``cfg.sweep_policy`` through the measured cost model
in `core.sweep_dispatch` (DESIGN.md §2 cost table).  Either way the
packed [P, Pk] Eq. 6 sync buffers are identical, so the communication
bill never depends on the compute layout.  `selective_sweep` is kept
below as the oracle/benchmark baseline.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import power as pw
from repro.core import quantize
from repro.core.residuals import (mean_residual, packed_rw_delta,
                                  token_scatter_wk)
from repro.core.sweep_dispatch import note_bypass, resolve_sweep_policy
from repro.core.sync import CommMeter, LocalReducer, MeshReducer, Reducer
from repro.core.types import (HIGHEST, LDAConfig, LDATrainState, MiniBatch,
                              TokenLayout)


# --------------------------------------------------------------------------
# dense (full) sweep — Fig. 4 lines 3-8 and the `dense` sync mode
# --------------------------------------------------------------------------

def dense_sweep(
    batch: MiniBatch,
    mu: jnp.ndarray,
    phi_eff_wk: jnp.ndarray,
    phi_tot: jnp.ndarray,
    cfg: LDAConfig,
    model_reducer: Reducer,
    norm_phase: str = "model_norm",
    wbeta=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One synchronous full update of all messages (Eq. 1).

    phi_eff_wk [W, Kl] is the *effective* topic-word statistic (accumulated
    prior + current-mini-batch contribution, already synchronized over data
    shards).  Kl is the local topic-shard width.  Returns (mu_new, r_wk).
    `norm_phase` labels the cross-topic-shard normalization psum — callers
    inside the inner while loop pass the per-iteration "model_norm_loop"
    so the byte meter can bill it per iteration (sync.LOOP_PHASES).
    `wbeta` overrides the W*beta smoothing mass — a capacity-laddered run
    passes the traced live_w*beta so guard rows never inflate the
    denominator (DESIGN.md §12); None keeps the static cfg value.
    """
    W = cfg.vocab_size
    wb = W * cfg.beta if wbeta is None else wbeta
    theta = jnp.einsum("dl,dlk->dk", batch.counts, mu,          # Eq. (2),
                       precision=HIGHEST)                     # local topics
    c = batch.counts[..., None]
    self_c = c * mu
    th = theta[:, None, :] - self_c + cfg.alpha
    ph = jnp.take(phi_eff_wk, batch.word_ids, axis=0) - self_c + cfg.beta
    pt = phi_tot[None, None, :] - self_c + wb
    unnorm = th * ph / pt
    norm = model_reducer.psum(jnp.sum(unnorm, axis=-1, keepdims=True),
                              norm_phase, compress=False)
    mu_new = unnorm / norm
    r_wk = token_scatter_wk(batch.word_ids, c * jnp.abs(mu_new - mu), W)
    return mu_new, r_wk


# --------------------------------------------------------------------------
# selective sweep — Fig. 4 lines 15-21 (power words x power topics only)
# --------------------------------------------------------------------------

def selective_sweep(
    batch: MiniBatch,
    mu: jnp.ndarray,
    theta: jnp.ndarray,
    phi_eff_wk: jnp.ndarray,
    phi_tot: jnp.ndarray,
    sel_w: jnp.ndarray,           # [P]      power word ids (identical on all shards)
    sel_k: jnp.ndarray,           # [P, Pk]  power topic ids per power word (local shard)
    cfg: LDAConfig,
):
    """Update messages only at (power word, power topic) coordinates.

    SEED-LAYOUT ORACLE: operates on the [D, L, K] batch-major messages and
    rewrites the full tensor per call.  The production inner loop uses the
    token-major `selective_sweep_tokens` below (numerically equivalent —
    pinned by tests/test_power_sweep.py); this version stays as the
    semantics oracle and the `benchmarks.run --only inner_loop` baseline.

    Never materializes a [W, K] intermediate: token deltas scatter straight
    into the packed [P, Pk] sync buffers (the TPU-native formulation of the
    paper's sparse communication — DESIGN.md §2).

    Returns (mu_new, theta_new, delta_phi_packed, r_packed).
    """
    D, L = batch.word_ids.shape
    P, Pk = sel_k.shape
    word_row = pw.word_to_row(sel_w, cfg.vocab_size)             # [W]
    p_tok = jnp.take(word_row, batch.word_ids, axis=0)           # [D, L] row or -1
    is_power = p_tok >= 0
    p_safe = jnp.where(is_power, p_tok, 0)
    k_tok = jnp.take(sel_k, p_safe, axis=0)                      # [D, L, Pk]

    c = batch.counts[..., None]                                  # [D, L, 1]
    mu_sel = jnp.take_along_axis(mu, k_tok, axis=-1)             # [D, L, Pk]
    sel_mass = jnp.sum(mu_sel, axis=-1, keepdims=True)           # conserved per shard
    self_c = c * mu_sel
    theta_sel = jnp.take_along_axis(
        jnp.broadcast_to(theta[:, None, :], (D, L, theta.shape[-1])), k_tok, axis=-1)
    phi_pack = pw.pack_rows(phi_eff_wk, sel_w, sel_k)            # [P, Pk]
    phi_sel = jnp.take(phi_pack, p_safe, axis=0)                 # [D, L, Pk]
    pt_sel = jnp.take(phi_tot, k_tok)                            # [D, L, Pk]

    th = theta_sel - self_c + cfg.alpha
    ph = phi_sel - self_c + cfg.beta
    pt = pt_sel - self_c + cfg.vocab_size * cfg.beta
    u = th * ph / pt
    # renormalize within the selected coordinates, conserving their old mass
    # (unselected message entries stay put => sum_k mu == 1 is invariant).
    mu_new_sel = u * sel_mass / jnp.maximum(jnp.sum(u, axis=-1, keepdims=True), 1e-30)
    mu_new_sel = jnp.where(is_power[..., None], mu_new_sel, mu_sel)

    d_mu = mu_new_sel - mu_sel                                   # [D, L, Pk]
    mu_new = jnp.put_along_axis(mu, k_tok, mu_new_sel, axis=-1, inplace=False)

    # theta update: scatter c * d_mu into [D, Kl] at selected topic coords
    d_idx = jnp.broadcast_to(jnp.arange(D)[:, None, None], (D, L, Pk))
    theta_new = theta.at[d_idx, k_tok].add((c * d_mu))

    # packed sync buffers: scatter straight to [P, Pk] (row==P drops padding)
    p_drop = jnp.where(is_power, p_tok, P).reshape(-1)           # [D*L]
    dv = (c * d_mu).reshape(-1, Pk)
    rv = (c * jnp.abs(d_mu)).reshape(-1, Pk)
    delta_phi_packed = jnp.zeros((P, Pk), mu.dtype).at[p_drop].add(dv, mode="drop")
    r_packed = jnp.zeros((P, Pk), mu.dtype).at[p_drop].add(rv, mode="drop")
    return mu_new, theta_new, delta_phi_packed, r_packed


# --------------------------------------------------------------------------
# token-major selective sweep — the production inner-loop body
# --------------------------------------------------------------------------

def _gather_selection(layout: TokenLayout, mu_t, theta, phi_tot, sel_k,
                      p_tok, num_power):
    """Per-token [T, Pk] gathers at the selected coordinates.

    All gathers are flat token streams — no [T, K] broadcast or temporary
    is ever formed (the jaxpr contract pinned in DESIGN.md §2).
    """
    p_safe = jnp.where(p_tok < num_power, p_tok, 0)
    k_tok = jnp.take(sel_k, p_safe, axis=0)                      # [T, Pk]
    mu_sel = jnp.take_along_axis(mu_t, k_tok, axis=1)            # [T, Pk]
    theta_sel = theta[layout.doc_ids[:, None], k_tok]            # [T, Pk]
    pt_sel = jnp.take(phi_tot, k_tok)                            # [T, Pk]
    return k_tok, mu_sel, theta_sel, pt_sel


def _apply_token_update(layout: TokenLayout, mu_t, theta, k_tok, mu_sel,
                        mu_new_sel):
    """Fold the [T, Pk] update back into the carried mu_t/theta, scatter-free.

    XLA's general scatter serializes per update element (~100ns/elem on
    CPU, similarly painful per-core on TPU); at T*Pk updates per iteration
    it dominates the sweep.  Instead the delta is accumulated through a
    static compare-select chain over the Pk selected columns — Pk fused
    vectorized passes that XLA folds into a single elementwise loop over
    the donated carry — and theta's per-doc reduction contracts the same
    delta against the counts in one einsum pass over the free [D, L, K]
    reshape view (an order of magnitude faster than the reduce_sum it
    replaces — DESIGN.md §2 cost table).  The true O(T*Pk) theta refresh
    (`residuals.token_topic_segment_sum`) is what the carry-resident
    kernel realizes on the MXU; XLA's element scatter loses to the
    contraction on CPU.

    Non-power tokens have d_mu == 0 exactly, so their carry entries are
    bit-identical after the add.
    """
    d_mu = mu_new_sel - mu_sel                                   # [T, Pk]
    K = mu_t.shape[1]
    iota = jnp.arange(K, dtype=k_tok.dtype)[None, :]
    delta = jnp.zeros_like(mu_t)
    for j in range(k_tok.shape[1]):                              # static Pk
        delta = delta + jnp.where(iota == k_tok[:, j:j + 1],
                                  d_mu[:, j:j + 1], 0.0)
    mu_t_new = mu_t + delta
    counts2 = layout.counts.reshape(layout.num_docs, layout.max_len)
    theta_new = theta + jnp.einsum(
        "dl,dlk->dk", counts2,
        delta.reshape(layout.num_docs, layout.max_len, K), precision=HIGHEST)
    return mu_t_new, theta_new, d_mu


def _selective_sweep_packed(
    layout: TokenLayout,
    mu_t: jnp.ndarray,            # [T, Kl] token-major messages
    theta: jnp.ndarray,           # [Dl, Kl]
    phi_eff_wk: jnp.ndarray,      # [W, Kl]
    phi_tot: jnp.ndarray,         # [Kl]
    sel_w: jnp.ndarray,           # [P]
    sel_k: jnp.ndarray,           # [P, Pk]
    cfg: LDAConfig,
    wbeta=None,
):
    """Packed-stream formulation: [T, Pk] gathers + fold-back chain.

    Same math as `selective_sweep` restricted to flat [T, Pk] streams:
    mass-conserving renormalization within the selected coordinates, packed
    [P, Pk] delta/residual outputs, untouched entries bit-identical.
    `wbeta` overrides the W*beta smoothing mass (live-W runs, §12).

    Returns (mu_t_new, theta_new, delta_phi_packed, r_packed).
    """
    P, Pk = sel_k.shape
    wb = cfg.vocab_size * cfg.beta if wbeta is None else wbeta
    p_tok = pw.token_power_rows(layout.word_ids, sel_w, cfg.vocab_size)
    k_tok, mu_sel, theta_sel, pt_sel = _gather_selection(
        layout, mu_t, theta, phi_tot, sel_k, p_tok, P)
    phi_pack = pw.pack_rows(phi_eff_wk, sel_w, sel_k)            # [P, Pk]
    phi_sel = jnp.take(phi_pack, jnp.where(p_tok < P, p_tok, 0), axis=0)

    c = layout.counts
    self_c = c * mu_sel
    sel_mass = jnp.sum(mu_sel, axis=-1, keepdims=True)           # conserved
    th = theta_sel - self_c + cfg.alpha
    ph = phi_sel - self_c + cfg.beta
    pt = pt_sel - self_c + wb
    u = th * ph / pt
    mu_new_sel = u * sel_mass / jnp.maximum(
        jnp.sum(u, axis=-1, keepdims=True), 1e-30)
    mu_new_sel = jnp.where((p_tok < P)[:, None], mu_new_sel, mu_sel)

    mu_t_new, theta_new, d_mu = _apply_token_update(
        layout, mu_t, theta, k_tok, mu_sel, mu_new_sel)
    cd, rv = c * d_mu, c * jnp.abs(d_mu)
    if layout.num_slots * P <= cfg.onehot_crossover:
        # one-hot contraction (the jnp mirror of the power_sweep kernel's
        # packed accumulation): tokens with p_tok == P match no column and
        # drop out.  The row scatter below covers shapes past the
        # configured crossover, where [T, P] MACs stop paying for
        # themselves (cfg.onehot_crossover, consumed by the dispatch cost
        # model in core/sweep_dispatch).
        onehot_p = (p_tok[:, None] ==
                    jnp.arange(P, dtype=p_tok.dtype)[None, :]).astype(mu_t.dtype)
        dims = (((0,), (0,)), ((), ()))
        delta_phi_packed = jax.lax.dot_general(onehot_p, cd, dims,
                                               precision=HIGHEST)
        r_packed = jax.lax.dot_general(onehot_p, rv, dims, precision=HIGHEST)
    else:
        # p_tok == P for non-power tokens -> dropped by the bounds check
        delta_phi_packed = jnp.zeros((P, Pk), mu_t.dtype).at[p_tok].add(
            cd, mode="drop")
        r_packed = jnp.zeros((P, Pk), mu_t.dtype).at[p_tok].add(
            rv, mode="drop")
    return mu_t_new, theta_new, delta_phi_packed, r_packed


def _selective_sweep_dense_layout(
    layout: TokenLayout, mu_t, theta, phi_eff_wk, phi_tot, sel_w, sel_k,
    cfg: LDAConfig, wbeta=None, model_reducer: Optional[Reducer] = None,
):
    """One-pass dense-layout formulation: masked [T, K] update, no chain.

    The jnp mirror of the carry-resident `power_sweep_carry` megakernel:
    the [T, K] carry is read and written exactly once per iteration,
    whatever Pk is.  A [P+1, K] *signed-phi* row table carries both the
    packed phi values and the selection in one gather — selected
    coordinates hold phi >= 0, everything else (and the whole p == P
    guard row) holds -1 — so the update

        u      = (theta - c mu + alpha)(phi - c mu + beta)
                 / (phi_tot - c mu + W beta)        where selected, else 0
        mu'    = u * mass / sum u                    (mass = selected mass)

    is a handful of fused [T, K] passes with u *exactly* zero off the
    power submatrix and untouched entries bit-identical (`where`, not
    arithmetic masking).  theta comes back through one counts contraction
    over the updated carry (theta == einsum(c, mu) is a loop invariant),
    and the packed [P, Pk] delta/residual buffers accumulate through a
    single complex-merged row scatter (delta in the real lane, |delta| in
    the imaginary lane — halves the serialized scatter elements) followed
    by an O(P*Pk) column pack.  Same contract and packed outputs as
    `_selective_sweep_packed`.

    ``model_reducer`` (topic-sharded runs only): ``sel_k`` holds this
    shard's local ids and the sentinel ``Kl`` for topics other shards own
    (`power.select_power_topics_merged`), so the selected mass and the
    denominator are psum'd over the model axis before the renormalization
    (exact over the global selection), and the sentinel's packed slots
    read 0.
    """
    P, Pk = sel_k.shape
    Kl = mu_t.shape[1]
    D, L = layout.num_docs, layout.max_len
    wb = cfg.vocab_size * cfg.beta if wbeta is None else wbeta
    p_tok3 = pw.token_power_rows(layout.word_ids, sel_w,
                                 cfg.vocab_size).reshape(D, L)
    mask = jnp.zeros((P + 1, Kl), bool).at[
        jnp.arange(P)[:, None], sel_k].set(True, mode="drop")
    phi_rows = jnp.concatenate(
        [jnp.take(phi_eff_wk, sel_w, axis=0),
         jnp.zeros((1, Kl), mu_t.dtype)], axis=0)                # [P+1, Kl]
    # sign carries the selection: selected coords hold phi (clamped at 0 —
    # incremental scatter_add refreshes can take a near-zero statistic a
    # few ulp negative, which must not flip the encoding), others -1.
    sphi = jnp.where(mask, jnp.maximum(phi_rows, 0.0), -1.0)
    sphi_tok = jnp.take(sphi, p_tok3, axis=0)                    # [D, L, Kl]
    selp = sphi_tok >= 0.0

    mu3 = mu_t.reshape(D, L, Kl)
    counts2 = layout.counts.reshape(D, L)
    c3 = counts2[..., None]
    self_c = c3 * mu3
    th = theta[:, None, :] - self_c + cfg.alpha
    ph = sphi_tok - self_c + cfg.beta
    pt = phi_tot[None, None, :] - self_c + wb
    u = jnp.where(selp, th * ph / pt, 0.0)
    mass = jnp.sum(jnp.where(selp, mu3, 0.0), -1, keepdims=True)
    denom = jnp.sum(u, -1, keepdims=True)
    fill = {}
    if model_reducer is not None:
        with jax.named_scope("pobp.model_norm"):
            both = model_reducer.psum(jnp.concatenate([mass, denom], -1),
                                      "model_norm_loop", compress=False)
            mass, denom = both[..., :1], both[..., 1:]
        fill = dict(mode="fill", fill_value=0)
    denom = jnp.maximum(denom, 1e-30)
    mu_new = jnp.where(selp, u * (mass / denom), mu3)
    theta_new = jnp.einsum("dl,dlk->dk", counts2, mu_new, precision=HIGHEST)
    cd = c3 * (mu_new - mu3)
    zc = jax.lax.complex(cd, jnp.abs(cd)).reshape(layout.num_slots, Kl)
    rows = jnp.zeros((P + 1, Kl), jnp.complex64).at[
        p_tok3.reshape(-1)].add(zc)
    d_pack = jnp.take_along_axis(jnp.real(rows[:P]), sel_k, axis=1, **fill)
    r_pack = jnp.take_along_axis(jnp.imag(rows[:P]), sel_k, axis=1, **fill)
    return (mu_new.reshape(layout.num_slots, Kl),
            theta_new, d_pack.astype(mu_t.dtype), r_pack.astype(mu_t.dtype))


def selective_sweep_tokens(
    layout: TokenLayout,
    mu_t: jnp.ndarray,            # [T, Kl] token-major messages
    theta: jnp.ndarray,           # [Dl, Kl]
    phi_eff_wk: jnp.ndarray,      # [W, Kl]
    phi_tot: jnp.ndarray,         # [Kl]
    sel_w: jnp.ndarray,           # [P]
    sel_k: jnp.ndarray,           # [P, Pk]
    cfg: LDAConfig,
    wbeta=None,
):
    """Token-major selective sweep (jnp production path, DESIGN.md §2).

    Dispatches between the packed-stream and dense-layout formulations per
    (T, K, Pk, P) through ``cfg.sweep_policy`` (resolved at trace time —
    static per compiled shape, never retraces across mini-batches).  Both
    produce identical packed [P, Pk] sync buffers and trajectories within
    float associativity; `theta` must be the doc-topic statistic of the
    incoming `mu_t` (a loop invariant of every caller).
    `wbeta` overrides the W*beta smoothing mass (live-W runs, §12).

    Returns (mu_t_new, theta_new, delta_phi_packed, r_packed).
    """
    P, Pk = sel_k.shape
    policy = resolve_sweep_policy(cfg, layout.num_slots, mu_t.shape[1],
                                  Pk, P, impl="jnp",
                                  n_docs=theta.shape[0])
    # 'kblocked' resolves to dense_layout on the jnp impl (same math; XLA
    # has no VMEM budget), so only two formulations exist here
    fn = (_selective_sweep_packed if policy == "packed"
          else _selective_sweep_dense_layout)
    return fn(layout, mu_t, theta, phi_eff_wk, phi_tot, sel_w, sel_k, cfg,
              wbeta=wbeta)


def _selective_sweep_carry_pallas(
    layout: TokenLayout, mu_t, theta, phi_eff_wk, phi_tot, sel_w, sel_k,
    cfg: LDAConfig, wbeta=None, kblocked: bool = False,
):
    """Carry-resident megakernel iteration (kernels/power_sweep).

    One grid pass over token tiles: the [TT, K] mu carry tile loads into
    VMEM once, the packed-phi/mask row tables and theta gather on the MXU
    (one-hot contractions), the selective update + renorm + fold-back
    write the carry back once, and the per-doc theta delta plus the
    [P1, K] delta/residual rows accumulate in VMEM across the whole grid
    — one HBM read and one write of the carry per iteration.  The small
    O(P*Pk) column pack happens outside the kernel; the packed [P, Pk]
    sync payload is identical to the jnp formulations.
    """
    from repro.kernels.power_sweep.ops import power_sweep_carry

    P, Pk = sel_k.shape
    Kl = mu_t.shape[1]
    p_tok = pw.token_power_rows(layout.word_ids, sel_w, cfg.vocab_size)
    mask = jnp.zeros((P + 1, Kl), jnp.float32).at[
        jnp.arange(P)[:, None], sel_k].set(1.0, mode="drop")
    phi_rows = jnp.concatenate(
        [jnp.take(phi_eff_wk, sel_w, axis=0), jnp.zeros((1, Kl))], axis=0)
    if wbeta is None:
        pt_arg, wb_static = phi_tot, cfg.vocab_size * cfg.beta
    else:
        # traced live-W smoothing folds into the phi_tot argument with the
        # kernel's static wbeta pinned at 1.0 (same trick as core/infer)
        pt_arg, wb_static = phi_tot + (wbeta - 1.0), 1.0
    mu_new, theta_delta, d_rows, r_rows, _ = power_sweep_carry(
        p_tok, layout.doc_ids, layout.counts, mu_t, theta, pt_arg,
        phi_rows, mask, alpha=cfg.alpha, beta=cfg.beta, wbeta=wb_static,
        update_phi=True, kblocked=kblocked,
        vmem_budget_bytes=cfg.vmem_budget_bytes)
    d_pack = jnp.take_along_axis(d_rows[:P], sel_k, axis=1)
    r_pack = jnp.take_along_axis(r_rows[:P], sel_k, axis=1)
    return mu_new, theta + theta_delta, d_pack, r_pack


def selective_sweep_tokens_pallas(
    layout: TokenLayout, mu_t, theta, phi_eff_wk, phi_tot, sel_w, sel_k,
    cfg: LDAConfig, wbeta=None,
):
    """Fused-kernel selective sweep, policy-dispatched like the jnp path.

    ``xla`` (auto's resolution where the carry kernels' one-hot work or
    VMEM footprint rules them out) runs the jnp dense-layout formulation.
    ``dense_layout`` (the 'auto' resolution on the pallas backend while
    the full-K carry fits VMEM) runs the carry-resident
    `power_sweep_carry` megakernel — one HBM read + one write of the
    [T, K] carry per iteration.  ``kblocked`` (auto's resolution past the
    VMEM-fit boundary, DESIGN.md §13) runs the same math as the K-blocked
    two-pass kernel.  ``packed`` keeps the
    [T, Pk]-stream pipeline: Pallas power_pack gather + the power_sweep
    kernel + the jnp fold-back chain.  Same contract either way.  A
    traced `wbeta` (live-W runs) folds into the pre-gathered pt argument
    with the kernel's static wbeta pinned at 1.0 — the kernels need no
    new code, and the unit offset keeps the ops-layer lane padding away
    from 0/0 (same trick as core/infer).
    """
    P, Pk = sel_k.shape
    policy = resolve_sweep_policy(cfg, layout.num_slots, mu_t.shape[1],
                                  Pk, P, impl="pallas",
                                  n_docs=theta.shape[0])
    if policy == "xla":
        # the dispatch printed why (core/sweep_dispatch.DISPATCH_LOG)
        return _selective_sweep_dense_layout(
            layout, mu_t, theta, phi_eff_wk, phi_tot, sel_w, sel_k, cfg,
            wbeta=wbeta)
    if policy in ("dense_layout", "kblocked"):
        return _selective_sweep_carry_pallas(
            layout, mu_t, theta, phi_eff_wk, phi_tot, sel_w, sel_k, cfg,
            wbeta=wbeta, kblocked=(policy == "kblocked"))

    from repro.kernels.power_pack import ops as pp_ops
    from repro.kernels.power_sweep.ops import power_sweep

    p_tok = pw.token_power_rows(layout.word_ids, sel_w, cfg.vocab_size)
    k_tok, mu_sel, theta_sel, pt_sel = _gather_selection(
        layout, mu_t, theta, phi_tot, sel_k, p_tok, P)
    phi_pack = pp_ops.pack_rows(phi_eff_wk, sel_w, sel_k)        # Pallas
    if wbeta is None:
        pt_arg, wb_static = pt_sel, cfg.vocab_size * cfg.beta
    else:
        pt_arg, wb_static = pt_sel + (wbeta - 1.0), 1.0
    mu_new_sel, delta_phi_packed, r_packed = power_sweep(
        p_tok, layout.counts, mu_sel, theta_sel, pt_arg, phi_pack,
        alpha=cfg.alpha, beta=cfg.beta, wbeta=wb_static)
    mu_t_new, theta_new, _ = _apply_token_update(
        layout, mu_t, theta, k_tok, mu_sel, mu_new_sel)
    return mu_t_new, theta_new, delta_phi_packed, r_packed


# --------------------------------------------------------------------------
# the per-shard mini-batch routine (Fig. 4 body, one m)
# --------------------------------------------------------------------------

_FIELD_MIN, _FIELD_MAX = 0.01, 1.0


@functools.partial(jax.jit, static_argnames=("L", "K", "kl"))
def _field_columns(key: jax.Array, L: int, K: int, k0, kl: int
                   ) -> jnp.ndarray:
    """Columns [k0, k0 + kl) of ``jax.random.uniform(key, (L, K),
    minval=0.01, maxval=1.0)``, bit for bit, without drawing the others.

    Under ``jax_threefry_partitionable`` element (l, k) of that draw is
    the threefry hash of the counter l*K + k under the key (the high
    counter word is 0 while L*K < 2**32), turned into a float the way
    `jax.random.uniform` does; a column block hashes its own counters.
    ``key`` is a raw threefry key (uint32[2])."""
    from jax.extend.random import threefry2x32_p
    cols = jnp.asarray(k0, jnp.uint32) + jnp.arange(kl, dtype=jnp.uint32)
    lo = jnp.arange(L, dtype=jnp.uint32)[:, None] * jnp.uint32(K) + cols
    # every operand at one shape and, under vmap, one batching (the
    # primitive's CPU lowering needs both): each depends on key and k0
    lo = lo + key[0] * 0
    zero = lo * 0
    b1, b2 = threefry2x32_p.bind(key[0] + zero, key[1] + zero, zero, lo)
    mant = jax.lax.shift_right_logical(b1 ^ b2, jnp.uint32(32 - 23))
    f = jax.lax.bitcast_convert_type(mant | jnp.uint32(0x3F800000),
                                     jnp.float32) - jnp.float32(1.0)
    lo_v, hi_v = jnp.float32(_FIELD_MIN), jnp.float32(_FIELD_MAX)
    return jnp.maximum(lo_v, f * (hi_v - lo_v) + lo_v)


def init_field(key: jax.Array, D: int, L: int, cfg: LDAConfig, kl: int,
               doc0, k0) -> jnp.ndarray:
    """The random message field u0 [D, L, kl] of a shard whose documents
    start at global index ``doc0`` and whose topics start at ``k0``.

    Each document's field is its [Lpad, K] draw from ``fold_in(key,
    global doc index)`` at the full K, restricted to this shard's topic
    columns, so the trajectory does not depend on how documents and
    topics are laid over shards or chips (an N-shard run and a 1-shard
    run start alike).  A topic shard hashes only its own columns
    (`_field_columns`): the full draw would be a [D, L, K] temporary on
    every chip.  cfg.init_pad_len: the field is drawn at a fixed padded
    length and sliced, so phi_acc is invariant to the L bucket this batch
    landed in (shape-bucketed streaming; padding slots have zero counts);
    a row's counters do not depend on Lpad.
    """
    K = cfg.num_topics
    docs = doc0 + jnp.arange(D, dtype=jnp.int32)
    if (kl != K and key.dtype == jnp.uint32 and key.shape == (2,)
            and jax.config.jax_threefry_partitionable and L * K < 2**32):
        return jax.vmap(lambda d: _field_columns(
            jax.random.fold_in(key, d), L=L, K=K, k0=k0, kl=kl))(docs)
    Lpad = L if cfg.init_pad_len is None else max(cfg.init_pad_len, L)
    u = jax.vmap(lambda d: jax.random.uniform(
        jax.random.fold_in(key, d), (Lpad, K), minval=_FIELD_MIN,
        maxval=_FIELD_MAX))(docs)[:, :L]
    if kl != K:
        u = jax.lax.dynamic_slice_in_dim(u, k0, kl, axis=2)
    return u


@dataclasses.dataclass
class MinibatchResult:
    phi_acc_new: jnp.ndarray       # [W, Kl] accumulated statistic after this batch
    iters: jnp.ndarray             # iterations actually run (incl. the dense one)
    mean_r: jnp.ndarray            # final mean residual (line 26 quantity)
    mu: jnp.ndarray                # final messages (for theta/perplexity)
    theta: jnp.ndarray             # final doc-topic statistics [Dl, Kl]


def pobp_minibatch(
    batch: MiniBatch,
    phi_acc_wk: jnp.ndarray,
    key: jax.Array,
    total_tokens: jnp.ndarray,
    delta_weight: jnp.ndarray,
    cfg: LDAConfig,
    data_reducer: Reducer,
    model_reducer: Optional[Reducer] = None,
    sync_mode: str = "power",
    live_w=None,
    decay=None,
) -> MinibatchResult:
    """Run one mini-batch to convergence on this shard (all Fig. 4 lines).

    `batch` is this shard's document slice; `phi_acc_wk` [W, Kl] is the
    synchronized accumulated statistic (identical on all data shards);
    `total_tokens` is the *global* mini-batch token count (psum'd once by the
    caller); `delta_weight` scales the accumulated gradient (Eq. 11).

    `live_w` (a traced int32 scalar) switches the W axis to capacity-ladder
    semantics (DESIGN.md §12): phi_acc_wk is [W_cap, Kl] with rows in
    [live_w, W_cap) as guard rows — every batch word id is < live_w, the
    W*beta smoothing uses live_w, power selection masks guard rows and
    caps the power-word count at the live lambda_w fraction.  Because all
    of this depends only on live_w (never on the rung), a run that grew
    across rungs and a fresh run allocated at the final rung compute
    identical trajectories.  None keeps the static fixed-W behavior.

    `decay` (a traced f32 scalar, or None) is the Robbins-Monro retention
    factor 1 - rho_m on the historical statistic (DESIGN.md §14): the
    Eq. 11 fold-back becomes ``decay * phi_acc + delta_weight * Delta``,
    so stale mass fades multiplicatively while the current batch enters
    at full weight.  None (decay_kappa == 0) keeps the exact
    plain-accumulation expression — bit-exact with the pre-lifecycle
    trajectory.  The decay pass reads + rewrites the full [W, Kl]
    statistic once per mini-batch, billed to the meter's ``decay`` phase.
    """
    model_reducer = model_reducer or LocalReducer(meter=data_reducer.meter)
    # topics sharded over the model axis: power topics are selected over
    # every shard's columns (an exact merge) and renormalized over them
    topic_sharded = not isinstance(model_reducer, LocalReducer)
    W = cfg.vocab_size
    Kl = phi_acc_wk.shape[1]
    P = cfg.num_power_words
    Pk = min(cfg.num_power_topics, cfg.num_topics if topic_sharded else Kl)
    wbeta = (None if live_w is None
             else jnp.asarray(live_w, jnp.float32) * cfg.beta)
    layout = batch.token_layout()    # persistent token-major view (§2)
    # compressed-accumulator runs (DESIGN.md §13) ship every phi/residual
    # statistic sync at the storage width: the Eq. 5/6 payload bytes halve
    # and the wire round-trip matches the precision the statistic is kept
    # at anyway.  None leaves the cfg.sync_dtype behavior untouched.
    phi_wire = (jnp.bfloat16 if cfg.phi_acc_dtype == "bfloat16" else None)

    # ---- lines 3-8: random init, local stats, first dense update ----
    # Each phase runs under a jax.named_scope named after the CommMeter's
    # phase vocabulary; the scope reaches every compiled instruction's
    # op_name, so a device trace's ops can be charged to their phase
    # (repro.obs.op_scopes).
    D, L = batch.word_ids.shape
    with jax.named_scope("pobp.init"):
        u0 = init_field(key, D, L, cfg, Kl,
                        data_reducer.shard_index() * D,
                        model_reducer.shard_index() * Kl)
        mu0 = u0 / model_reducer.psum(jnp.sum(u0, -1, keepdims=True),
                                      "model_norm", compress=False)
        delta_local0 = token_scatter_wk(batch.word_ids,
                                        batch.counts[..., None] * mu0, W)
        phi_eff = phi_acc_wk + delta_local0      # local phi^0 (Fig. 4 line 5)
        phi_tot = jnp.sum(phi_eff, axis=0)
    pallas_dense = cfg.impl == "pallas" and isinstance(model_reducer,
                                                       LocalReducer)
    if cfg.impl == "pallas" and not pallas_dense:
        note_bypass("dense_sweep", dict(D=D, L=L, Kl=Kl))
    with jax.named_scope("pobp.dense_sweep"):
        if pallas_dense:
            # fused Pallas kernel (normalization in-kernel => K unsharded)
            from repro.kernels.bp_update.ops import dense_sweep_pallas
            mu1, r_wk_local = dense_sweep_pallas(batch, mu0, phi_eff, phi_tot,
                                                 cfg, layout, wbeta=wbeta)
        else:
            mu1, r_wk_local = dense_sweep(batch, mu0, phi_eff, phi_tot, cfg,
                                          model_reducer, wbeta=wbeta)

    # ---- lines 9-10: dense synchronization of phi and r ----
    with jax.named_scope("pobp.dense_sync"):
        delta_glob = data_reducer.psum(
            token_scatter_wk(batch.word_ids,
                             batch.counts[..., None] * mu1, W),
            "dense", w_rows=W, dtype=phi_wire)
        phi_eff = phi_acc_wk + delta_glob
        phi_tot = jnp.sum(phi_eff, axis=0)
        r_glob = data_reducer.psum(r_wk_local, "dense", w_rows=W,
                                   dtype=phi_wire)
        theta = jnp.einsum("dl,dlk->dk", batch.counts, mu1,
                           precision=HIGHEST)
        r_w = model_reducer.psum(jnp.sum(r_glob, axis=1), "model_rw",
                                 compress=False, w_rows=W)

    if sync_mode == "power":
        # Token-major persistent inner loop (DESIGN.md §2): messages are
        # carried as [T, Kl], every iteration touches only [T, Pk] token
        # streams + [P, Pk] packed buffers, and the r_w convergence signal
        # updates incrementally from the packed residual refresh instead of
        # an O(W*K) row reduction per iteration.
        if cfg.impl == "pallas":
            sweep_fn = selective_sweep_tokens_pallas
            from repro.kernels.power_pack import ops as pp_ops
            phi_scatter = pp_ops.scatter_add_rows
        else:
            sweep_fn = selective_sweep_tokens
            phi_scatter = pw.scatter_add_rows
        if topic_sharded:
            # only the XLA dense-layout formulation renormalizes across
            # topic shards; the kernels and the packed stream normalize
            # over the shard's own columns
            if cfg.sweep_policy == "packed":
                raise ValueError(
                    "sweep_policy='packed' cannot run with topics sharded "
                    "over the model axis: use 'auto' or 'dense_layout'")
            if cfg.impl == "pallas":
                note_bypass("selective_sweep",
                            dict(T=layout.num_slots, Kl=Kl, Pk=Pk, P=P))
            sweep_fn = functools.partial(_selective_sweep_dense_layout,
                                         model_reducer=model_reducer)
        carry0 = (mu1.reshape(layout.num_slots, Kl), theta, phi_eff, phi_tot,
                  r_glob, r_w, jnp.asarray(1, jnp.int32))

        def cond(carry):
            *_, r_w_c, t = carry
            return jnp.logical_and(t < cfg.inner_iters,
                                   mean_residual(r_w_c, total_tokens) > cfg.residual_tol)

        def body(carry):
            mu_t, theta, phi_eff, phi_tot, r_glob, r_w_c, t = carry
            # lines 12-13 / 27-28: two-step power selection (identical on
            # every data shard -- computed from synchronized residuals).
            # Live-W runs mask guard rows out and cap the selection at the
            # live lambda_w fraction; dead slots point at the first guard
            # row, whose packed values are exact zeros (§12).
            with jax.named_scope("pobp.select"):
                if live_w is None:
                    sel_w = pw.select_power_words(r_w_c, P)
                else:
                    sel_w = pw.select_power_words_live(r_w_c, P, live_w,
                                                       cfg.lambda_w)
                if topic_sharded:
                    sel_k = pw.select_power_topics_merged(
                        r_glob, sel_w, Pk, model_reducer)
                else:
                    sel_k = pw.select_power_topics(r_glob, sel_w, Pk)
            with jax.named_scope("pobp.selective_sweep"):
                mu_t, theta, d_phi_pack, r_pack = sweep_fn(
                    layout, mu_t, theta, phi_eff, phi_tot, sel_w, sel_k, cfg,
                    wbeta=wbeta)
            # lines 23-24: communicate only the power submatrices (the [P,
            # Pk] buffers scale with W through P = lambda_w*W: live-W
            # accounting bills only the live fraction of their rows)
            with jax.named_scope("pobp.power_sync"):
                d_phi_pack = data_reducer.psum(d_phi_pack, "power", w_rows=W,
                                               dtype=phi_wire)
                r_pack = data_reducer.psum(r_pack, "power", w_rows=W,
                                           dtype=phi_wire)
            # packed-carry refresh: O(P*Pk) state updates, Eq. 9
            with jax.named_scope("pobp.scatter"):
                rw_delta = packed_rw_delta(r_glob, sel_w, sel_k, r_pack,
                                           sentinel=topic_sharded)
                phi_eff = phi_scatter(phi_eff, sel_w, sel_k, d_phi_pack)
                phi_tot = phi_tot + jnp.zeros_like(phi_tot).at[sel_k].add(
                    d_phi_pack)
                r_glob = pw.scatter_set_rows(r_glob, sel_w, sel_k, r_pack)
                rw_delta = model_reducer.psum(rw_delta, "model_rw_loop",
                                              compress=False, w_rows=W)
                r_w_c = r_w_c.at[sel_w].add(rw_delta)
            return (mu_t, theta, phi_eff, phi_tot, r_glob, r_w_c, t + 1)

        mu_t, theta, phi_eff, phi_tot, r_glob, r_w, t = jax.lax.while_loop(
            cond, body, carry0)
        mu = layout.to_batch_major(mu_t)
    elif sync_mode == "dense":
        carry0 = (mu1, theta, phi_eff, phi_tot, r_w, jnp.asarray(1, jnp.int32))

        def cond(carry):
            *_, r_w_c, t = carry
            return jnp.logical_and(t < cfg.inner_iters,
                                   mean_residual(r_w_c, total_tokens) > cfg.residual_tol)

        def body(carry):
            mu, theta, phi_eff, phi_tot, _, t = carry
            mu, r_wk = dense_sweep(batch, mu, phi_eff, phi_tot, cfg,
                                   model_reducer, norm_phase="model_norm_loop",
                                   wbeta=wbeta)
            delta = data_reducer.psum(
                token_scatter_wk(batch.word_ids, batch.counts[..., None] * mu, W),
                "dense_loop", w_rows=W, dtype=phi_wire)
            phi_eff = phi_acc_wk + delta
            phi_tot = jnp.sum(phi_eff, axis=0)
            theta = jnp.einsum("dl,dlk->dk", batch.counts, mu,
                               precision=HIGHEST)
            r_w_c = model_reducer.psum(
                jnp.sum(data_reducer.psum(r_wk, "dense_loop", w_rows=W,
                                          dtype=phi_wire),
                        axis=1),
                "model_rw_loop", compress=False, w_rows=W)
            return (mu, theta, phi_eff, phi_tot, r_w_c, t + 1)

        mu, theta, phi_eff, phi_tot, r_w, t = jax.lax.while_loop(cond, body, carry0)
    else:
        raise ValueError(f"unknown sync_mode: {sync_mode}")

    # ---- Eq. (11): accumulate this batch's synchronized gradient ----
    with jax.named_scope("pobp.accumulate"):
        if decay is None:
            phi_acc_new = phi_acc_wk + delta_weight * (phi_eff - phi_acc_wk)
        else:
            # RM decay (§14): retain (1 - rho_m) of the historical
            # statistic.  phi_eff - phi_acc_wk is exactly this batch's
            # synchronized Delta, so the expression below is the decayed
            # Eq. 11 and reduces to the branch above at decay == 1.  The
            # full-statistic touch is billed once per mini-batch (not a
            # psum — decay is shard-local and identical everywhere, but it
            # is a real [W, Kl] HBM pass).
            data_reducer.bill(phi_acc_wk, "decay", w_rows=W)
            phi_acc_new = (decay * phi_acc_wk
                           + delta_weight * (phi_eff - phi_acc_wk))
    return MinibatchResult(phi_acc_new=phi_acc_new, iters=t,
                           mean_r=mean_residual(r_w, total_tokens),
                           mu=mu, theta=theta)


# --------------------------------------------------------------------------
# drivers
# --------------------------------------------------------------------------
#
# Every execution mode funnels through ONE per-shard body (pobp_shard_body):
#   - `make_train_step`        jitted, donated-carry production step
#                              (vmap N-shard simulation; the streaming
#                              driver `launch.lda_train` and `run_stream`)
#   - `make_sim_minibatch_fn`  the stateless single-mini-batch entry used
#                              by tests and paper-figure benchmarks
#   - `make_mesh_shard_fn`     the shard_map body for the production mesh
#                              (launch.dryrun's compile-only cell and
#                              launch.lda_train's --backend shard_map)


def pobp_shard_body(word_ids, counts, phi_acc, key, delta_weight,
                    cfg: LDAConfig, data_reducer: Reducer,
                    model_reducer: Optional[Reducer] = None,
                    sync_mode: str = "power", live_w=None, decay=None):
    """One shard's complete mini-batch routine (Fig. 4, one m).

    `word_ids`/`counts` are THIS shard's [Dl, L] slice; `phi_acc` is the
    synchronized accumulated statistic.  The global token count is psum'd
    here ("tokens" phase), so callers never pre-reduce anything.
    `live_w` (traced) enables capacity-ladder W semantics (§12); `decay`
    (traced, or None) the RM retention on the fold-back (§14).
    Returns (phi_acc_new, iters, mean_r, mu, theta).
    """
    batch = MiniBatch(word_ids=word_ids, counts=counts)
    total = data_reducer.psum(jnp.sum(counts), "tokens", compress=False)
    res = pobp_minibatch(batch, phi_acc, key, total, delta_weight, cfg,
                         data_reducer, model_reducer, sync_mode=sync_mode,
                         live_w=live_w, decay=decay)
    return res.phi_acc_new, res.iters, res.mean_r, res.mu, res.theta


# fold_in tag deriving the stochastic-rounding key from the per-batch key
# without consuming the split stream (float32 runs stay bit-identical)
_SR_FOLD = 0x5F0C4


def _delta_weight(cfg: LDAConfig, m):
    """Traced Eq. 11 weight for the (1-indexed, possibly traced) batch m."""
    if cfg.lr_schedule == "paper":
        return jnp.float32(1.0)
    return (cfg.lr_tau0 + m.astype(jnp.float32)) ** (-cfg.lr_kappa)


def _decay_factor(cfg: LDAConfig, m):
    """Traced RM retention 1 - rho_m for batch m, or None when decay is off.

    rho_m = (decay_tau0 + m)^(-decay_kappa) is the classic Robbins-Monro
    step size (Hoffman-style online VB, DESIGN.md §14): the historical
    statistic keeps a (1 - rho_m) fraction per batch, so an untouched row
    decays multiplicatively toward zero while rho_m -> 0 makes the memory
    horizon grow as the model matures.  decay_kappa == 0 returns None —
    a *static* bypass, so the fold-back runs the identical expression the
    plain-accumulation path always ran (bit-exact, not merely close).
    """
    if not cfg.decay_kappa:
        return None
    rho = (jnp.float32(cfg.decay_tau0) + m.astype(jnp.float32)
           ) ** jnp.float32(-cfg.decay_kappa)
    return jnp.float32(1.0) - rho


def init_train_state(cfg: LDAConfig, seed: int = 0) -> LDATrainState:
    """Cold-start carry for `make_train_step` (phi_acc = 0, m = 0).

    phi_acc is allocated at ``cfg.phi_acc_dtype`` (DESIGN.md §13): the
    accumulate still runs in f32 — the carry only STORES narrow."""
    return LDATrainState(
        phi_acc=jnp.zeros((cfg.vocab_size, cfg.num_topics),
                          quantize.phi_acc_dtype(cfg)),
        m=jnp.zeros((), jnp.int32),
        rng=jax.random.PRNGKey(seed))


def grow_state(state: LDATrainState, new_vocab_cap: int) -> LDATrainState:
    """Grow-only capacity resize — `core.lifecycle.resize_state` without a
    fence (DESIGN.md §14 owns the full grow/shrink lifecycle; shrinking
    here still raises because no live_w fence is provided)."""
    from repro.core.lifecycle import resize_state
    return resize_state(state, new_vocab_cap)


def make_train_step(cfg: LDAConfig, num_shards: int = 1,
                    sync_mode: str = "power", sync_dtype=jnp.float32,
                    donate: bool = True, reducer: Optional[Reducer] = None):
    """The production streaming step: one jitted, donated-carry POBP batch.

    Returns (step, meter) with ``step(state, word_ids, counts) ->
    (new_state, diag)``.  `word_ids`/`counts` are [Dl, L] (num_shards == 1)
    or [N, Dl, L] stacked; `state` is an `LDATrainState` whose buffers are
    donated (constant memory over an unbounded stream — §3.2 / Table 5).
    `diag` = {iters, mean_r, theta} stays on device: the caller decides
    when to pay a host sync (asynchronous dispatch — the driver fetches
    every --log-every batches, never per batch).

    The step recompiles once per distinct (Dl, L) input shape; feed it
    through `repro.data.batching.bucketed_minibatch_stream` to bound the
    compile count.  Compiles so far: ``step._cache_size()``.

    ``step`` also accepts an optional trailing ``live_w`` (int32 scalar):
    the live vocabulary size of a capacity-laddered run whose cfg
    ``vocab_size`` is the current rung.  live_w is *traced*, so vocabulary
    growth within a rung never recompiles — only crossing a rung does
    (``grow_state`` + a fresh step; compiles <= #rungs x #buckets).

    ``reducer`` injects an alternative sync provider for the SAME shard
    body — `launch.lda_train --backend ps` passes a ``sync.PSReducer``
    (push/pull wire billing; identical in-step math) while the allreduce
    backends keep the default Local/Mesh reducer.  Injected reducers over
    a multi-shard body must reduce over axis name ``"shards"``.
    """
    if reducer is not None:
        meter = reducer.meter
    else:
        meter = CommMeter()
        if num_shards == 1:
            reducer = LocalReducer(meter=meter, sync_dtype=sync_dtype)
        else:
            reducer = MeshReducer("shards", meter=meter, sync_dtype=sync_dtype)

    storage = quantize.phi_acc_dtype(cfg)

    def body(wid, cnt, phi_acc, key, weight, live_w, decay):
        return pobp_shard_body(wid, cnt, phi_acc, key, weight, cfg, reducer,
                               sync_mode=sync_mode, live_w=live_w,
                               decay=decay)

    def step(state: LDATrainState, word_ids, counts, live_w=None):
        rng, sub = jax.random.split(state.rng)
        weight = _delta_weight(cfg, state.m + 1)
        decay = _decay_factor(cfg, state.m + 1)
        if num_shards == 1:
            phi, iters, mean_r, _mu, theta = body(word_ids, counts,
                                                  state.phi_acc, sub, weight,
                                                  live_w, decay)
        else:
            phi, iters, mean_r, _mu, theta = jax.vmap(
                body, in_axes=(0, 0, None, None, None, None, None),
                axis_name="shards")(
                    word_ids, counts, state.phi_acc, sub, weight, live_w,
                    decay)
            # shard-identical by construction: carry shard 0's copy
            phi, iters, mean_r = phi[0], iters[0], mean_r[0]
        if storage != jnp.float32:
            # fold the f32 accumulate back into the narrow carry with
            # stochastic rounding (core/quantize).  The SR key derives by
            # fold_in so the per-batch split stream above stays
            # bit-identical to a float32 run's.
            phi = quantize.stochastic_round(
                phi, storage, jax.random.fold_in(sub, _SR_FOLD))
        new_state = LDATrainState(phi_acc=phi, m=state.m + 1, rng=rng)
        return new_state, dict(iters=iters, mean_r=mean_r, theta=theta)

    return jax.jit(step, donate_argnums=(0,) if donate else ()), meter


def make_sim_minibatch_fn(cfg: LDAConfig, num_shards: int, sync_mode: str = "power",
                          sync_dtype=jnp.float32):
    """N-shard simulation on one device: vmap over a leading shard axis with a
    named axis so lax.psum is bit-identical to the mesh execution.

    Returns (jitted_fn, meter).  jitted_fn(word_ids[N,Dl,L], counts[N,Dl,L],
    phi_acc[W,Kl], key, delta_weight) -> MinibatchResult with leading N axis
    on mu/theta and shard-identical phi_acc_new (checked in tests).
    """
    meter = CommMeter()
    if num_shards == 1:
        reducer: Reducer = LocalReducer(meter=meter, sync_dtype=sync_dtype)
    else:
        reducer = MeshReducer("shards", meter=meter, sync_dtype=sync_dtype)

    def per_shard(word_ids, counts, phi_acc, key, delta_weight):
        return pobp_shard_body(word_ids, counts, phi_acc, key, delta_weight,
                               cfg, reducer, sync_mode=sync_mode)

    def fn(word_ids, counts, phi_acc, key, delta_weight):
        if num_shards == 1:
            return per_shard(word_ids, counts, phi_acc, key, delta_weight)
        return jax.vmap(per_shard, in_axes=(0, 0, None, None, None),
                        axis_name="shards")(word_ids, counts, phi_acc, key,
                                            delta_weight)

    return jax.jit(fn), meter


def make_mesh_shard_fn(cfg: LDAConfig, mesh_axis_names, sync_mode: str = "power",
                       sync_dtype=jnp.float32, meter: Optional[CommMeter] = None,
                       with_decay: bool = False, reducer_factory=None,
                       topic_shards: Optional[int] = None):
    """Per-shard POBP body for ``shard_map`` on a production mesh: documents
    sharded over the data (and pod) axes, topics over the 'model' axis.

    Shared by ``launch.dryrun.run_lda_cell`` (compile-only HLO analysis) and
    ``launch.lda_train`` (--backend shard_map), so the production cell and
    the streaming driver cannot fork.  Returns (local_fn, meter) with
    ``local_fn(wid, cnt, phi_acc, key, delta_weight) ->
    (phi_acc_new, iters, mean_r)``; ``with_decay=True`` (a decayed run,
    cfg.decay_kappa > 0) appends a trailing RM-retention scalar argument —
    the arity is static so the undecayed program stays byte-identical.

    ``reducer_factory(axis_name, meter, sync_dtype) -> Reducer`` replaces
    the default ``MeshReducer`` for the DATA reducer (the vocabulary-row
    sync the parameter-server mode reroutes); the model-axis reducer is
    always a plain mesh psum — topic shards of one worker live on one
    host and never cross the PS wire.  ``topic_shards=1`` (a 'model' axis
    of one device) keeps the topic axis local instead: a `LocalReducer`,
    so the fused Pallas dense sweep runs as it does on one chip.
    """
    dp = tuple(a for a in mesh_axis_names if a in ("pod", "data"))
    meter = meter or CommMeter()

    def run(wid, cnt, phi_acc, key, delta_weight, decay):
        if reducer_factory is not None:
            data_red = reducer_factory(dp, meter, sync_dtype)
        else:
            data_red = MeshReducer(dp, meter=meter, sync_dtype=sync_dtype)
        if topic_shards == 1:
            model_red = LocalReducer(meter=meter, sync_dtype=sync_dtype)
        else:
            model_red = MeshReducer("model", meter=meter,
                                    sync_dtype=sync_dtype)
        phi, iters, mean_r, _mu, _theta = pobp_shard_body(
            wid, cnt, phi_acc, key, delta_weight, cfg, data_red, model_red,
            sync_mode=sync_mode, decay=decay)
        return phi, iters, mean_r

    if with_decay:
        local = run
    else:
        def local(wid, cnt, phi_acc, key, delta_weight):
            return run(wid, cnt, phi_acc, key, delta_weight, None)

    return local, meter


def shard_map_minibatch_fn(cfg: LDAConfig, mesh, sync_mode: str = "power",
                           sync_dtype=jnp.float32,
                           meter: Optional[CommMeter] = None,
                           with_decay: bool = False):
    """`make_mesh_shard_fn` wrapped in shard_map on `mesh`, partition specs
    included: fn(wid[D, L], cnt[D, L], phi_acc[W, K], key, delta_weight)
    -> (phi_acc_new, iters, mean_r) with documents split over data/pod and
    topics over 'model'.  The ONE wrapper both `launch.dryrun.run_lda_cell`
    (lower/compile) and `launch.lda_train` (execute) use — specs cannot
    fork between the compile-only cell and the production driver.
    ``with_decay=True`` appends the replicated RM-retention scalar (§14).
    Returns (fn, meter).
    """
    from jax.sharding import PartitionSpec as P

    local, meter = make_mesh_shard_fn(cfg, mesh.axis_names, sync_mode,
                                      sync_dtype, meter,
                                      with_decay=with_decay,
                                      topic_shards=mesh.shape["model"])
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    in_specs = (P(dp, None), P(dp, None), P(None, "model"), P(), P())
    if with_decay:
        in_specs += (P(),)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=in_specs,
                       out_specs=(P(None, "model"), P(), P()),
                       check_vma=False)
    return fn, meter


class DiagBuffer:
    """Buffers per-batch device scalars and materializes them to host
    values in blocks: dispatch stays asynchronous (a flushed value is many
    batches old, its compute long finished) while the set of live device
    buffers stays bounded on an unbounded stream.  Shared by `run_stream`
    and `launch.lda_train`."""

    def __init__(self, block: int = 64):
        self.block = max(int(block), 1)
        self._pending: list = []
        self._done: list = []

    def append(self, *vals) -> None:
        self._pending.append(vals)
        if len(self._pending) >= self.block:
            self.flush()

    def flush(self) -> None:
        import numpy as np
        self._done.extend(
            tuple(np.asarray(v).reshape(-1)[0] for v in vals)
            for vals in self._pending)
        self._pending.clear()

    def rows(self) -> list:
        self.flush()
        return self._done


def run_stream(
    stream,
    cfg: LDAConfig,
    num_shards: int = 1,
    sync_mode: str = "power",
    seed: int = 0,
    sync_dtype=jnp.float32,
    callback=None,
    state: Optional[LDATrainState] = None,
    donate: bool = True,
):
    """OBP/POBP outer loop over a mini-batch stream (Fig. 4 outer `for m`),
    built on the donated-carry `make_train_step`.

    `stream` yields either MiniBatch (N=1) or [N, Dl, L] stacked arrays.
    Dispatch is asynchronous: nothing forces a host sync per mini-batch —
    history diagnostics are materialized once, after the loop.  `callback`
    (if given) receives ``(m, phi_acc, rec, theta)`` with *device* scalars
    in `rec`; convert them only as often as a sync is affordable.  Because
    the carry is donated, the phi_acc handed to the callback is only valid
    until the next step runs — ``np.asarray`` it if it must outlive that
    (checkpointing does exactly this).  Pass `state` to continue a run.
    Returns (phi_acc[W, K], history list of per-batch dicts, meter).
    """
    step, meter = make_train_step(cfg, num_shards, sync_mode, sync_dtype,
                                  donate=donate)
    if state is None:
        state = init_train_state(cfg, seed)
    buf = DiagBuffer()
    for m, batch in enumerate(stream, start=int(state.m) + 1):
        state, diag = step(state, batch.word_ids, batch.counts)
        buf.append(m, diag["iters"], diag["mean_r"])
        if callback is not None:
            callback(m, state.phi_acc,
                     dict(m=m, iters=diag["iters"], mean_r=diag["mean_r"]),
                     diag["theta"])
    history = [dict(m=int(m), iters=int(it), mean_r=float(r))
               for m, it, r in buf.rows()]
    return state.phi_acc, history, meter
