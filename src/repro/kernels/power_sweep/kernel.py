"""Fused selective power-sweep kernels (Fig. 4 lines 15-21, token-major).

Three kernels share this package:

  - ``power_sweep_tokens`` — the packed-stream kernel: pre-gathered
    [T, Pk] tiles in, updated [T, Pk] tiles + packed [P1, Pk] buffers out
    (the caller folds the tiles back into the carry);
  - ``power_sweep_carry_tokens`` — the carry-resident megakernel: the
    full [TT, K] mu carry tile loads into VMEM, the packed-phi/mask row
    gathers, the selective update + mass-conserving renorm, the fold-back,
    the per-doc theta delta and the [P1, K] delta/residual accumulation
    all happen in that one grid pass (one HBM read + one write of the
    carry per iteration; every gather/scatter is an MXU one-hot
    contraction).  A static ``update_phi=False`` turns the same kernel
    into the serving fold-in body (core/infer): phi is a normalized
    constant streamed per token as [TT, K] tiles (a whole-vocabulary row
    table never fits VMEM), there is no self-count subtraction and no
    packed output, and the per-doc |delta| residual accumulates instead.
  - ``power_sweep_carry_kblocked_tokens`` — the K-blocked megakernel
    (DESIGN.md §13): the same carry-resident math tiled as [TT, KB]
    topic blocks over a 2D grid, so the token tile no longer shrinks
    with K.  The mass-conserving renormalization needs complete per-token
    row sums over ALL of K before any mu can be rewritten, and a Pallas
    output block may only be revisited on consecutive grid steps — so the
    sweep runs as two pallas_calls: a **sums pass** with K blocks
    innermost (per-token mass/denominator accumulators stay grid-resident
    at [TT, 1]) and an **update pass** with token tiles innermost (the
    per-K-block table accumulators stay grid-resident at [rows, KB]).
    The update pass recomputes the u block instead of staging a [T, K]
    temporary — the gathers run twice, trading MXU flops for the VMEM/HBM
    a staged u would cost.  One K block covering all of K routes straight
    back to the one-pass megakernel: the full-K kernel is the NKB == 1
    specialization of this path.

One packed-stream grid pass performs, entirely in VMEM:

  1. the per-token gather of the packed phi power rows — the tile's
     power-row ids ``p_tok`` (a [TT, 1] int32 VMEM block — no index map
     reads them, so they need no scalar prefetch) select rows of the
     VMEM-resident ``phi_pack [P1, Pk]`` through an MXU one-hot contraction
     (TPU Pallas has no dynamic vector gather);
  2. the selective message update + mass-conserving renormalization
     (Eq. 1 restricted to the power submatrix, DESIGN.md §2):
         u   = (theta_sel - c*mu + alpha)(phi_sel - c*mu + beta)
               / (pt_sel - c*mu + W*beta)
         mu' = u * mass / sum_j u        on power tokens, mu otherwise;
  3. the packed delta/residual scatter: ``onehot^T @ (c*d)`` accumulates
     straight into the [P1, Pk] sync buffers, which live in VMEM across the
     whole grid (their BlockSpec index is constant) and are written back to
     HBM once — the token loop never touches a [W, K] or [T, K] temporary.

Non-power and padding tokens carry ``p_tok == n_pow`` (the guard row):
their mask keeps mu unchanged, so their deltas are exactly zero and the
guard row accumulates nothing but zeros.

Layout contract (ops.py): Pk padded to 128 lanes with theta padded to
-alpha (=> u == 0 on pad columns), T padded to a tile multiple with zero
counts, packed rows padded to a sublane multiple with zero phi rows.

VMEM: every pallas_call runs with the shared budget as its Mosaic VMEM
limit (`repro.kernels.vmem_budget`), and the tile choosers below plan
double-buffered blocks, lane-padded [TT, 1] blocks, the one-hot operands
and the main temporaries against that same number.  A shape whose
footprint at the minimum tile exceeds the budget raises here; the
dispatch (`core.sweep_dispatch`) routes such shapes to the XLA
formulation before a kernel is ever traced.  All one-hot contractions
run at HIGHEST precision: they are gathers and scatters, exact only in
full f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels as K_
from repro.kernels import DEFAULT_VMEM_BUDGET, vmem_budget  # noqa: F401

_HI = jax.lax.Precision.HIGHEST
_ROWS = (((1,), (0,)), ((), ()))      # onehot[TT, R] @ table[R, k]: gather
_ACC = (((0,), (0,)), ((), ()))       # onehot[TT, R]^T @ vals[TT, k]: scatter


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _pow2_tile(fixed_bytes: int, per_token_bytes: int, budget: int) -> int:
    """Largest power-of-two TT in [8, 512] fitting the VMEM budget.

    ``fixed_bytes`` is the grid-resident footprint (tables/accumulators
    whose BlockSpec index is constant), ``per_token_bytes`` the marginal
    cost of one carry row.  Power of two so `fit_token_tile`'s halving
    always lands on a full sublane-aligned tile; floors at 8 — the kernel
    entry points check the floor tile against the budget (`_check_fit`).
    """
    tt = max(8, min(512, max(0, budget - fixed_bytes) // per_token_bytes))
    return 1 << (tt.bit_length() - 1)


def _check_fit(what: str, fixed: int, per_token: int, tt: int,
               budget: int) -> None:
    need = fixed + tt * per_token
    if need > budget:
        raise ValueError(
            f"{what} needs {need:,} B of VMEM at token tile {tt}, over the "
            f"{budget:,} B budget; core.sweep_dispatch routes such shapes "
            f"to the XLA formulation")


def fit_token_tile(n_tokens: int, tt: int) -> int:
    """Shrink TT (power of two) until it divides T, clamped at the floor
    of 8.  T not divisible by 8 is a caller bug — the grid would silently
    drop the trailing tokens — so it raises instead of degenerating to
    TT < 8 (ops.py always pads T to a multiple of 8).
    """
    while n_tokens % tt and tt > 8:
        tt //= 2
    if n_tokens % tt:
        raise ValueError(
            f"token count {n_tokens} is not a multiple of the minimum "
            f"tile 8; pad T before calling (see ops.py padding contract)")
    return tt


def _kernel(p_ref, c_ref, mu_ref, th_ref, pt_ref, phi_ref,
            mu_out_ref, d_out_ref, r_out_ref, *,
            alpha: float, beta: float, wbeta: float, n_pow: int):
    i = pl.program_id(0)
    p_tile = p_ref[...]                                        # [TT, 1] int32
    tt = p_tile.shape[0]
    n_rows = phi_ref.shape[0]
    iota_p = jax.lax.broadcasted_iota(jnp.int32, (tt, n_rows), 1)
    onehot = (iota_p == p_tile).astype(jnp.float32)            # [TT, P1]

    c = c_ref[...]                                             # [TT, 1]
    mu = mu_ref[...]                                           # [TT, Pk]
    phi_sel = _dot(onehot, phi_ref[...], _ROWS)                # MXU row gather

    self_c = c * mu
    th = th_ref[...] - self_c + alpha
    ph = phi_sel - self_c + beta
    pt = pt_ref[...] - self_c + wbeta
    u = th * ph / pt
    mass = jnp.sum(mu, axis=-1, keepdims=True)                 # conserved mass
    denom = jnp.maximum(jnp.sum(u, axis=-1, keepdims=True), 1e-30)
    mu_new = u * mass / denom
    mu_new = jnp.where(p_tile < n_pow, mu_new, mu)

    d_mu = mu_new - mu
    mu_out_ref[...] = mu_new

    @pl.when(i == 0)
    def _init():
        d_out_ref[...] = jnp.zeros_like(d_out_ref)
        r_out_ref[...] = jnp.zeros_like(r_out_ref)

    # packed scatter: guard row n_pow only ever receives exact zeros
    d_out_ref[...] += _dot(onehot, c * d_mu, _ACC)             # [P1, Pk]
    r_out_ref[...] += _dot(onehot, c * jnp.abs(d_mu), _ACC)


def _packed_footprint(pk_width: int, n_rows: int):
    """(fixed, per_token) VMEM bytes of the packed kernel: the phi/delta/
    residual [P1, Pk] tables, four copies each as in `_carry_footprint`;
    the five [TT, Pk] plus two lane-padded [TT, 1] blocks,
    double-buffered; ~8 [TT, Pk] temporaries and the [TT, P1] one-hot
    (counted twice for its transposed use)."""
    fixed = 4 * 3 * n_rows * pk_width * 4
    per_token = (2 * (5 * pk_width * 4 + 2 * K_.LANE_ROW_BYTES)
                 + (8 * pk_width + 2 * n_rows) * 4)
    return fixed, per_token


def token_tile(pk_width: int, n_rows: int,
               vmem_budget_bytes=None) -> int:
    """Packed-stream tile under the shared budget (`vmem_budget`)."""
    fixed, per_token = _packed_footprint(pk_width, n_rows)
    return _pow2_tile(fixed, per_token, vmem_budget(vmem_budget_bytes))


@functools.partial(jax.jit,
                   static_argnames=("alpha", "beta", "wbeta", "n_pow"))
def power_sweep_tokens(p_tok: jnp.ndarray, counts_t: jnp.ndarray,
                       mu_sel: jnp.ndarray, theta_sel: jnp.ndarray,
                       pt_sel: jnp.ndarray, phi_pack: jnp.ndarray, *,
                       alpha: float, beta: float, wbeta: float, n_pow: int):
    """Fused selective update over pre-gathered [T, Pk] token tiles.

    p_tok [T] int32 power-row id per token (n_pow => not selected);
    counts_t [T, 1]; mu_sel/theta_sel/pt_sel [T, Pk]; phi_pack [P1, Pk]
    with P1 > n_pow.  T % TT == 0, Pk % 128 == 0 and P1 % 8 == 0 are the
    caller's (ops.py) responsibility.
    Returns (mu_new_sel [T, Pk], d_pack [P1, Pk], r_pack [P1, Pk]).
    """
    T, Pk = mu_sel.shape
    P1 = phi_pack.shape[0]
    budget = vmem_budget()
    fixed, per_token = _packed_footprint(Pk, P1)
    TT = fit_token_tile(T, _pow2_tile(fixed, per_token, budget))
    _check_fit("power_sweep_tokens", fixed, per_token, TT, budget)
    spec_tk = pl.BlockSpec((TT, Pk), lambda i: (i, 0))
    spec_col = pl.BlockSpec((TT, 1), lambda i: (i, 0))
    spec_pack = pl.BlockSpec((P1, Pk), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, beta=beta, wbeta=wbeta,
                          n_pow=n_pow),
        grid=(T // TT,),
        in_specs=[spec_col, spec_col, spec_tk, spec_tk, spec_tk, spec_pack],
        out_specs=[spec_tk, spec_pack, spec_pack],
        out_shape=[jax.ShapeDtypeStruct((T, Pk), jnp.float32),
                   jax.ShapeDtypeStruct((P1, Pk), jnp.float32),
                   jax.ShapeDtypeStruct((P1, Pk), jnp.float32)],
        compiler_params=K_.compiler_params(budget),
        interpret=K_.INTERPRET,
    )(p_tok.reshape(T, 1), counts_t, mu_sel, theta_sel, pt_sel, phi_pack)


# --------------------------------------------------------------------------
# carry-resident megakernel (dense-layout formulation, DESIGN.md §2)
# --------------------------------------------------------------------------


def _block_terms(p_tile, d_tile, c, mu, theta_ref, pt_ref, phi_ref,
                 mask_ref, *, alpha: float, beta: float, wbeta: float,
                 update_phi: bool, n_guard: int):
    """One [TT, KB] block of the selective update, shared by the full-K
    carry kernel (KB == K) and both passes of the K-blocked pair.

    Gathers the block's theta rows (and, when training, the phi/mask
    rows) through MXU one-hot contractions and returns (u, m_tok,
    onehot_p, onehot_d) — the unnormalized message u = th*ph/pt masked by
    the token's topic selection.  The renormalization (mass / sum u) is
    the caller's job: it needs the complete row sum over all of K, which
    a K block cannot see.
    """
    tt = mu.shape[0]
    n_docs = theta_ref.shape[0]                                # D  (padded)
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (tt, n_docs), 1)
    onehot_d = (iota_d == d_tile).astype(jnp.float32)          # [TT, D]
    theta_tok = _dot(onehot_d, theta_ref[...], _ROWS)          # [TT, KB]

    self_c = c * mu
    th = theta_tok - self_c + alpha
    if update_phi:
        n_rows = phi_ref.shape[0]                              # P1 (padded)
        iota_p = jax.lax.broadcasted_iota(jnp.int32, (tt, n_rows), 1)
        onehot_p = (iota_p == p_tile).astype(jnp.float32)      # [TT, P1]
        m_tok = _dot(onehot_p, mask_ref[...], _ROWS)           # [TT, KB]
        ph = _dot(onehot_p, phi_ref[...], _ROWS) - self_c + beta
        pt = pt_ref[...] - self_c + wbeta
    else:
        # serving fold-in: phi_ref is this tile's pre-gathered [TT, KB]
        # phi (a fixed normalized constant — the caller passes beta = 0,
        # keeping the K lane padding at u == 0 exactly); every live row
        # selects ALL topics, so the mask is one guard compare per token
        # (mask_ref is a dummy), and the denominator trick (pt_ref = 0,
        # wbeta = 1) makes pt exactly 1
        onehot_p = None
        m_tok = (p_tile != n_guard).astype(jnp.float32)        # [TT, 1]
        ph = phi_ref[...] + beta
        pt = pt_ref[...] + wbeta                               # [1, KB] bcast
    u = th * ph / pt * m_tok
    return u, m_tok, onehot_p, onehot_d


def _accumulate(i, c, mu, mu_new, onehot_p, onehot_d, th_out_ref,
                d_out_ref, r_out_ref, rd_out_ref, update_phi: bool):
    """Fold-back deltas into the grid-resident accumulators (zeroed on the
    first token tile): per-doc theta delta, and the packed delta/residual
    rows (training) or the per-doc |delta| residual (serving)."""
    cd = c * (mu_new - mu)

    @pl.when(i == 0)
    def _init():
        th_out_ref[...] = jnp.zeros_like(th_out_ref)
        d_out_ref[...] = jnp.zeros_like(d_out_ref)
        r_out_ref[...] = jnp.zeros_like(r_out_ref)
        rd_out_ref[...] = jnp.zeros_like(rd_out_ref)

    th_out_ref[...] += _dot(onehot_d, cd, _ACC)                # theta delta
    if update_phi:
        d_out_ref[...] += _dot(onehot_p, cd, _ACC)
        r_out_ref[...] += _dot(onehot_p, jnp.abs(cd), _ACC)
    else:
        rd_out_ref[...] += _dot(onehot_d, jnp.abs(cd), _ACC)   # doc residual


def _carry_kernel(p_ref, doc_ref, c_ref, mu_ref, theta_ref, pt_ref,
                  phi_ref, mask_ref,
                  mu_out_ref, th_out_ref, d_out_ref, r_out_ref, rd_out_ref,
                  *, alpha: float, beta: float, wbeta: float,
                  update_phi: bool, n_guard: int):
    i = pl.program_id(0)
    c = c_ref[...]                                             # [TT, 1]
    mu = mu_ref[...]                                           # [TT, K]
    u, m_tok, onehot_p, onehot_d = _block_terms(
        p_ref[...], doc_ref[...], c, mu, theta_ref, pt_ref, phi_ref,
        mask_ref, alpha=alpha, beta=beta, wbeta=wbeta,
        update_phi=update_phi, n_guard=n_guard)
    mass = jnp.sum(mu * m_tok, axis=-1, keepdims=True)         # conserved
    denom = jnp.maximum(jnp.sum(u, axis=-1, keepdims=True), 1e-30)
    mu_new = jnp.where(m_tok > 0, u * (mass / denom), mu)
    mu_out_ref[...] = mu_new                                   # fold-back
    _accumulate(i, c, mu, mu_new, onehot_p, onehot_d, th_out_ref,
                d_out_ref, r_out_ref, rd_out_ref, update_phi)


def _carry_footprint(k_width: int, n_rows: int, n_docs: int,
                     update_phi: bool = True):
    """(fixed, per_token) VMEM bytes of the carry kernel at block width
    ``k_width``, double-buffering included.

    Grid-resident: training holds the phi/mask tables and the d/r
    accumulators at [P1, k]; both modes hold theta in/out and the doc
    residual at [D, k] (8-row dummies where a mode does not use one) and
    the [1 -> 8, k] phi_tot row.  Each table counts four times: two
    pipeline buffers plus the operand splits and the result copy of its
    HIGHEST-precision MXU contraction — the factor that bounds what
    Mosaic allocated in described-v5e compiles of the K-blocked kernel
    (up to 1.8x the double-buffered size; the full-K kernel needs less).
    Per token: the mu in/out blocks (plus the streamed phi block when
    serving), five lane-padded [TT, 1] blocks (ids, docs, counts, and the
    K-blocked pass's mass/denominator), ~10 [TT, k] temporaries, and the
    one-hot operands (counted twice for the transposed accumulation).
    """
    if update_phi:
        rows = 4 * n_rows + 2 * n_docs + 8 + 8
        tok_blocks, onehot = 2, n_rows + n_docs
    else:
        rows = 3 * 8 + 3 * n_docs + 8
        tok_blocks, onehot = 3, n_docs
    fixed = 4 * rows * k_width * 4
    per_token = (2 * (tok_blocks * k_width * 4 + 5 * K_.LANE_ROW_BYTES)
                 + (10 * k_width + 2 * onehot) * 4)
    return fixed, per_token


def carry_token_tile(k_width: int, n_rows: int, n_docs: int,
                     vmem_budget_bytes=None, update_phi: bool = True) -> int:
    """Carry-kernel tile at block width ``k_width`` (the full K for the
    one-pass megakernel, KB for the K-blocked pair).  Same power-of-two /
    floor-at-8 contract as `token_tile`; budget via `vmem_budget`."""
    fixed, per_token = _carry_footprint(k_width, n_rows, n_docs, update_phi)
    return _pow2_tile(fixed, per_token, vmem_budget(vmem_budget_bytes))


def carry_vmem_fits(k_width: int, n_rows: int, n_docs: int,
                    vmem_budget_bytes=None, min_tile: int = 64,
                    update_phi: bool = True) -> bool:
    """Does the carry kernel fit the VMEM budget at block width
    ``k_width`` with a usefully large token tile?

    "Fits" means the fixed tables plus ``min_tile`` carry rows stay
    inside the budget — a tile below ~64 re-fetches the grid-resident
    tables so often the kernel loses to the K-blocked path anyway.  This
    is the dispatch-side predicate `core.sweep_dispatch` uses to pick
    full-K vs kblocked vs the XLA formulation.
    """
    fixed, per_token = _carry_footprint(k_width, n_rows, n_docs, update_phi)
    return fixed + min_tile * per_token <= vmem_budget(vmem_budget_bytes)


def kblock_width(k_width: int, n_rows: int, n_docs: int,
                 vmem_budget_bytes=None, update_phi: bool = True) -> int:
    """Topic-block width KB for the K-blocked sweep: the largest of
    (512, 256, 128) dividing K whose carry footprint passes
    `carry_vmem_fits`, else the smallest divisor (the kernel entry then
    refuses the shape if even its floor tile busts the budget).
    K must be lane-padded (multiple of 128) so 128 always divides.
    """
    if k_width % 128:
        raise ValueError(f"kblock_width needs K padded to 128, got {k_width}")
    cands = [d for d in (512, 256, 128) if k_width % d == 0]
    for d in cands:
        if carry_vmem_fits(d, n_rows, n_docs, vmem_budget_bytes,
                           update_phi=update_phi):
            return d
    return cands[-1]


def _carry_specs(update_phi: bool, tt: int, kb: int, n_rows: int,
                 n_mask: int, n_docs: int, tok, tab):
    """BlockSpecs shared by the one-pass kernel and the K-blocked update
    pass.  ``tok(i, j)``/``tab(j)`` turn the grid indices into the token
    block index and the topic block index, so one table serves both grid
    orders."""
    n_dr = n_rows if update_phi else 8
    n_rd = 8 if update_phi else n_docs
    col = pl.BlockSpec((tt, 1), lambda *g: (tok(*g), 0))
    tk = pl.BlockSpec((tt, kb), lambda *g: (tok(*g), tab(*g)))
    rows = lambda r: pl.BlockSpec((r, kb), lambda *g: (0, tab(*g)))  # noqa: E731
    phi = tk if not update_phi else rows(n_rows)
    ins = dict(col=col, tk=tk, docs=rows(n_docs), pt=rows(1), phi=phi,
               mask=rows(n_mask))
    outs = [tk, rows(n_docs), rows(n_dr), rows(n_dr), rows(n_rd)]
    return ins, outs, n_dr, n_rd


@functools.partial(jax.jit,
                   static_argnames=("alpha", "beta", "wbeta", "update_phi",
                                    "n_guard", "vmem_budget_bytes"))
def power_sweep_carry_tokens(p_tok: jnp.ndarray, doc_ids: jnp.ndarray,
                             counts_t: jnp.ndarray, mu_t: jnp.ndarray,
                             theta: jnp.ndarray, pt_row: jnp.ndarray,
                             phi_rows: jnp.ndarray, mask_rows: jnp.ndarray,
                             *, alpha: float, beta: float, wbeta: float,
                             update_phi: bool = True, n_guard: int = -1,
                             vmem_budget_bytes=None):
    """Carry-resident selective sweep over the full [T, K] carry.

    p_tok [T] int32 power-row id per token (rows with an all-zero mask —
    the guard row and padding — leave the token untouched); doc_ids [T]
    int32; counts_t [T, 1]; mu_t [T, K]; theta [D, K]; pt_row [1, K]
    (phi_tot, the update denominator); phi_rows/mask_rows [P1, K].
    T % TT == 0, K % 128 == 0, P1 % 8 == 0 and D % 8 == 0 are the
    caller's (ops.py) responsibility.
    Returns (mu_new [T, K], theta_delta [D, K], d_rows, r_rows, rdoc_rows).

    On the serving path ``update_phi=False`` phi_rows is the per-token
    phi [T, K] (gathered by ops.py) and the selection collapses to "every
    token not routed to the static ``n_guard`` row selects all topics";
    ``mask_rows`` may be a dummy.  Mode-dead accumulators shrink to an
    (8, K) dummy so they cost no HBM on the hot path: d_rows/r_rows are
    [P1, K] only when ``update_phi`` (else (8, K) of zeros), rdoc_rows is
    [D, K] only when not (else (8, K) of zeros).
    """
    if not update_phi and n_guard < 0:
        raise ValueError("update_phi=False requires the static n_guard "
                         "(logical guard-row id) for the mask compare")
    T, K = mu_t.shape
    P1 = phi_rows.shape[0] if update_phi else 0
    D = theta.shape[0]
    n_mask = mask_rows.shape[0]
    budget = vmem_budget(vmem_budget_bytes)
    fixed, per_token = _carry_footprint(K, P1, D, update_phi)
    TT = fit_token_tile(T, _pow2_tile(fixed, per_token, budget))
    _check_fit("power_sweep_carry_tokens", fixed, per_token, TT, budget)
    ins, outs, n_dr, n_rd = _carry_specs(
        update_phi, TT, K, P1, n_mask, D,
        tok=lambda i: i, tab=lambda i: 0)
    return pl.pallas_call(
        functools.partial(_carry_kernel, alpha=alpha, beta=beta,
                          wbeta=wbeta, update_phi=update_phi,
                          n_guard=n_guard),
        grid=(T // TT,),
        in_specs=[ins["col"], ins["col"], ins["col"], ins["tk"],
                  ins["docs"], ins["pt"], ins["phi"], ins["mask"]],
        out_specs=outs,
        out_shape=[jax.ShapeDtypeStruct((T, K), jnp.float32),
                   jax.ShapeDtypeStruct((D, K), jnp.float32),
                   jax.ShapeDtypeStruct((n_dr, K), jnp.float32),
                   jax.ShapeDtypeStruct((n_dr, K), jnp.float32),
                   jax.ShapeDtypeStruct((n_rd, K), jnp.float32)],
        compiler_params=K_.compiler_params(budget),
        interpret=K_.INTERPRET,
    )(p_tok.reshape(T, 1), doc_ids.reshape(T, 1), counts_t, mu_t, theta,
      pt_row, phi_rows, mask_rows)


# --------------------------------------------------------------------------
# K-blocked carry megakernel (ultra-high-K formulation, DESIGN.md §13)
# --------------------------------------------------------------------------


def _carry_sums_kernel(p_ref, doc_ref, c_ref, mu_ref, theta_ref, pt_ref,
                       phi_ref, mask_ref, mass_ref, denom_ref, *,
                       alpha: float, beta: float, wbeta: float,
                       update_phi: bool, n_guard: int):
    """Pass 1 of the K-blocked sweep: complete the per-token row sums.

    Grid (T//TT, NKB) with K blocks innermost, so the [TT, 1] mass and
    denominator outputs are revisited only on consecutive steps (the
    Pallas output-revisit rule) and stay grid-resident while the token
    tile's K blocks stream through VMEM.
    """
    j = pl.program_id(1)
    mu = mu_ref[...]                                           # [TT, KB]
    u, m_tok, _, _ = _block_terms(
        p_ref[...], doc_ref[...], c_ref[...], mu, theta_ref, pt_ref,
        phi_ref, mask_ref, alpha=alpha, beta=beta, wbeta=wbeta,
        update_phi=update_phi, n_guard=n_guard)

    @pl.when(j == 0)
    def _init():
        mass_ref[...] = jnp.zeros_like(mass_ref)
        denom_ref[...] = jnp.zeros_like(denom_ref)

    mass_ref[...] += jnp.sum(mu * m_tok, axis=-1, keepdims=True)
    denom_ref[...] += jnp.sum(u, axis=-1, keepdims=True)


def _carry_update_kernel(p_ref, doc_ref, c_ref, mass_ref, denom_ref,
                         mu_ref, theta_ref, pt_ref, phi_ref, mask_ref,
                         mu_out_ref, th_out_ref, d_out_ref, r_out_ref,
                         rd_out_ref, *, alpha: float, beta: float,
                         wbeta: float, update_phi: bool, n_guard: int):
    """Pass 2 of the K-blocked sweep: renormalize, fold back, accumulate.

    Grid (NKB, T//TT) with token tiles innermost, so each K block's
    [rows, KB] table accumulators (theta delta, packed d/r, doc residual)
    stay grid-resident across the whole token stream and are written to
    HBM once per block.  u is recomputed from the same inputs as pass 1 —
    the gathers run twice, which is cheaper than staging a [T, K] u.
    """
    i = pl.program_id(1)                                       # token tile
    c = c_ref[...]                                             # [TT, 1]
    mu = mu_ref[...]                                           # [TT, KB]
    u, m_tok, onehot_p, onehot_d = _block_terms(
        p_ref[...], doc_ref[...], c, mu, theta_ref, pt_ref, phi_ref,
        mask_ref, alpha=alpha, beta=beta, wbeta=wbeta,
        update_phi=update_phi, n_guard=n_guard)
    mass = mass_ref[...]                                       # complete sums
    denom = jnp.maximum(denom_ref[...], 1e-30)
    mu_new = jnp.where(m_tok > 0, u * (mass / denom), mu)
    mu_out_ref[...] = mu_new                                   # fold-back
    _accumulate(i, c, mu, mu_new, onehot_p, onehot_d, th_out_ref,
                d_out_ref, r_out_ref, rd_out_ref, update_phi)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "beta", "wbeta", "update_phi",
                                    "n_guard", "kb", "vmem_budget_bytes"))
def power_sweep_carry_kblocked_tokens(
        p_tok: jnp.ndarray, doc_ids: jnp.ndarray, counts_t: jnp.ndarray,
        mu_t: jnp.ndarray, theta: jnp.ndarray, pt_row: jnp.ndarray,
        phi_rows: jnp.ndarray, mask_rows: jnp.ndarray, *,
        alpha: float, beta: float, wbeta: float, update_phi: bool = True,
        n_guard: int = -1, kb=None, vmem_budget_bytes=None):
    """K-blocked carry-resident sweep: identical contract and outputs as
    `power_sweep_carry_tokens`, with the carry tiled as [TT, KB] topic
    blocks over a 2D grid so TT no longer shrinks with K.

    ``kb`` pins the topic-block width (must divide K); by default
    `kblock_width` picks the largest of (512, 256, 128) whose footprint
    fits the VMEM budget.  A single block covering all of K routes back
    to the one-pass megakernel — the full-K kernel is the NKB == 1
    specialization.  Results differ from full-K only by the summation
    order of the renormalization reductions (float associativity).
    """
    T, K = mu_t.shape
    P1 = phi_rows.shape[0] if update_phi else 0
    D = theta.shape[0]
    n_mask = mask_rows.shape[0]
    KB = int(kb) if kb else kblock_width(K, P1, D, vmem_budget_bytes,
                                         update_phi=update_phi)
    if K % KB:
        raise ValueError(f"kb={KB} must divide the padded K={K}")
    if KB >= K:
        return power_sweep_carry_tokens(
            p_tok, doc_ids, counts_t, mu_t, theta, pt_row, phi_rows,
            mask_rows, alpha=alpha, beta=beta, wbeta=wbeta,
            update_phi=update_phi, n_guard=n_guard,
            vmem_budget_bytes=vmem_budget_bytes)
    if not update_phi and n_guard < 0:
        raise ValueError("update_phi=False requires the static n_guard "
                         "(logical guard-row id) for the mask compare")
    NKB = K // KB
    budget = vmem_budget(vmem_budget_bytes)
    fixed, per_token = _carry_footprint(KB, P1, D, update_phi)
    TT = fit_token_tile(T, _pow2_tile(fixed, per_token, budget))
    _check_fit("power_sweep_carry_kblocked_tokens", fixed, per_token, TT,
               budget)
    body = dict(alpha=alpha, beta=beta, wbeta=wbeta, update_phi=update_phi,
                n_guard=n_guard)
    p2, d2 = p_tok.reshape(T, 1), doc_ids.reshape(T, 1)

    # pass 1 — K blocks innermost: per-token sums stay grid-resident
    s_in, _, _, _ = _carry_specs(update_phi, TT, KB, P1, n_mask, D,
                                 tok=lambda i, j: i, tab=lambda i, j: j)
    mass, denom = pl.pallas_call(
        functools.partial(_carry_sums_kernel, **body),
        grid=(T // TT, NKB),
        in_specs=[s_in["col"], s_in["col"], s_in["col"], s_in["tk"],
                  s_in["docs"], s_in["pt"], s_in["phi"], s_in["mask"]],
        out_specs=[s_in["col"], s_in["col"]],
        out_shape=[jax.ShapeDtypeStruct((T, 1), jnp.float32),
                   jax.ShapeDtypeStruct((T, 1), jnp.float32)],
        compiler_params=K_.compiler_params(budget),
        interpret=K_.INTERPRET,
    )(p2, d2, counts_t, mu_t, theta, pt_row, phi_rows, mask_rows)

    # pass 2 — token tiles innermost: table accumulators stay grid-resident
    u_in, u_out, n_dr, n_rd = _carry_specs(
        update_phi, TT, KB, P1, n_mask, D,
        tok=lambda j, i: i, tab=lambda j, i: j)
    return pl.pallas_call(
        functools.partial(_carry_update_kernel, **body),
        grid=(NKB, T // TT),
        in_specs=[u_in["col"], u_in["col"], u_in["col"], u_in["col"],
                  u_in["col"], u_in["tk"], u_in["docs"], u_in["pt"],
                  u_in["phi"], u_in["mask"]],
        out_specs=u_out,
        out_shape=[jax.ShapeDtypeStruct((T, K), jnp.float32),
                   jax.ShapeDtypeStruct((D, K), jnp.float32),
                   jax.ShapeDtypeStruct((n_dr, K), jnp.float32),
                   jax.ShapeDtypeStruct((n_dr, K), jnp.float32),
                   jax.ShapeDtypeStruct((n_rd, K), jnp.float32)],
        compiler_params=K_.compiler_params(budget),
        interpret=K_.INTERPRET,
    )(p2, d2, counts_t, mass, denom, mu_t, theta, pt_row, phi_rows,
      mask_rows)
