"""Core dataclasses for the LDA / POBP stack.

The document-word matrix x[W, D] of the paper is represented in
*padded-CSR* form per mini-batch: each document d owns up to L distinct
word slots; slot l holds a vocabulary index ``word_ids[d, l]`` and a count
``counts[d, l]``.  Padding slots use ``word_ids == 0`` and ``counts == 0``
(zero count makes every padded contribution vanish; alpha/beta smoothing
keeps the message update finite there).

Notation maps 1:1 onto the paper (Table 1):
  D   documents per mini-batch          W   vocabulary size
  K   topics                            L   max distinct words per doc
  mu[D, L, K]        messages (Eq. 1)
  theta_hat[D, K]    doc-topic sufficient statistics (Eq. 2)
  phi_hat[K, W]      topic-word sufficient statistics (Eq. 3)
  r[W, K]            residual matrix (Eqs. 7-9)
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

# precision of every f32 contraction on the main path: the TPU's default
# runs a single bf16 pass, which makes the count-weighted sums (theta,
# the one-hot packs) inexact; HIGHEST keeps them float32 on every backend
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LDAConfig:
    """Static configuration of an LDA/POBP run (hashable; safe to close over jit).

    ``vocab_size`` is the *allocated* W — on a dynamic-vocabulary run it
    is the current capacity-ladder rung W_cap (DESIGN.md §12): phi/r
    buffers are [W_cap, K]-shaped, rows in [live_w, W_cap) are guard rows,
    and the traced live_w that flows through ``core.pobp`` carries the
    actual vocabulary size (smoothing, selection, byte accounting).  On a
    fixed-vocabulary run the two coincide and live_w stays None.
    """

    vocab_size: int                 # W (capacity rung W_cap when dynamic)
    num_topics: int                 # K
    alpha: float = 0.1              # Dirichlet prior on theta (paper: 2/K)
    beta: float = 0.01              # Dirichlet prior on phi   (paper: 0.01)
    # --- power selection (the paper's contribution) ---
    lambda_w: float = 0.1           # ratio of power words   (paper default 0.1)
    lambda_k_abs: int = 50          # number of power topics per word (paper: lambda_K*K = 50)
    # --- convergence / schedule ---
    inner_iters: int = 10           # T_m: max message-passing sweeps per mini-batch
    residual_tol: float = 0.1       # line 26 of Fig. 4: mean residual per token
    # --- online learning rate (Eq. 11); 'paper' => 1/max(m-1, 1) ---
    lr_schedule: str = "paper"      # 'paper' | 'power'
    lr_tau0: float = 1.0            # used by the 'power' schedule (tau0 + m)^-kappa
    lr_kappa: float = 0.9
    # --- Robbins-Monro forgetting on the phi accumulator (DESIGN.md §14) ---
    # The Eq. 11 fold-back becomes
    #     phi_acc <- (1 - rho_m) * phi_acc + delta_weight * Delta_phi,
    # with rho_m = (decay_tau0 + m)^(-decay_kappa) the classic RM step on
    # the historical statistic: stale mass fades (a row that stops
    # receiving tokens decays multiplicatively toward the prior) while the
    # current batch always enters at full weight.  decay_kappa == 0
    # statically disables the term — the fold-back is then the *identical
    # expression* the plain-accumulation path always ran, so kappa=0 runs
    # are bit-exact with the pre-lifecycle trajectory (pinned in
    # tests/test_lifecycle.py).
    decay_tau0: float = 1.0
    decay_kappa: float = 0.0
    # --- communication payload ---
    sync_dtype: str = "float32"     # 'float32' | 'bfloat16' (beyond-paper byte halving)
    # --- compute backend for the dense sweep ---
    impl: str = "jnp"               # 'jnp' | 'pallas' (fused bp_update kernel)
    # --- selective-sweep formulation (DESIGN.md §2 / §13 cost model) ---
    # 'auto' picks per (T, K, Pk, P) from the measured cost model at trace
    # time (on pallas, extended with the VMEM-fit predicate: full-K carry
    # while it fits, kblocked beyond); 'packed' forces the [T, Pk] stream +
    # fold-back chain; 'dense_layout' forces the one-pass [T, K] masked
    # formulation (the jnp mirror of the carry-resident power_sweep
    # megakernel); 'kblocked' forces the K-blocked two-pass carry kernel
    # (ultra-high K; on the jnp impl an alias of dense_layout).  Identical
    # selective math and identical packed Eq. 6 communication any way.
    sweep_policy: str = "auto"  # 'auto'|'packed'|'dense_layout'|'kblocked'
    # VMEM byte budget for the pallas tile choosers and the kblocked
    # dispatch predicate; None resolves REPRO_VMEM_BUDGET_BYTES then the
    # built-in default (kernels/power_sweep/kernel.py).
    vmem_budget_bytes: Optional[int] = None
    # --- compressed phi accumulators (DESIGN.md §13) ---
    # Storage dtype of the streaming phi_acc statistic: 'float32' (exact)
    # or 'bfloat16' (halves accumulator HBM + Eq. 6 phi-delta sync bytes;
    # the Eq. 11 accumulate runs in f32 and folds back with stochastic
    # rounding so small per-batch deltas are not systematically lost).
    phi_acc_dtype: str = "float32"  # 'float32' | 'bfloat16'
    # Crossover for the packed path's [P, Pk] accumulation: one-hot MXU
    # contraction while T*P <= crossover, row-scatter above.  Consumed by
    # the dispatch cost model (core/sweep_dispatch.py).
    onehot_crossover: int = 8_000_000
    # --- shape-bucketed streaming ---
    # When set, the random message init is drawn at [D, init_pad_len, K] and
    # sliced to the batch's L, so phi_acc is invariant to how far L was
    # padded (padding slots carry zero counts and contribute nothing).  The
    # streaming driver sets this to its largest length bucket, making
    # bucketed and unbucketed runs of the same corpus agree.
    init_pad_len: Optional[int] = None

    @property
    def num_power_words(self) -> int:
        return max(1, int(round(self.lambda_w * self.vocab_size)))

    @property
    def num_power_topics(self) -> int:
        return max(1, min(self.lambda_k_abs, self.num_topics))

    def delta_weight(self, m: int) -> float:
        """Weight on the current mini-batch's unnormalized gradient Delta-phi.

        The paper's Eq. (11) writes a 1/(m-1) learning rate, but (as §3.2.1
        notes) parameter estimation is invariant to the scaling of sufficient
        statistics: plain accumulation of the *unnormalized* statistic
        (Fig. 4 line 5, weight 1.0) IS the Robbins-Monro 1/m rate on the
        normalized parameter.  'paper' therefore returns 1.0; 'power' gives
        the OVB-style decaying weight for ablations.
        """
        if self.lr_schedule == "paper":
            return 1.0
        return float((self.lr_tau0 + m) ** (-self.lr_kappa))


@dataclasses.dataclass
class MiniBatch:
    """Padded-CSR mini-batch of documents.

    word_ids: int32[D, L]   vocabulary indices (0 for padding)
    counts:   float32[D, L] word counts        (0 for padding)
    """

    word_ids: jnp.ndarray
    counts: jnp.ndarray

    @property
    def num_docs(self) -> int:
        return self.word_ids.shape[0]

    @property
    def max_len(self) -> int:
        return self.word_ids.shape[1]

    def num_tokens(self) -> jnp.ndarray:
        return jnp.sum(self.counts)

    def token_layout(self) -> "TokenLayout":
        """Flatten to the token-major [T] layout (T = D*L, row-major)."""
        D, L = self.word_ids.shape
        return TokenLayout(
            word_ids=self.word_ids.reshape(-1),
            counts=self.counts.reshape(-1, 1),
            doc_ids=jnp.repeat(jnp.arange(D, dtype=jnp.int32), L),
            num_docs=D, max_len=L)


@dataclasses.dataclass(frozen=True)
class TokenLayout:
    """Token-major view of a padded-CSR mini-batch (DESIGN.md §2).

    The [D, L] slot grid flattens row-major to T = D*L token slots, built
    ONCE per mini-batch and carried through every sweep — per-token state
    (messages mu) lives as [T, K] and per-token metadata as [T] vectors, so
    sweeps are flat streams over tokens with no [D, L, K] reshapes.

    word_ids: int32[T]    vocabulary index per token slot (0 for padding)
    counts:   float32[T,1] count per token slot            (0 for padding)
    doc_ids:  int32[T]    owning document of each slot
    """

    word_ids: jnp.ndarray
    counts: jnp.ndarray
    doc_ids: jnp.ndarray
    num_docs: int
    max_len: int

    @property
    def num_slots(self) -> int:
        return self.num_docs * self.max_len

    def to_batch_major(self, values_tk: jnp.ndarray) -> jnp.ndarray:
        """[T, K] token-major tensor back to the [D, L, K] batch view."""
        return values_tk.reshape(self.num_docs, self.max_len, -1)


@dataclasses.dataclass
class LDAState:
    """Persistent (cross-mini-batch) state of an online run.

    phi_acc[K, W]  accumulated topic-word sufficient statistics (Eq. 11)
    m              1-indexed count of mini-batches consumed so far
    """

    phi_acc: jnp.ndarray
    m: int = 0


@dataclasses.dataclass
class LDATrainState:
    """Device-carried state of the streaming POBP driver (a jax pytree).

    This is the donated carry of ``core.pobp.make_train_step``: it never
    leaves the device between mini-batches (asynchronous dispatch) and is
    the exact payload of a driver checkpoint — phi_acc, the mini-batch
    cursor and the RNG together make a crash-resumed run bit-identical to
    an uninterrupted one.

    phi_acc[W, K]  accumulated topic-word sufficient statistics (Eq. 11);
                   W is the capacity rung on a dynamic-vocabulary run —
                   ``core.pobp.grow_state`` pads it to the next rung
                   (guard rows stay exactly zero, DESIGN.md §12)
    m              int32 scalar: mini-batches consumed so far (0-indexed
                   cursor; batch m+1 is the next one, matching Eq. 11's m)
    rng            PRNG key split once per mini-batch
    """

    phi_acc: jnp.ndarray
    m: jnp.ndarray
    rng: jnp.ndarray


jax.tree_util.register_dataclass(
    LDATrainState, data_fields=("phi_acc", "m", "rng"), meta_fields=())


@dataclasses.dataclass
class SweepStats:
    """Diagnostics from one message-passing sweep."""

    mean_residual: jnp.ndarray            # sum_w r_w / sum tokens (line 26)
    comm_bytes: int                       # bytes all-reduced this sweep (analytic meter)
    selected_words: Optional[jnp.ndarray] = None   # power word indices, if selective
