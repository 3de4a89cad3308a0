"""Residual bookkeeping (Eqs. 7-10) and token->matrix scatters.

Residuals drive both convergence detection (Fig. 5: the mean residual
tracks predictive perplexity) and the dynamic power selection (Fig. 3).
"""

from __future__ import annotations

import jax.numpy as jnp


def token_scatter_wk(word_ids: jnp.ndarray, values_dlk: jnp.ndarray,
                     vocab_size: int) -> jnp.ndarray:
    """Scatter per-token [D, L, K] values into a [W, K] matrix by word id.

    Used for Delta-phi (Eq. 3 contribution) and the residual matrix (Eq. 8).
    Padding tokens carry zero values, so word id 0 padding is harmless.
    """
    K = values_dlk.shape[-1]
    flat_w = word_ids.reshape(-1)
    flat_v = values_dlk.reshape(-1, K)
    return jnp.zeros((vocab_size, K), flat_v.dtype).at[flat_w].add(flat_v)


def token_topic_segment_sum(doc_ids: jnp.ndarray, k_tok: jnp.ndarray,
                            vals: jnp.ndarray, num_docs: int,
                            num_topics: int) -> jnp.ndarray:
    """Segment-sum [T, Pk] per-token values into [D, K] at (doc, topic).

    The O(T*Pk) theta refresh of the selective sweep: each token scatters
    its Pk selected-coordinate deltas straight to its document's row —
    never materializing a [T, K] or [D, L, K] intermediate.  This is what
    the carry-resident power_sweep kernel does on the MXU; on CPU XLA the
    element scatter serializes, so the jnp formulations reach theta
    through contractions instead (DESIGN.md §2 cost table) and this
    helper serves as the layout-free oracle for both.
    """
    flat = (doc_ids[:, None] * num_topics + k_tok).reshape(-1)
    out = jnp.zeros((num_docs * num_topics,), vals.dtype).at[flat].add(
        vals.reshape(-1))
    return out.reshape(num_docs, num_topics)


def mean_residual(r_w: jnp.ndarray, total_tokens: jnp.ndarray) -> jnp.ndarray:
    """Line 26 of Fig. 4: sum_w r_w / sum_{w,d} x_{w,d}."""
    return jnp.sum(r_w) / jnp.maximum(total_tokens, 1.0)


def packed_rw_delta(r_glob_wk: jnp.ndarray, sel_w: jnp.ndarray,
                    sel_k: jnp.ndarray, r_pack_new: jnp.ndarray,
                    sentinel: bool = False) -> jnp.ndarray:
    """Per-power-word change of the word residual under a packed refresh.

    The selective iteration only rewrites r at the [P, Pk] power coordinates
    (Eq. 9), so the [W] word-residual vector moves by exactly

        delta[p] = sum_j r_pack_new[p, j] - r_glob[sel_w[p], sel_k[p, j]]

    — an O(P*Pk) update of the convergence signal instead of the seed's
    O(W*K) row reduction per iteration (DESIGN.md §2 packed-carry
    invariant).  Call BEFORE scattering r_pack_new into r_glob.
    Returns delta [P]; the caller adds it at rows sel_w (after the model
    psum when the topic axis is sharded).  ``sentinel``: ``sel_k`` holds
    out-of-range ids for topics another shard owns
    (`power.select_power_topics_merged`); they read 0 and add nothing.
    """
    rows = jnp.take(r_glob_wk, sel_w, axis=0)
    fill = dict(mode="fill", fill_value=0) if sentinel else {}
    old = jnp.take_along_axis(rows, sel_k, axis=1, **fill)
    return jnp.sum(r_pack_new - old, axis=1)
