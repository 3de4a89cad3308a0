"""Two-step power word / power topic selection (paper §3.1, Fig. 2)
and the packed gather/scatter ops that realize sparse synchronization.

Layout convention: every *sync-side* matrix is [W, K] ("wk" layout) —
residual matrix r and phi sufficient statistics alike.  Rows are words,
so power-word selection is a row gather and power-topic selection a
per-row column gather, which is exactly the paper's Fig. 2 picture.

Because selection is computed from the *synchronized* residual (Eq. 9),
every shard computes identical indices — no index traffic is needed,
only the packed [P, Pk] value tensor crosses the interconnect.  This is
the property that makes the paper's scheme XLA/TPU-native (DESIGN.md §2).
Topics sharded over the model axis are the exception: each shard holds
some of every row's columns, so the shards merge their candidates
(`select_power_topics_merged`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def select_power_words(r_w: jnp.ndarray, num_power_words: int) -> jnp.ndarray:
    """Top-`num_power_words` vocabulary indices by total residual (Eq. 10).

    The paper uses a partial sort (Fig. 4 lines 12/27); `lax.top_k` is the
    on-device equivalent.
    """
    _, idx = jax.lax.top_k(r_w, num_power_words)
    return idx.astype(jnp.int32)


def select_power_words_live(r_w: jnp.ndarray, num_power_words: int,
                            live_w: jnp.ndarray,
                            lambda_w: float) -> jnp.ndarray:
    """Live-W-masked power-word selection on a capacity-laddered run.

    ``r_w`` is [W_cap]-shaped; rows in [live_w, W_cap) are guard rows and
    must never be selected, and the *number* of power words must track
    the live vocabulary — ``P_live = max(1, floor(lambda_w * live_w))``
    — so the selection (and therefore the whole trajectory) depends only
    on the live vocabulary, never on which rung W_cap happens to be.
    ``floor`` guarantees ``P_live <= num_power_words`` for every
    ``live_w < W_cap`` (`num_power_words` rounds at capacity).

    The returned vector still has the static shape [num_power_words]:
    slots past P_live point at row ``live_w`` — the first guard row, a
    row no token maps to and whose residual/phi entries are identically
    zero — so the packed buffers they feed transmit exact zeros and every
    downstream scatter is a no-op (the W-axis analogue of the power_sweep
    kernel's guard-row token routing).
    """
    W = r_w.shape[0]
    live_w = jnp.asarray(live_w, jnp.int32)
    masked = jnp.where(jnp.arange(W) < live_w, r_w, -jnp.inf)
    _, idx = jax.lax.top_k(masked, num_power_words)
    p_live = jnp.maximum(
        1, jnp.floor(lambda_w * live_w.astype(jnp.float32))).astype(jnp.int32)
    slot = jnp.arange(num_power_words, dtype=jnp.int32)
    return jnp.where(slot < p_live, idx.astype(jnp.int32), live_w)


def select_power_topics(r_wk: jnp.ndarray, word_idx: jnp.ndarray,
                        num_power_topics: int) -> jnp.ndarray:
    """Per power word, top-`num_power_topics` topic indices (Fig. 4 lines 13/28).

    r_wk: [W, K] synchronized residual matrix over every topic (a
    topic-sharded run merges its shards' candidates instead:
    `select_power_topics_merged`).  Returns [P, Pk] int32.
    """
    rows = jnp.take(r_wk, word_idx, axis=0)          # [P, K]
    _, idx = jax.lax.top_k(rows, num_power_topics)   # [P, Pk]
    return idx.astype(jnp.int32)


def select_power_topics_merged(r_wk: jnp.ndarray, word_idx: jnp.ndarray,
                               num_power_topics: int,
                               model_reducer) -> jnp.ndarray:
    """`select_power_topics` over the whole topic axis when ``r_wk`` is
    this shard's [W, Kl] column block of a topic-sharded residual.

    Each shard takes its local top candidates of every power word's row;
    the shards all-gather the candidates' values and global ids over the
    model axis (in shard order) and take the top `num_power_topics` of
    them.  The gathered list holds equal values in ascending global id
    (``top_k`` puts the lower index first, and shards hold ascending
    column ranges), so the merge picks the same topics in the same order
    as ``top_k`` over the unsharded row, ties included.  Every global
    top-Pk topic is in its own shard's local top-Pk, so nothing is lost.

    Returns [P, Pk] int32 *local* ids: the shard's own members of the
    selection, and the sentinel ``Kl`` (one past its columns) in every
    slot another shard owns.  Scatters drop the sentinel (out of
    bounds); gathers at it must fill (``mode="fill"``).
    """
    Kl = r_wk.shape[1]
    P = word_idx.shape[0]
    rows = jnp.take(r_wk, word_idx, axis=0)                      # [P, Kl]
    vals, idx = jax.lax.top_k(rows, min(num_power_topics, Kl))
    k0 = model_reducer.shard_index() * Kl
    with jax.named_scope("pobp.topic_merge"):
        g_vals = model_reducer.all_gather(vals, "topic_merge")   # [S, P, c]
        g_ids = model_reducer.all_gather(idx.astype(jnp.int32) + k0,
                                         "topic_merge")
        cand_v = jnp.moveaxis(g_vals, 0, 1).reshape(P, -1)
        cand_i = jnp.moveaxis(g_ids, 0, 1).reshape(P, -1)
        _, pos = jax.lax.top_k(cand_v, num_power_topics)
        local = jnp.take_along_axis(cand_i, pos, axis=1) - k0
    return jnp.where((local >= 0) & (local < Kl), local, Kl).astype(jnp.int32)


def word_to_row(word_idx: jnp.ndarray, vocab_size: int) -> jnp.ndarray:
    """Inverse map: word -> its row in the packed buffer, or -1 if not selected."""
    rows = jnp.full((vocab_size,), -1, jnp.int32)
    return rows.at[word_idx].set(jnp.arange(word_idx.shape[0], dtype=jnp.int32))


def token_power_rows(word_ids_t: jnp.ndarray, sel_w: jnp.ndarray,
                     vocab_size: int) -> jnp.ndarray:
    """Token-major power-row map: token -> packed row in [0, P), or P.

    The P "guard" value is what the power_sweep kernel and the packed
    scatters use to drop non-power tokens (DESIGN.md §2) — one [W] scatter
    plus one [T] gather per iteration, never a [T, K] mask.
    """
    P = sel_w.shape[0]
    word_row = word_to_row(sel_w, vocab_size)
    p_tok = jnp.take(word_row, word_ids_t, axis=0)
    return jnp.where(p_tok >= 0, p_tok, P).astype(jnp.int32)


def pack_rows(mat_wk: jnp.ndarray, word_idx: jnp.ndarray,
              topic_idx: jnp.ndarray) -> jnp.ndarray:
    """Gather the [P, Pk] power submatrix out of a [W, K] matrix."""
    rows = jnp.take(mat_wk, word_idx, axis=0)                    # [P, K]
    return jnp.take_along_axis(rows, topic_idx, axis=1)          # [P, Pk]


def scatter_add_rows(mat_wk: jnp.ndarray, word_idx: jnp.ndarray,
                     topic_idx: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """mat[word_idx[p], topic_idx[p, j]] += vals[p, j]  (sync of phi deltas, Eq. 4/15)."""
    return mat_wk.at[word_idx[:, None], topic_idx].add(vals)


def scatter_set_rows(mat_wk: jnp.ndarray, word_idx: jnp.ndarray,
                     topic_idx: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """mat[word_idx[p], topic_idx[p, j]] = vals[p, j]  (residual refresh, Eq. 9)."""
    return mat_wk.at[word_idx[:, None], topic_idx].set(vals)
