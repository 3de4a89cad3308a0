"""Operations and bytes that no implementation of a step or kernel can
avoid, computed from shapes and counts.  Every function is a lower
bound, so a share of a roofline or a peak built on it cannot pass 100%
unless the time leaves out part of the work.

Counts are float32 (4 bytes) and count real nonzero tokens (a document's
distinct words), real documents and distinct words, never padding slots
or padded topic lanes, which an implementation may skip.  Bytes count
each operand once per call: an implementation may hold anything else
on chip between its reads.
"""

from __future__ import annotations

F32 = 4
# Eq. 1 per (token, topic): the self-exclusion product and its three
# subtractions, two smoothing adds, the product and quotient, the sum
# and the normalizing division, the residual's difference, magnitude
# and count weight — counted as 12 to stay below any implementation.
DENSE_FLOPS = 12
# the selective update per (power token, power topic): the same update
# restricted to the selected topics, counted as 8
SELECTIVE_FLOPS = 8
# the fold-in per (token, topic, sweep): the self-exclusion, the
# smoothing add, the product, the sum and the division, the theta
# update — counted as 6
FOLD_IN_FLOPS = 6


def bp_update(nnz: float, docs: float, words: float, K: int) -> tuple:
    """One dense t=1 sweep over ``nnz`` real tokens of ``docs`` documents
    with ``words`` distinct words: each token's message read and its new
    message written, each document's theta row and each word's phi row
    read once, each word's [K] residual row written once."""
    return (DENSE_FLOPS * nnz * K,
            F32 * K * (2 * nnz + docs + 2 * words))


def power_pack_scatter(P: int, Pk: int) -> tuple:
    """One scatter-add of the packed [P, Pk] phi delta: reads the values
    and topic ids, reads and writes each target element."""
    return P * Pk, F32 * P * Pk * 4


def fold_in(token_sweeps: float, token_calls: float, K: int) -> tuple:
    """Fold-in work: ``token_sweeps`` (real token, sweep) pairs of
    operations, and each token's [K] message read and written once in
    each of ``token_calls`` (real token, kernel call) pairs — a call may
    run several sweeps with the messages on chip, but they live in HBM
    between calls."""
    return FOLD_IN_FLOPS * token_sweeps * K, 2 * F32 * token_calls * K


def pobp_step_flops(nnz: int, K: int, power_words: int, Pk: int,
                    selective_iters: int) -> float:
    """Operations every POBP implementation does in one minibatch: the
    dense sweep over every real token and topic, and per selective
    iteration at least one update of each (power word, power topic)
    coordinate (every selected word has a token in the minibatch)."""
    return (DENSE_FLOPS * nnz * K
            + SELECTIVE_FLOPS * power_words * Pk * selective_iters)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take (the larger of the compute and
    the memory bound) over the measured time, in percent."""
    least = max(flops / peaks["flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
