"""The counting functions: lower bounds from shapes and counts."""

import numpy as np
import pytest

from bench import counting


def test_bp_update_counts_each_operand_once():
    flops, nbytes = counting.bp_update(1000, 10, 300, 64)
    assert nbytes == 4 * 64 * (2 * 1000 + 10 + 2 * 300)
    assert flops == counting.DENSE_FLOPS * 1000 * 64


def _padded_minibatch(seed, docs=12, L=24, W=200):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, W, (docs, L))
    cnt = rng.integers(1, 4, (docs, L)).astype(np.float32)
    cnt[rng.random((docs, L)) < 0.3] = 0.0          # padding slots
    return ids, cnt


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bp_update_stays_below_ideal_and_kernel_bytes(seed):
    """An ideal fused sweep reads each real token's message once, each
    document's theta row once, each word's phi row once, and writes each
    message and each word's residual row once; the kernel as built moves
    five [T, K] streams over every slot.  The count is at most either."""
    K = 128
    ids, cnt = _padded_minibatch(seed)
    real = cnt > 0
    nnz = int(real.sum())
    docs = int(real.any(axis=1).sum())
    words = int(np.unique(ids[real]).size)
    _, counted = counting.bp_update(nnz, docs, words, K)
    ideal = 4 * K * (nnz + nnz + docs + words + words)
    kernel = 5 * 4 * ids.size * K
    assert counted <= ideal <= kernel


def test_power_pack_scatter():
    flops, nbytes = counting.power_pack_scatter(10266, 50)
    assert nbytes == 16 * 10266 * 50 and flops == 10266 * 50


@pytest.mark.parametrize("sweeps_per_call", [1, 4])
def test_fold_in_counts_messages_once_per_call(sweeps_per_call):
    """A document active for 10 sweeps over 100 tokens: its messages
    pass through HBM in each call it is active in, however many sweeps
    a call runs; the operations follow the sweeps."""
    tokens, iters = 100.0, 10
    calls = -(-iters // sweeps_per_call)
    flops, nbytes = counting.fold_in(tokens * iters, tokens * calls, 1000)
    assert flops == counting.FOLD_IN_FLOPS * tokens * iters * 1000
    assert nbytes == 2 * 4 * tokens * calls * 1000
    assert nbytes <= 2 * 4 * tokens * iters * 1000


def test_step_flops_grow_with_iterations():
    a = counting.pobp_step_flops(1000, 64, 40, 8, 0)
    b = counting.pobp_step_flops(1000, 64, 40, 8, 3)
    assert a == counting.DENSE_FLOPS * 1000 * 64
    assert b - a == counting.SELECTIVE_FLOPS * 40 * 8 * 3


@pytest.mark.parametrize("flops,nbytes,bound", [(197e12, 1.0, "compute"),
                                                (1.0, 819e9, "memory")])
def test_roofline_share_takes_the_binding_bound(flops, nbytes, bound):
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counting.roofline_share(flops, nbytes, 2.0, peaks) == \
        pytest.approx(50.0)
