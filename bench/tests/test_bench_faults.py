"""A run with the timed path broken underneath comes out not correct,
for each fault a cell can have: a step that returns its state
unchanged, half of the batch left out, the exchange between chips left
out, an answer altered where it is produced, documents retired before
the configuration's stopping rule holds.  The harness's look for a chip
is skipped; everything else is a whole run."""

import os

import pytest

import tiny
from bench import faults


def _wrap_train_step(monkeypatch, fault):
    import repro.core.pobp as pobp
    from repro.core.types import LDATrainState
    make = pobp.make_train_step

    def broken(cfg, *a, **k):
        step, meter = make(cfg, *a, donate=False, **k)

        def s(state, word_ids, counts):
            if fault == "unchanged":
                new, diag = step(state, word_ids, counts)
                return LDATrainState(state.phi_acc, new.m, new.rng), diag
            half = counts.shape[0] // 2
            return step(state, word_ids, counts.at[half:].set(0.0))
        return s, meter
    monkeypatch.setattr(pobp, "make_train_step", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_faults_are_not_correct(monkeypatch, fault):
    _wrap_train_step(monkeypatch, fault)
    run = tiny.run("stream")
    assert not run.correct, run.checks


@pytest.mark.skipif(
    "--xla_force_host_platform_device_count=4" not in os.environ.get(
        "XLA_FLAGS", ""),
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
def test_dp4_without_exchange_is_not_correct(monkeypatch):
    import repro.core.sync as sync
    psum = sync.MeshReducer.psum

    def local_only(self, x, phase, *a, **k):
        if phase in ("dense", "power"):
            return x
        return psum(self, x, phase, *a, **k)
    monkeypatch.setattr(sync.MeshReducer, "psum", local_only)
    run = tiny.run("stream-dp4", chips=4)
    assert not run.correct, run.checks


def test_serve_altered_answer_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    import repro.core.infer as infer
    make = infer.make_slab_step

    def broken(cfg, **k):
        init, step, meter = make(cfg, **k)

        def s(*args):
            state, retired, theta, it, r = step(*args)
            theta = jnp.roll(theta, 1, axis=1)
            return state, retired, theta, it, r
        return init, jax.jit(s), meter
    monkeypatch.setattr(infer, "make_slab_step", broken)
    run = tiny.run("poisson")
    assert not run.correct, run.checks


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_serve_early_retirement_is_not_correct(monkeypatch, fault):
    faults.plant(fault, monkeypatch.setattr)
    run = tiny.run("poisson")
    assert not run.correct, run.checks
    assert run.checks["iters_gap"]["value"] > \
        run.checks["iters_gap"]["limit"], run.checks
