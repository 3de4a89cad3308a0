"""The topic-sharded training cell (`bench/train_tp_cell.py`) on four CPU
devices at a tiny size: the generator by topic rows against the one the
other cells use, a whole run against its reference, and the phase
readers on the cell's own step."""

import copy
import os

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

pytestmark = pytest.mark.skipif(
    "--xla_force_host_platform_device_count=4" not in os.environ.get(
        "XLA_FLAGS", ""),
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")

TRAFFIC = {"kind": "train_tp", "mesh": [1, 4], "pool_minibatches": 4}


def _cell():
    config = copy.deepcopy(tiny.CONFIG)
    config.update(num_topics=64, lambda_k_abs=8)
    wl = {"name": "tiny.stream-tp4", "config": "tiny",
          "traffic": "stream-tp4", "chips": 4, "why": "rehearsal"}
    return harness.Cell(wl, config, dict(TRAFFIC),
                        {"loss_gap": 1e-3, "grad_gap": 1e-3,
                         "change_gap": 1e-3}, [], [])


def test_the_generator_by_topic_rows_draws_the_same_corpus():
    import jax

    from bench.generator import Corpus
    from bench.train_tp_cell import TopicShardedCorpus, make_mesh
    config = _cell().config
    one = Corpus(5, config)
    tp = TopicShardedCorpus(5, config, make_mesh(TRAFFIC, jax.devices()))
    np.testing.assert_array_equal(np.asarray(tp.topics),
                                  np.asarray(one.topics))
    lens = one.lengths(32, "train", groups=2)
    for (w1, c1), (w2, c2) in zip(one.docs(lens, "train", 16, 80),
                                  tp.docs(lens, "train", 16, 80)):
        np.testing.assert_array_equal(w1, w2)
        np.testing.assert_array_equal(c1, c2)
    np.testing.assert_array_equal(np.asarray(tp.phi_acc(1e4)),
                                  np.asarray(one.phi_acc(1e4)))


def test_train_tp4_rehearsal():
    run = harness.Run(_cell(), 2**31 + 11, 0.5,
                      device={"platform": "cpu", "kind": "cpu", "count": 4})
    run.compiles = harness.CompileCounter()
    harness.runner_for("train_tp")(run, False, "", control=True)
    assert run.correct, run.checks
    assert run.counters["mesh"] == [1, 4] and run.counters["steps"] >= 1
    assert set(run.e2e) == {"setup_s", "train_tokens_per_s_chip"}
    # the low-precision control reads above the sound run
    bf16 = run.counters["control"]["bfloat16"]
    assert max(bf16.values()) > max(
        run.checks[g]["value"] for g in bf16)


def test_the_phase_readers_on_the_cells_own_step():
    """The cell's step compiled on its mesh, its scoped instructions
    traced one after another: each reader reads its scope's time, and
    nothing under another module's name."""
    from bench.program_trace import _compiled_text
    from bench.trace import Event, Trace
    from bench.train_tp_cell import TP_SCOPES, lower_step
    from repro import obs
    run = harness.Run(_cell(), 0, 1.0)
    text = _compiled_text(lower_step(run), TP_SCOPES)
    ops = obs.hlo_ops(text)
    scopes = obs.op_scopes(text, TP_SCOPES)
    leaves = [n for n, (op, _) in ops.items()
              if op not in ("while", "call", "conditional") and scopes[n]]
    t0 = 1_000
    events = [Event(n, t0 + 10 * i, t0 + 10 * i + 10)
              for i, n in enumerate(leaves)]
    run.trace = Trace.from_events(
        {"/device:TPU:0": events},
        {"/device:TPU:0": [Event("jit_step(1)", t0, t0 + 10 * len(leaves))]},
        [Event("window", 0, 10**12)])
    for metric, scope in (("topic_merge_ms.tp4", "pobp.topic_merge"),
                          ("model_norm_ms.tp4", "pobp.model_norm")):
        n = sum(1 for x in leaves if scopes[x] == scope)
        assert n and harness.load_reader(metric)(run) == pytest.approx(
            10e-6 * n), metric
    run.counters.pop("tp_step_split")
    run.trace.modules = {"/device:TPU:0": [Event("jit_other(1)", t0,
                                                 t0 + 10 * len(leaves))]}
    assert harness.load_reader("topic_merge_ms.tp4")(run) is None
