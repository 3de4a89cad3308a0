"""CPU rehearsal of each cell's traffic at a tiny size, through the same
runners, the same program entry points and the same references as on
the chip (Pallas kernels in interpret mode)."""

import json
import os

import pytest

import tiny


def test_train_stream_rehearsal():
    run = tiny.run("stream")
    line = json.loads(tiny.dumps(run))
    assert line["correct"], run.checks
    assert run.attempted > 0 and run.failed == 0
    assert line["metrics"]["train_tokens_per_s_chip"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert list(line)[-1] == "checks"
    assert 0 < run.counters["pad_slots"] < run.counters["slots"]


def test_serve_poisson_rehearsal():
    run = tiny.run("poisson")
    line = json.loads(tiny.dumps(run))
    assert line["correct"], run.checks
    assert run.failed == 0 and run.attempted == round(
        tiny.TRAFFIC["poisson"]["rate_docs_per_s"] * run.seconds)
    m = line["metrics"]
    assert 0 < m["serve_latency_p50_ms"]["value"] <= \
        1e3 * run.counters["latency_p95_s"]
    assert run.counters["readings"]["sample"] > 0
    assert run.checks["iters_gap"]["value"] == 0, run.checks


@pytest.mark.skipif(
    "--xla_force_host_platform_device_count=4" not in os.environ.get(
        "XLA_FLAGS", ""),
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")
def test_train_dp4_rehearsal():
    run = tiny.run("stream-dp4", chips=4)
    assert run.correct, run.checks
