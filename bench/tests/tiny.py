"""Tiny cells for CPU rehearsals: the same runners and references at
sizes a test run can hold (interpret-mode kernels)."""

from __future__ import annotations

import copy
import json

from bench import harness

CONFIG = {
    "name": "tiny", "vocab_size": 512, "num_topics": 16,
    "lambda_w": 0.1, "lambda_k_abs": 4, "inner_iters": 4,
    "residual_tol": 0.0, "alpha": 0.1, "beta": 0.01, "impl": "pallas",
    "docs_per_batch": 16, "len_bucket": 32, "prior_minibatches": 8,
    "corpus": {"distinct_per_doc": 16.6, "tokens_per_doc": 36.0,
               "doc_len_sigma": 0.5, "zipf_exponent": 1.0,
               "topic_concentration": 5.12, "doc_topic_concentration": 3.0},
}
TRAFFIC = {
    "stream": {"kind": "train", "pool_minibatches": 4},
    "stream-dp4": {"kind": "train", "pool_minibatches": 4,
                   "docs_per_chip": 8},
    "poisson": {"kind": "serve", "arrivals": "poisson",
                "rate_docs_per_s": 200, "slots": 8, "slot_len": 32,
                "sweeps_per_step": 4, "fold_iters": 30, "residual_tol": 0.01,
                "pool_docs": 64, "gen_block": 32,
                "warmup_docs": 8, "check_docs": 16},
}
LIMITS = {"stream": {"loss_gap": 1e-3, "grad_gap": 1e-3, "change_gap": 1e-3},
          "stream-dp4": {"loss_gap": 1e-3, "grad_gap": 1e-3,
                         "change_gap": 1e-3},
          "poisson": {"theta_gap": 1e-3, "iters_gap": 4}}


def cell(traffic: str, chips: int = 1, config=None) -> harness.Cell:
    bench = harness.load_benchmark()
    name = f"tiny.{traffic}"
    wl = {"name": name, "config": "tiny", "traffic": traffic,
          "chips": chips, "why": "rehearsal"}
    kind = TRAFFIC[traffic]["kind"]
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or m["name"].startswith(kind)]
    return harness.Cell(wl, copy.deepcopy(config or CONFIG),
                        copy.deepcopy(TRAFFIC[traffic]),
                        dict(LIMITS[traffic]), e2e, [])


def run(traffic: str, seed: int = 3, seconds: float = 0.5, chips: int = 1,
        control: bool = False, config=None) -> harness.Run:
    """A whole run of a tiny cell past the device check."""
    c = cell(traffic, chips, config)
    r = harness.Run(c, seed, seconds,
                    device={"platform": "cpu", "kind": "cpu", "count": chips},
                    peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    r.compiles = harness.CompileCounter()
    harness.runner_for(c.traffic["kind"])(r, False, "", control=control)
    return r


def dumps(run: harness.Run) -> str:
    return json.dumps(harness.result_line(run, False))
