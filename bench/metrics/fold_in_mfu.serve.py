"""Share of the chip's peak (the bf16 figure; the fold-in computes in
float32) that the fold-in work of the window reaches: every sweep of
every real token of the documents answered in the window, at the
operations `bench.counting.fold_in` counts, over the window."""

import numpy as np

from bench.counting import fold_in


def read(run):
    c = run.counters
    w = c.get("span_window")
    if c.get("doc_iters") is None or not len(c["doc_iters"]) or not w:
        return None
    flops, _ = fold_in(float(np.sum(c["doc_tokens"] * c["doc_iters"])), 0.0,
                       c["num_topics"])
    return 100.0 * flops / ((w[1] - w[0]) * 1e-9) / run.peaks["flops_per_s"]
