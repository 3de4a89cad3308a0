"""The dense t=1 sweep kernel (``kernels/bp_update``) against its
roofline: the least time of its unavoidable bytes and operations
(`bench.counting.bp_update`: real tokens, documents and distinct words
only) over its device time in the trace."""

import numpy as np

from bench.counting import bp_update, roofline_share


def read(run):
    c = run.counters
    if run.trace is None or not c.get("steps"):
        return None
    calls, seconds = run.trace.kernel_seconds("bp_update_tokens")
    if not calls or seconds <= 0:
        return None
    # one call per step on every chip, over that chip's share of the
    # minibatch; the chips' distinct words together are at least the
    # minibatch's
    chips = c["chips"]
    flops, nbytes = bp_update(c["nnz"] / c["steps"] / chips,
                              c["docs"] / chips,
                              float(np.mean(c["words"])) / chips,
                              c["num_topics"])
    return roofline_share(calls * flops, calls * nbytes, seconds, run.peaks)
