"""The fold-in carry kernel (``kernels/power_sweep``,
``power_sweep_carry_tokens`` with ``update_phi=False``) against its
roofline: the operations of every sweep of a real token, and each real
token's message read and written once in every call it is active in
(`bench.counting.fold_in`, over the documents answered in the window),
over the kernel's device time in the trace."""

import numpy as np

from bench.counting import fold_in, roofline_share


def read(run):
    c = run.counters
    if run.trace is None or c.get("doc_iters") is None \
            or not len(c["doc_iters"]) or not c.get("steps"):
        return None
    calls, seconds = run.trace.kernel_seconds("power_sweep_carry_tokens")
    if not calls or seconds <= 0:
        return None
    # a document active for n sweeps is in at least ceil(n / s) calls,
    # where a call runs s of a step's sweeps
    per_call = max(1.0, c["sweeps_per_step"] * c["steps"] / calls)
    tokens, iters = c["doc_tokens"], c["doc_iters"]
    flops, nbytes = fold_in(float(np.sum(tokens * iters)),
                            float(np.sum(tokens * np.ceil(iters / per_call))),
                            c["num_topics"])
    return roofline_share(flops, nbytes, seconds, run.peaks)
