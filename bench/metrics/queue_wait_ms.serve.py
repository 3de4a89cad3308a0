"""How long a document waited in the admission queue: the median of
its ``refill`` stamp (the dispatch that put it in a slot) minus its
``submit`` stamp, over the documents whose theta reached the host inside
the trace's window (``repro.obs`` stamps of ``SlabEngine``)."""

import numpy as np

from bench.program_trace import window_requests


def read(run):
    reqs = window_requests(run, ("submit", "refill"))
    if not reqs:
        return None
    return 1e-6 * float(np.median([r["refill"] - r["submit"]
                                   for r in reqs]))
