"""How long a retired document's theta took to reach the host: the
median of its ``done`` stamp minus the dispatch of the step whose mask
retired it (``retire_dispatch``), over the documents done inside the
trace's window.  It holds that step's device time and the harvest
pipeline's lag behind it (``repro.obs`` stamps of ``SlabEngine``)."""

import numpy as np

from bench.program_trace import window_requests


def read(run):
    reqs = window_requests(run, ("retire_dispatch",))
    if not reqs:
        return None
    return 1e-6 * float(np.median([r["done"] - r["retire_dispatch"]
                                   for r in reqs]))
