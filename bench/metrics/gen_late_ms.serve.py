"""How late the load generator submitted documents: the 95th percentile
of submit time minus due time over the window, in milliseconds."""

import numpy as np


def read(run):
    late = run.counters.get("late_s")
    if late is None or len(late) == 0:
        return None
    return 1e3 * float(np.percentile(late, 95))
