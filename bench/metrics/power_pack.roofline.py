"""The packed phi scatter kernel (``kernels/power_pack``,
``scatter_add_rows``) against its roofline: per call, the [P, Pk] values
and topic ids read and each target element read and written, over its
device time in the trace."""

from bench.counting import power_pack_scatter, roofline_share


def read(run):
    c = run.counters
    if run.trace is None or not c.get("power_words"):
        return None
    calls, seconds = run.trace.kernel_seconds("scatter_add_rows")
    if not calls or seconds <= 0:
        return None
    flops, nbytes = power_pack_scatter(c["power_words"], c["power_topics"])
    return roofline_share(calls * flops, calls * nbytes, seconds, run.peaks)
