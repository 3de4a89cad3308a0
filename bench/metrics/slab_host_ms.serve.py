"""Host time per call of ``SlabEngine.step`` (refill, dispatch and
harvest) inside the window, from the benchmark's own spans."""


def read(run):
    w = run.counters.get("span_window")
    if not w:
        return None
    n = run.spans.count("slab_step", *w)
    if not n:
        return None
    return 1e-6 * run.spans.total_ns("slab_step", *w) / n
