"""The longest host time of one ``SlabEngine.step`` call inside the
window, from the benchmark's own spans: the host stalls that hold the
slab loop, and with it every document in flight."""


def read(run):
    w = run.counters.get("span_window")
    if not w or not run.spans.count("slab_step", *w):
        return None
    return 1e-6 * run.spans.longest_ns("slab_step", *w)
