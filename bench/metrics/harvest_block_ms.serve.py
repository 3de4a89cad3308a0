"""Host time per slab step spent waiting for a step's retirement mask:
the ``slab.harvest.block`` spans of ``SlabEngine._materialize`` inside
the trace's window, over the window's ``slab.dispatch`` spans."""

from bench.program_trace import program_spans


def read(run):
    spans = program_spans(run, "slab.")
    if spans is None:
        return None
    steps = sum(1 for s in spans if s.name == "slab.dispatch")
    if not steps:
        return None
    return 1e-6 * sum(s.end - s.start for s in spans
                      if s.name == "slab.harvest.block") / steps
