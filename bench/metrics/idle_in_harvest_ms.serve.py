"""Device idle time per slab step that falls inside the engine's
harvest (``slab.harvest.block``, ``.fetch`` and ``.retire`` spans): the
gaps in the first chip's busy union that the harvest holds up, over the
window's ``slab.dispatch`` spans."""

from bench.program_trace import device_gaps, overlap_ns, program_spans


def read(run):
    spans = program_spans(run, "slab.")
    if spans is None:
        return None
    steps = sum(1 for s in spans if s.name == "slab.dispatch")
    if not steps:
        return None
    harvest = [(s.start, s.end) for s in spans
               if s.name.startswith("slab.harvest.")]
    return 1e-6 * overlap_ns(device_gaps(run.trace), harvest) / steps
