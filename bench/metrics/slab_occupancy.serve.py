"""Mean share of the slab's slots holding a live document per step over
the window, in percent (``SlabEngine.stats()["slot_occupancy"]``)."""


def read(run):
    c = run.counters
    if not c.get("steps"):
        return None
    return 100.0 * c["occupied_share"]
