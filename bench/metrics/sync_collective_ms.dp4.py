"""Device time of the collectives (all-reduce and its kin) per training
step, averaged over the chips: the power sync and the dense phase's
all-reduce of ``core/sync`` under ``shard_map``."""

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")


def read(run):
    c = run.counters
    if run.trace is None or not c.get("steps"):
        return None
    calls, seconds = run.trace.op_seconds(
        lambda name: any(k in name for k in KINDS))
    if not calls:
        return None
    return 1e3 * seconds / c["steps"]
