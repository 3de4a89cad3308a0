"""Device time of the jitted training step per call: the ``jit_step``
module's events on the device trace, averaged over the chips."""


def read(run):
    if run.trace is None:
        return None
    calls, seconds = run.trace.module_seconds("jit_step")
    if not calls:
        return None
    return 1e3 * seconds / calls
