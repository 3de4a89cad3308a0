"""Device time per training step of the selective sweep over the power
coordinates: the leaf ops traced under ``pobp.selective_sweep``, over
every selective iteration (`bench.program_trace.step_split`)."""

from bench.program_trace import phase_ms


def read(run):
    return phase_ms(run, ("pobp.selective_sweep",))
