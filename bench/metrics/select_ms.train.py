"""Device time per training step of power selection (``top_k`` over
the words' residuals, then over the topics of the power words): the
leaf ops traced under ``pobp.select``, over every selective iteration
(`bench.program_trace.step_split`)."""

from bench.program_trace import phase_ms


def read(run):
    return phase_ms(run, ("pobp.select",))
