"""Device time per training step of the packed refresh: the
``power_pack`` phi scatter, the residual row scatter and the ``r_w``
update, the leaf ops traced under ``pobp.scatter``, over every selective
iteration (`bench.program_trace.step_split`)."""

from bench.program_trace import phase_ms


def read(run):
    return phase_ms(run, ("pobp.scatter",))
