"""Device time per training step of the dense phase (Fig. 4 lines
3-10): the leaf ops traced under ``pobp.init``, ``pobp.dense_sweep`` and
``pobp.dense_sync`` (`bench.program_trace.step_split`)."""

from bench.program_trace import phase_ms


def read(run):
    return phase_ms(run, ("pobp.init", "pobp.dense_sweep",
                          "pobp.dense_sync"))
