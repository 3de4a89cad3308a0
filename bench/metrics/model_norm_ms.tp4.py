"""Device time per training step of the selective sweep's
renormalization over topic shards (the psum of each token's selected
mass and denominator over the model axis): the leaf ops traced under
``pobp.model_norm``, over every selective iteration, through the cell's
own step on its mesh (`bench.train_tp_cell.phase_ms`)."""

from bench.train_tp_cell import phase_ms


def read(run):
    return phase_ms(run, ("pobp.model_norm",))
