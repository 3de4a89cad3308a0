"""Device time per training step of the exact power-topic merge across
topic shards (the all-gather of every shard's candidates over the model
axis and the top-Pk of them): the leaf ops traced under
``pobp.topic_merge``, over every selective iteration, through the
cell's own step on its mesh (`bench.train_tp_cell.phase_ms`)."""

from bench.train_tp_cell import phase_ms


def read(run):
    return phase_ms(run, ("pobp.topic_merge",))
