"""Padding slots over all slots of the minibatches fed in the window, in
percent: what the batching layer (``data/batching.docs_to_padded``)
hands the step beyond the real (document, word) pairs."""


def read(run):
    c = run.counters
    if not c.get("slots"):
        return None
    return 100.0 * c["pad_slots"] / c["slots"]
