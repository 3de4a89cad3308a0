"""Faults planted in the serving program underneath a whole run, which
the comparison has to find: the tests plant them at a tiny size, and
``bench/readings.py --fault`` on the chip at the cell's own size.

- ``early_retire``: every document retires after its first slab step;
- ``loose_tail``: the retirement rule's tolerance is four times the
  configuration's.
"""

from __future__ import annotations

FAULTS = ("early_retire", "loose_tail")


def plant(name: str, set_attr=setattr) -> None:
    """Replaces ``core.infer.make_slab_step`` with one that builds the
    step with fault ``name``; ``set_attr`` is ``setattr`` or a test's
    ``monkeypatch.setattr``."""
    import repro.core.infer as infer
    make = infer.make_slab_step
    if name == "early_retire":
        def broken(cfg, **k):
            return make(cfg, **dict(k, fold_iters=k["sweeps_per_step"]))
    elif name == "loose_tail":
        def broken(cfg, **k):
            return make(cfg, **dict(k, residual_tol=4 * k["residual_tol"]))
    else:
        raise KeyError(f"no fault named {name!r}; known: {FAULTS}")
    set_attr(infer, "make_slab_step", broken)
