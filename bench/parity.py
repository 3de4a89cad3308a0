"""Second witnesses for a training cell, on the chip: the program's
Pallas kernels against its XLA formulations on identical inputs, and a
whole minibatch through ``impl=pallas`` against ``impl=jnp``, at the
cell's width and on the benchmark's own minibatch.

  python bench/parity.py --workload train.nytimes-k1000 --seed 5

Where the reference comparison of a cell fails, these say whether a
kernel or the shared algorithm departs.  Prints one JSON line of
readings beside the bounds the kernels are held to (the bounds and the
comparisons come from the repository's smoke run, ``chip_smoke.py``).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# kernels against XLA on identical inputs: bp_update sums the K-wide
# normalization in another order (a few ulp per message); the power_pack
# gather copies and its scatter adds each entry once, as XLA does
KERNEL_BOUND = 1e-5
# one minibatch pallas against jnp: ulp differences can flip a near-tied
# power selection, which moves the flipped entries by one iteration's
# update (max) but little of the mass (l1)
FLIP_MAX_BOUND = 1e-2
FLIP_L1_BOUND = 1e-3


def rel(a, b) -> float:
    """max |a - b| over max |b|."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def l1(a, b) -> float:
    """sum |a - b| over sum |b|."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sum(np.abs(a - b)) / max(np.sum(np.abs(b)), 1e-30))


def kernel_parity(cfg, phi_acc, word_ids, counts, seed: int) -> dict:
    """bp_update and the power_pack gather/scatter against their XLA
    formulations on identical inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import power as pw
    from repro.core.pobp import dense_sweep
    from repro.core.residuals import token_scatter_wk
    from repro.core.sync import LocalReducer
    from repro.core.types import MiniBatch
    from repro.kernels.bp_update.ops import dense_sweep_pallas
    from repro.kernels.power_pack import ops as pp

    D, L = word_ids.shape
    W, K = phi_acc.shape
    u = jax.random.uniform(jax.random.PRNGKey(seed), (D, L, K),
                           minval=0.01, maxval=1.0)
    mu0 = u / jnp.sum(u, -1, keepdims=True)
    phi_eff = phi_acc + token_scatter_wk(word_ids, counts[..., None] * mu0, W)
    phi_tot = jnp.sum(phi_eff, axis=0)
    sweeps = [jax.jit(lambda w, c, m, f, t: dense_sweep_pallas(
                  MiniBatch(w, c), m, f, t, cfg)),
              jax.jit(lambda w, c, m, f, t: dense_sweep(
                  MiniBatch(w, c), m, f, t, cfg, LocalReducer()))]
    (mp, rp), (mj, rj) = (jax.device_get(fn(word_ids, counts, mu0, phi_eff,
                                            phi_tot)) for fn in sweeps)
    out = {"bp_update_mu": rel(mp, mj), "bp_update_r": rel(rp, rj)}
    rng = np.random.default_rng(seed)
    P, Pk = cfg.num_power_words, cfg.num_power_topics
    sel_w = rng.permutation(np.append(
        rng.choice(W - 1, P - 1, replace=False), W - 1)).astype(np.int32)
    sel_k = np.stack([rng.choice(K, Pk, replace=False)
                      for _ in range(P)]).astype(np.int32)
    vals = rng.standard_normal((P, Pk)).astype(np.float32)
    out["power_pack_gather"] = rel(pp.pack_rows(phi_acc, sel_w, sel_k),
                                   jax.jit(pw.pack_rows)(phi_acc, sel_w,
                                                         sel_k))
    out["power_pack_scatter"] = rel(
        pp.scatter_add_rows(phi_acc, sel_w, sel_k, vals),
        jax.jit(pw.scatter_add_rows)(phi_acc, sel_w, sel_k, vals))
    return out


def minibatch_parity(cfg, phi_acc, word_ids, counts, seed: int) -> dict:
    """One minibatch through impl=pallas and impl=jnp."""
    import jax
    import jax.numpy as jnp

    from repro.core.pobp import make_sim_minibatch_fn
    got = {}
    for impl in ("pallas", "jnp"):
        fn, _ = make_sim_minibatch_fn(dataclasses.replace(cfg, impl=impl), 1)
        phi, _, mean_r, _, _ = fn(word_ids, counts, phi_acc,
                                  jax.random.PRNGKey(seed), jnp.float32(1.0))
        got[impl] = (jax.device_get(phi), float(mean_r))
    (pp_, rp), (pj, rj) = got["pallas"], got["jnp"]
    return {"phi_pallas_vs_jnp": rel(pp_, pj),
            "phi_l1_pallas_vs_jnp": l1(pp_, pj),
            "mean_r_pallas_vs_jnp": abs(rp - rj) / max(abs(rj), 1e-30)}


def main(argv=None) -> int:
    import argparse

    import jax.numpy as jnp

    from bench.train_cell import Pool, lda_config

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from bench.run import prepare
    run = prepare(args.workload, args.seed, 0.0)
    cfg = lda_config(run.cell.config)
    pool = Pool(run, int(run.cell.config["docs_per_batch"]))
    w, c = jnp.asarray(pool.word_ids[0]), jnp.asarray(pool.counts[0])
    res = kernel_parity(cfg, pool.phi0, w, c, args.seed)
    res.update(minibatch_parity(cfg, pool.phi0, w, c, args.seed))
    bounds = {"bp_update_mu": KERNEL_BOUND, "bp_update_r": KERNEL_BOUND,
              "power_pack_gather": 0.0, "power_pack_scatter": 0.0,
              "phi_pallas_vs_jnp": FLIP_MAX_BOUND,
              "phi_l1_pallas_vs_jnp": FLIP_L1_BOUND,
              "mean_r_pallas_vs_jnp": FLIP_L1_BOUND}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "readings": res, "bounds": bounds,
                      "within": all(res[k] <= bounds[k] for k in res)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
