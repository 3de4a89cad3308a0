"""Smoke run of the main path on a TPU: POBP streaming training and
fold-in serving at NYTimes width, through the normal entry points, with
the Pallas kernels compiled by Mosaic.

  python chip_smoke.py             # one chip: train -> parity -> serve
  python chip_smoke.py --chips 4   # four chips: the shard_map meshes only

One chip (the default):

  1. device check — the first device must be a TPU and the kernels must
     not be in interpret mode;
  2. training — `repro.launch.lda_train.main` at W=102,660 (the UCI
     NYTimes vocabulary), K=1000, lambda_W=0.1, 50 power topics,
     impl=pallas, one shard, with held-out perplexity and a checkpoint;
  3. parity — one minibatch at the same width through impl=pallas and
     impl=jnp, and one at a reduced W through pallas and the plain
     float32 reference (`repro.core.ref`);
  4. serving — `repro.launch.serve.main` on that checkpoint: the slab
     engine on the Pallas fold-in kernel, a few hundred documents.

``--chips 4`` runs `lda_train` under ``--backend shard_map`` at mesh
(4,1) (documents over chips) and (2,2) (topics over 'model' too) on the
same seeded stream as ``--backend sim --shards 4``, and nothing else.

All data comes from ``--seed``.  A failed phase raises, so the script
exits non-zero; the last stdout line, printed only when every phase
passed, is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Timings printed here are from a smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Shapes of one smoke run.  The defaults are the chip run's: the
    NYTimes vocabulary and K=1000 at full width; documents of ~240 tokens
    (NYTimes averages ~232 distinct words per document) in one L bucket
    of 256 word slots, 768 per minibatch — a training step that holds
    4.6 GiB of a v5e's 16 GB (its `memory_analysis`).  The host-side
    corpus sampler draws each document's words topic by topic over all W
    words (~0.1 s per document at this width), so it, not the chip, sets
    the run's wall time."""

    vocab: int = 102_660
    topics: int = 1000
    lambda_w: float = 0.1
    lambda_k: int = 50
    docs: int = 768               # documents per training minibatch
    doc_len: int = 240            # mean tokens per document
    len_buckets: str = "256"
    minibatches: int = 2
    inner_iters: int = 12
    eval_docs: int = 128
    parity_docs: int = 64         # the full-width pallas-vs-jnp minibatch
    ref_vocab: int = 4096         # reduced W of the float32 reference
    ref_docs: int = 32
    requests: int = 300
    slots: int = 128
    slot_len: int = 256
    mesh_docs: int = 32           # --chips 4: documents per minibatch
    mesh_minibatches: int = 2
    mesh_eval_docs: int = 32


# Parity bounds.  "max" is max |a - b| over max |b|; "l1" is
# sum |a - b| over sum |b| (the share of the statistic's mass that moved).
#  - kernels against XLA on identical inputs, full width: bp_update
#    evaluates the dense sweep's float32 formula with the K-wide
#    normalization summed in another order — a few ulp per message
#    (~1e-7), so 1e-5 on messages and residuals; the power_pack gather
#    copies and its scatter adds each entry once, as XLA does — exact;
#  - one minibatch through impl=pallas and impl=jnp, full width: those
#    ulp differences reach the top-k power selection, where a near-tied
#    residual at the P-th word or a row's Pk-th topic can fall on either
#    side.  A flipped entry gains or misses one iteration's update, no
#    larger than its residual — a small share of the largest |phi| (max
#    1e-2) — and the few flipped entries hold a tiny part of the mass
#    (l1 1e-3).  mean_r sums the residual over every token, so flips
#    move it by their share only: 1e-3;
#  - pallas vs the float32 reference at a reduced W: with every word and
#    topic selected (lambda_W = 1, Pk = K) POBP is batch BP, there is no
#    selection to flip and only summation order differs — 1e-4 (mean_r
#    on the scale of the first sweep's, see `parity_phase`).
KERNEL_BOUND = 1e-5
FLIP_MAX_BOUND = 1e-2
FLIP_L1_BOUND = 1e-3
MEAN_R_BOUND = 1e-3
REF_BOUND = 1e-4
# Mesh bounds (--chips 4):
#  - (4,1) against sim with 4 shards: the same algorithm, the same
#    per-document init and the same shards; only the all-reduce order
#    differs, so the flip bounds above hold;
#  - (2,2): two data shards, not four — each minibatch's first sweep
#    starts from shard-local statistics (Fig. 4 line 5) — and power topics
#    chosen per topic shard (Pk/2 of each shard's K/2 topics, DESIGN.md
#    §2), not as sim's global top-Pk.  Token messages differ by design.
#    What may not differ: every token's message sums to one over all
#    topics on any layout, so each word's row of the statistic sums to
#    its count in the stream (1e-4, summation order only); and the
#    model's quality — held-out perplexity within 2% of sim's.
MESH_MASS_BOUND = 1e-4
MESH_PPL_BOUND = 0.02


def _log(msg: str) -> None:
    print(msg, flush=True)


def check_device(want: int) -> dict:
    """Refuse to run anywhere but on ``want`` TPU chips with compiled
    (not interpreted) kernels; returns the device record."""
    import jax

    from repro import kernels
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(f"[device] JAX found no TPU (first device: "
                         f"{d0.platform}); this smoke run needs the chip")
    if kernels.INTERPRET:
        raise SystemExit("[device] Pallas kernels are in interpret mode on "
                         "a TPU")
    if len(devices) < want:
        raise SystemExit(f"[device] need {want} chips, JAX found "
                         f"{len(devices)}")
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices)}
    _log(f"[device] {dev}")
    return dev


def _peak_bytes(device) -> str:
    """Peak device bytes where the backend reports them (a TPU does):
    buffers in use, and the reserved region that holds the compiled
    programs' temporaries (which ``peak_bytes_in_use`` does not count)."""
    stats = device.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"peak_bytes_reserved={stats.get('peak_bytes_reserved')}")


def _train_argv(s: Sizes, seed: int, *extra: str, topic_shards: int = 1
                ) -> list:
    # power topics are chosen per topic shard: split them so every word
    # selects lambda_k topics in all
    return ["--vocab", str(s.vocab), "--topics", str(s.topics),
            "--lambda-w", str(s.lambda_w),
            "--lambda-k", str(s.lambda_k // topic_shards),
            "--inner-iters", str(s.inner_iters),
            "--doc-len-means", str(s.doc_len),
            "--len-buckets", s.len_buckets, "--impl", "pallas",
            "--eval-docs", str(s.eval_docs), "--log-every", "1",
            "--seed", str(seed), *extra]


def _require_finite(what: str, *values) -> None:
    import numpy as np
    for v in values:
        if not np.all(np.isfinite(np.asarray(v))):
            raise AssertionError(f"{what}: non-finite values")


def train_phase(s: Sizes, seed: int, ckpt_dir: Path) -> dict:
    """POBP streaming training through `lda_train.main` (impl=pallas,
    one shard) into a fresh checkpoint directory."""
    import jax
    import jax.numpy as jnp

    from repro.core import sweep_dispatch as sd
    from repro.core.pobp import init_train_state, make_train_step
    from repro.launch import lda_train

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    argv = _train_argv(
        s, seed, "--backend", "sim", "--shards", "1",
        "--minibatches", str(s.minibatches),
        "--docs-per-batch", str(s.docs),
        "--eval-every", str(s.minibatches),
        "--ckpt-dir", str(ckpt_dir), "--ckpt-every", str(s.minibatches))
    _log(f"[train] lda_train {' '.join(argv)}")
    res = lda_train.main(argv)

    cfg, buckets = lda_train._build_cfg(lda_train.build_parser()
                                        .parse_args(argv))
    P, Pk = cfg.num_power_words, cfg.num_power_topics
    step, _ = make_train_step(cfg)
    state = jax.eval_shape(lambda: init_train_state(cfg, seed))
    for L in buckets:
        got = sd.resolve_sweep_policy(cfg, s.docs * L, cfg.num_topics, Pk, P,
                                      impl="pallas", n_docs=s.docs)
        _log(f"[train] formulations at D={s.docs} L={L} K={cfg.num_topics} "
             f"P={P} Pk={Pk}: dense t=1 sweep=bp_update (Pallas), selective "
             f"sweep={got}, phi scatter=power_pack (Pallas)")
        # the step's own footprint, from the program the run executed
        # (a persistent-cache hit where one is configured)
        ma = step.lower(state, jax.ShapeDtypeStruct((s.docs, L), jnp.int32),
                        jax.ShapeDtypeStruct((s.docs, L), jnp.float32)
                        ).compile().memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        _log(f"[train] step memory_analysis at D={s.docs} L={L}: "
             f"arguments={ma.argument_size_in_bytes} "
             f"outputs={ma.output_size_in_bytes} "
             f"temporaries={ma.temp_size_in_bytes} "
             f"aliased={ma.alias_size_in_bytes} -> {total} bytes "
             f"({total / 2**30:.2f} GiB)")
    _log(f"[train] fold-in at slab {s.slots}x{s.slot_len}: "
         f"{sd.resolve_fold_in(cfg.num_topics, s.slots)}")
    for e in sd.DISPATCH_LOG:
        _log(f"[train] kernel bypassed: {e}")
    _log(f"[train] mean_r per minibatch={res['mean_r']}  "
         f"iters={res['iters']}  held-out ppl={res['ppl']}  "
         f"compiles={res['compiles']}  "
         f"{_peak_bytes(jax.devices()[0])}")
    _require_finite("training", res["mean_r"], res["ppl"], res["phi_acc"])
    if len(res["mean_r"]) != s.minibatches:
        raise AssertionError(f"trained {len(res['mean_r'])} of "
                             f"{s.minibatches} minibatches")
    return res


def _rel(a, b) -> float:
    """max |a - b| over max |b|."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _l1(a, b) -> float:
    """sum |a - b| over sum |b|."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sum(np.abs(a - b)) / max(np.sum(np.abs(b)), 1e-30))


def _rel_scalars(a, b) -> float:
    """Largest relative difference over paired scalars."""
    return max(abs(x - y) / max(abs(y), 1e-30) for x, y in zip(a, b))


def _check(what: str, value: float, bound: float) -> None:
    _log(f"[parity] {what}={value:.3e} (bound {bound:.0e})")
    if not value <= bound:
        raise AssertionError(f"{what}={value:.3e} exceeds {bound:.0e}")


def _minibatch(cfg, seed: int, n_docs: int, doc_len: int, L: int):
    """One seeded minibatch drawn from a fixed random topic set."""
    import numpy as np

    from repro.data.batching import docs_to_padded
    from repro.data.synthetic import lda_corpus_from_phi
    phi = np.random.default_rng(seed).dirichlet(
        np.full(cfg.vocab_size, 0.06), size=cfg.num_topics)
    docs, _ = lda_corpus_from_phi(seed + 1, n_docs, phi, doc_len_mean=doc_len)
    return docs_to_padded(docs, max_len=L)


def kernel_parity(cfg, phi_acc, mb, seed: int) -> dict:
    """bp_update and the power_pack gather/scatter against their XLA
    formulations on identical inputs, at the width of ``cfg``."""
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

    D, L = mb.word_ids.shape
    W, K = phi_acc.shape
    u = jax.random.uniform(jax.random.PRNGKey(seed), (D, L, K),
                           minval=0.01, maxval=1.0)
    mu0 = u / jnp.sum(u, -1, keepdims=True)
    phi = jnp.asarray(phi_acc)
    # the sweep's phi holds this minibatch's own messages (Fig. 4 line 5)
    phi_eff = phi + token_scatter_wk(mb.word_ids, mb.counts[..., None] * mu0,
                                     W)
    phi_tot = jnp.sum(phi_eff, axis=0)
    sweeps = {
        "pallas": jax.jit(lambda w, c, m, f, t: dense_sweep_pallas(
            MiniBatch(w, c), m, f, t, cfg)),
        "jnp": jax.jit(lambda w, c, m, f, t: dense_sweep(
            MiniBatch(w, c), m, f, t, cfg, LocalReducer()))}
    (mp, rp), (mj, rj) = (jax.device_get(fn(mb.word_ids, mb.counts, mu0,
                                            phi_eff, phi_tot))
                          for fn in sweeps.values())
    _require_finite("dense sweep", mp, rp, mj, rj)
    res = {"bp_update_mu": _rel(mp, mj), "bp_update_r": _rel(rp, rj)}

    # a selection like POBP's: P distinct rows (one in the partial last
    # 8-row tile, which the ops layer moves with XLA), Pk distinct topics
    rng = np.random.default_rng(seed)
    P, Pk = cfg.num_power_words, cfg.num_power_topics
    sel_w = rng.permutation(np.append(
        rng.choice(W - 1, P - 1, replace=False), W - 1)).astype(np.int32)
    sel_k = np.stack([rng.choice(K, Pk, replace=False)
                      for _ in range(P)]).astype(np.int32)
    vals = rng.standard_normal((P, Pk)).astype(np.float32)
    res["power_pack_gather"] = _rel(pp.pack_rows(phi, sel_w, sel_k),
                                    jax.jit(pw.pack_rows)(phi, sel_w, sel_k))
    res["power_pack_scatter"] = _rel(
        pp.scatter_add_rows(phi, sel_w, sel_k, vals),
        jax.jit(pw.scatter_add_rows)(phi, sel_w, sel_k, vals))
    _log(f"[parity] kernels vs XLA on identical inputs, W={W} K={K} D={D} "
         f"L={L} P={P} Pk={Pk}")
    for what in ("bp_update_mu", "bp_update_r"):
        _check(what, res[what], KERNEL_BOUND)
    for what in ("power_pack_gather", "power_pack_scatter"):
        _check(what, res[what], 0.0)
    return res


def parity_phase(s: Sizes, seed: int, phi_acc) -> dict:
    """At full width from the trained statistic: the kernels against XLA
    on identical inputs, then one minibatch through impl=pallas vs
    impl=jnp; and pallas vs the float32 reference at a reduced W with
    every word and topic selected."""
    import jax
    import jax.numpy as jnp

    from repro.core import ref
    from repro.core.pobp import make_sim_minibatch_fn
    from repro.core.types import LDAConfig

    L = int(s.len_buckets.split(",")[-1])
    key = jax.random.PRNGKey(seed + 7)
    cfg = LDAConfig(vocab_size=s.vocab, num_topics=s.topics,
                    lambda_w=s.lambda_w, lambda_k_abs=s.lambda_k,
                    inner_iters=s.inner_iters, residual_tol=0.0,
                    impl="pallas", init_pad_len=L)
    mb = _minibatch(cfg, seed + 11, s.parity_docs, s.doc_len, L)
    res = kernel_parity(cfg, phi_acc, mb, seed + 5)

    phi0 = jnp.asarray(phi_acc)
    out = {}
    for impl in ("pallas", "jnp"):
        fn, _ = make_sim_minibatch_fn(dataclasses.replace(cfg, impl=impl), 1)
        phi, iters, mean_r, _, _ = fn(mb.word_ids, mb.counts, phi0, key,
                                      jnp.float32(1.0))
        out[impl] = (jax.device_get(phi), float(mean_r), int(iters))
    (pp, rp, ip), (pj, rj, ij) = out["pallas"], out["jnp"]
    _require_finite("parity", pp, pj, rp, rj)
    _log(f"[parity] one minibatch W={s.vocab} K={s.topics} "
         f"D={s.parity_docs} L={L}: iters pallas={ip} jnp={ij}  "
         f"mean_r pallas={rp} jnp={rj}")
    res.update(phi_pallas_vs_jnp=_rel(pp, pj),
               phi_l1_pallas_vs_jnp=_l1(pp, pj),
               mean_r_pallas_vs_jnp=_rel_scalars([rp], [rj]))
    _check("phi_pallas_vs_jnp", res["phi_pallas_vs_jnp"], FLIP_MAX_BOUND)
    _check("phi_l1_pallas_vs_jnp", res["phi_l1_pallas_vs_jnp"],
           FLIP_L1_BOUND)
    _check("mean_r_pallas_vs_jnp", res["mean_r_pallas_vs_jnp"],
           MEAN_R_BOUND)

    # float32 reference: POBP with lambda_W = 1 and Pk = K is batch BP
    rcfg = LDAConfig(vocab_size=s.ref_vocab, num_topics=s.topics,
                     lambda_w=1.0, lambda_k_abs=s.topics,
                     inner_iters=s.inner_iters, residual_tol=0.0,
                     impl="pallas")
    rb = _minibatch(rcfg, seed + 13, s.ref_docs, s.doc_len, L)
    fn, _ = make_sim_minibatch_fn(rcfg, 1)
    phi, iters, mean_r, _, _ = fn(
        rb.word_ids, rb.counts,
        jnp.zeros((s.ref_vocab, s.topics), jnp.float32), key,
        jnp.float32(1.0))
    _, phi_ref, _, trace = ref.batch_bp(key, rb, rcfg, iters=int(iters))
    pr, rr = jax.device_get(phi), float(mean_r)
    _require_finite("reference parity", pr, rr)
    res["phi_pallas_vs_ref"] = _rel(pr, jax.device_get(phi_ref).T)
    # mean_r after the last sweep is a small difference of nearly
    # converged messages (~1e-3 of the first sweep's): against its own
    # size, their rounding is magnified by that ratio, so it is compared
    # on the scale the run started from, the first sweep's mean_r
    res["mean_r_pallas_vs_ref"] = (abs(rr - float(trace[-1]))
                                   / max(abs(float(trace[0])), 1e-30))
    _log(f"[parity] reference W={s.ref_vocab} K={s.topics} D={s.ref_docs}: "
         f"iters={int(iters)}  mean_r pallas={rr} ref={float(trace[-1])} "
         f"(ref first sweep {float(trace[0])})")
    _check("phi_pallas_vs_ref", res["phi_pallas_vs_ref"], REF_BOUND)
    _check("mean_r_pallas_vs_ref", res["mean_r_pallas_vs_ref"], REF_BOUND)
    return res


def serve_phase(s: Sizes, seed: int, ckpt_dir: Path) -> dict:
    """Fold-in serving from the trained checkpoint through
    `serve.main`: the slab engine on the Pallas fold-in kernel."""
    import numpy as np

    from repro.launch import serve

    argv = ["--mode", "lda", "--ckpt-dir", str(ckpt_dir),
            "--admission", "slab", "--slots", str(s.slots),
            "--slot-len", str(s.slot_len), "--requests", str(s.requests),
            "--doc-len-means", str(s.doc_len), "--seed", str(seed)]
    _log(f"[serve] serve {' '.join(argv)}")
    results, stats = serve.main(argv)
    if len(results) != s.requests:
        raise AssertionError(f"served {len(results)} of {s.requests}")
    bad = [r.req_id for r in results
           if r.error is not None or not np.all(np.isfinite(r.theta))]
    if bad:
        raise AssertionError(f"requests with errors or non-finite theta: "
                             f"{bad[:10]}")
    _log(f"[serve] smoke run, not a benchmark: {len(results)} docs  "
         f"p50={stats['latency_p50_s'] * 1e3:.1f}ms  "
         f"p99={stats['latency_p99_s'] * 1e3:.1f}ms  "
         f"docs/s={stats['docs_per_s']:.0f}  compiles={stats['compiles']}")
    return stats


def _memory(devices) -> str:
    return "  ".join(f"device {d.id}: {_peak_bytes(d)}" for d in devices)


def mesh_phase(s: Sizes, seed: int) -> dict:
    """shard_map meshes (4,1) and (2,2) against the vmap simulation with
    4 shards on the same seeded stream."""
    import jax
    import numpy as np

    from repro.launch import lda_train

    devices = jax.devices()[:4]
    common = ("--minibatches", str(s.mesh_minibatches),
              "--docs-per-batch", str(s.mesh_docs),
              "--eval-docs", str(s.mesh_eval_docs), "--no-warmup-buckets")
    runs = {}
    for name, extra, topic_shards in [
            ("mesh 2,2", ("--backend", "shard_map", "--mesh-shape", "2,2"), 2),
            ("mesh 4,1", ("--backend", "shard_map", "--mesh-shape", "4,1"), 1),
            ("sim 4", ("--backend", "sim", "--shards", "4"), 1)]:
        argv = _train_argv(s, seed, *common, *extra,
                           topic_shards=topic_shards)
        _log(f"[mesh] {name}: lda_train {' '.join(argv)}")
        res = lda_train.main(argv)
        _require_finite(name, res["mean_r"], res["phi_acc"])
        runs[name] = res
        _log(f"[mesh] {name}: mean_r={res['mean_r']}  "
             f"{_memory(devices)}")
    sim, dp, tp = runs["sim 4"], runs["mesh 4,1"], runs["mesh 2,2"]
    out = {"phi_4x1_vs_sim": _rel(dp["phi_acc"], sim["phi_acc"]),
           "phi_l1_4x1_vs_sim": _l1(dp["phi_acc"], sim["phi_acc"]),
           "mean_r_4x1_vs_sim": _rel_scalars(dp["mean_r"], sim["mean_r"]),
           "phi_l1_2x2_vs_sim": _l1(tp["phi_acc"], sim["phi_acc"]),
           "word_mass_2x2_vs_sim": _rel(
               np.asarray(tp["phi_acc"], np.float64).sum(axis=1),
               np.asarray(sim["phi_acc"], np.float64).sum(axis=1)),
           "ppl_2x2_vs_sim": abs(tp["ppl"] - sim["ppl"]) / sim["ppl"]}
    _log(f"[parity] held-out ppl 2,2={tp['ppl']} 4,1={dp['ppl']} "
         f"sim={sim['ppl']}  phi_l1_2x2_vs_sim (not bounded: token "
         f"messages differ by design)={out['phi_l1_2x2_vs_sim']:.3e}")
    _log(f"[parity] mesh comparisons: {out}")
    _check("phi_4x1_vs_sim", out["phi_4x1_vs_sim"], FLIP_MAX_BOUND)
    _check("phi_l1_4x1_vs_sim", out["phi_l1_4x1_vs_sim"], FLIP_L1_BOUND)
    _check("mean_r_4x1_vs_sim", out["mean_r_4x1_vs_sim"], MEAN_R_BOUND)
    _check("word_mass_2x2_vs_sim", out["word_mass_2x2_vs_sim"],
           MESH_MASS_BOUND)
    _check("ppl_2x2_vs_sim", out["ppl_2x2_vs_sim"], MESH_PPL_BOUND)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: train, parity and serving on one chip; "
                         "4: the shard_map meshes against sim, only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    dev = check_device(args.chips)
    s = Sizes()
    if args.chips == 4:
        mesh_phase(s, args.seed)
    else:
        ckpt = ROOT / ".smoke_ckpt"
        res = train_phase(s, args.seed, ckpt)
        parity_phase(s, args.seed, res["phi_acc"])
        serve_phase(s, args.seed, ckpt)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
