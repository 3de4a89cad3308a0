"""Adaptive sweep dispatch — the measured cost model behind
``LDAConfig.sweep_policy`` (DESIGN.md §2).

The selective iteration (Fig. 4 lines 15-21) has two algebraically
identical formulations whose relative cost flips with the shape:

  - **packed**: [T, Pk] token streams + a Pk-term fold-back chain into the
    [T, K] carry.  Work scales with T*K*Pk (the chain) — unbeatable when
    Pk << K, K-proportional pain when Pk approaches K (the K64_Pk50
    regression this module exists to fix).
  - **dense_layout**: the one-pass [T, K] masked formulation (the jnp
    mirror of the carry-resident ``power_sweep`` megakernel): a signed-phi
    row table makes u exactly zero off the power submatrix, so the update,
    fold-back and theta contraction are a handful of fused [T, K] passes —
    Pk-independent.

A third formulation exists only on the pallas side:

  - **kblocked**: the K-blocked two-pass carry megakernel (DESIGN.md
    §13) — same dense-layout math tiled as [TT, KB] topic blocks, for
    ultra-high K where the full-K carry no longer fits a useful token
    tile in VMEM.  On the jnp impl it is an alias of dense_layout (XLA
    has no VMEM constraint to respect).

Both produce the same packed [P, Pk] sync buffers, so the Eq. 6
communication (CommMeter bytes) is invariant to the choice — pinned by
tests/test_sweep_policy.py.

A fourth resolution exists only on the pallas side:

  - **xla**: the dense-layout formulation in plain XLA ops, for shapes
    the carry kernels should not take — their one-hot MXU gathers cost
    (4*P1 + 2*D) MACs per token-topic (P1 power rows, D documents), and
    past ``ONEHOT_MACS_MAX`` that work exceeds the XLA formulation's
    memory-bound passes (at NYTimes width, P1 ~ 10k, it is ~10x over);
    a shape whose tables do not fit the VMEM budget at any block width
    lands here too.  Every such resolution is printed once per shape and
    recorded in ``DISPATCH_LOG`` — the kernel is never silently skipped
    and never left to fail in Mosaic.

``resolve_sweep_policy`` picks the cheaper formulation per (T, K, Pk, P)
at trace time from a **measured** cost model: four per-element machine
rates (fused elementwise pass, compare-select chain term, row scatter-add,
row gather) are timed once per process on small probe shapes and plugged
into analytic element counts.  The pallas branch extends the model with a
VMEM-fit predicate (`kernels.power_sweep.kernel.carry_vmem_fits`): auto
resolves to the one-pass carry kernel while its footprint admits a >= 64
token tile within the budget (``LDAConfig.vmem_budget_bytes`` >
``REPRO_VMEM_BUDGET_BYTES`` > default), and to kblocked beyond that.
Resolution is cached per shape so dispatch is deterministic within a
process and never retraces across mini-batches (compile-count pinned).

Set ``REPRO_SWEEP_CALIBRATE=0`` to skip the ~100 ms measurement and use
the committed fallback coefficients (measured on a 2-core CPU container).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Optional


@dataclasses.dataclass(frozen=True)
class SweepCoeffs:
    """Per-element machine rates, nanoseconds (see measure_coeffs)."""

    ew_ns: float        # fused elementwise pass, per element
    chain_ns: float     # one compare-select chain term, per element
    scatter_ns: float   # row-indexed scatter-add, per scattered element
    gather_ns: float    # per gathered element ([T, Pk]-style take_along)


# Fallback (and test-determinism) coefficients, measured in this repo's
# CPU container; real TPUs resolve through the pallas branch below, which
# never consults them.
DEFAULT_COEFFS = SweepCoeffs(ew_ns=0.55, chain_ns=0.30, scatter_ns=1.9,
                             gather_ns=1.3)

_MEASURED: Optional[SweepCoeffs] = None


def _time_jitted(fn, *args, reps: int = 5) -> float:
    """Best-of-reps wall seconds for one call of a jitted fn."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def measure_coeffs() -> SweepCoeffs:
    """Time the four elementary access patterns on small probe shapes.

    One-time ~100 ms; cached for the process.  Probe shapes are big enough
    to swamp dispatch overhead (~1M elements) and small enough to stay
    cache-resident the way the real sweeps are not — the absolute rates
    matter less than their ratios, which is what the dispatch compares.
    """
    global _MEASURED
    if _MEASURED is not None:
        return _MEASURED
    if os.environ.get("REPRO_SWEEP_CALIBRATE", "1") == "0":
        _MEASURED = DEFAULT_COEFFS
        return _MEASURED
    import jax
    import jax.numpy as jnp

    T0, K0 = 16384, 64
    n = T0 * K0
    a = jnp.linspace(0.1, 1.0, n, dtype=jnp.float32).reshape(T0, K0)
    b = a[::-1]

    ew = jax.jit(lambda a, b: a * b + a - 0.5 * b)
    t_ew = _time_jitted(ew, a, b) / (n * 1)

    idx = (jnp.arange(T0, dtype=jnp.int32) * 7919) % 64
    kcol = ((jnp.arange(T0, dtype=jnp.int32) * 31) % K0)[:, None]
    CH = 8
    iota = jnp.arange(K0, dtype=jnp.int32)[None, :]

    def chain(a, kcol):
        d = jnp.zeros_like(a)
        for j in range(CH):
            d = d + jnp.where(iota == (kcol + j) % K0, 1.0, 0.0)
        return d

    t_chain = _time_jitted(jax.jit(chain), a, kcol) / (n * CH)

    scat = jax.jit(lambda a, idx: jnp.zeros((64, K0), jnp.float32)
                   .at[idx].add(a))
    t_scat = _time_jitted(scat, a, idx) / n

    gath = jax.jit(lambda a, kcol: jnp.take_along_axis(
        a, (kcol + iota[:, :8]) % K0, axis=1))
    t_gath = _time_jitted(gath, a, kcol) / (T0 * 8)

    _MEASURED = SweepCoeffs(ew_ns=t_ew * 1e9, chain_ns=t_chain * 1e9,
                            scatter_ns=t_scat * 1e9, gather_ns=t_gath * 1e9)
    return _MEASURED


def packed_cost(T: int, K: int, Pk: int, P: int, crossover: int,
                c: SweepCoeffs) -> float:
    """Analytic cost (ns) of one packed-formulation iteration.

    Element counts mirror core/pobp._selective_sweep_packed: ~4 gathered
    [T, Pk] streams, ~10 fused elementwise ops on them, the Pk-term
    fold-back chain over [T, K], the carry add + theta contraction
    (2 passes over [T, K]), and the [P, Pk] accumulation (one-hot MXU
    mirror below the crossover, row scatter above).
    """
    stream = T * Pk * (4 * c.gather_ns + 10 * c.ew_ns)
    chain = T * K * Pk * c.chain_ns
    fold = 2 * T * K * c.ew_ns
    if T * P <= crossover:
        accum = 2.0 * T * P * Pk * 0.5 * c.ew_ns     # MAC ~ half a fused op
    else:
        accum = 2 * T * Pk * c.scatter_ns
    return stream + chain + fold + accum


def dense_layout_cost(T: int, K: int, Pk: int, P: int,
                      c: SweepCoeffs) -> float:
    """Analytic cost (ns) of one dense-layout iteration.

    Mirrors core/pobp._selective_sweep_dense_layout: one [T, K] row gather
    of the signed-phi table, ~8 fused [T, K] update passes, the theta
    contraction, the complex-merged delta/residual row scatter (~1.2x a
    plain [T, K] scatter for the doubled payload width), and the O(P*K)
    table build (charged as scatter elements).
    """
    gather = T * K * 0.35 * c.gather_ns   # row gather: contiguous K runs
    update = 8 * T * K * c.ew_ns
    theta = T * K * c.ew_ns
    scatter = 1.2 * T * K * c.scatter_ns
    table = 2 * P * K * c.scatter_ns
    return gather + update + theta + scatter + table


def _pad_to(n: int, m: int) -> int:
    return -(-int(n) // m) * m


# One-hot MACs per token-topic above which the carry kernels' MXU gathers
# and scatters cost more than the XLA dense-layout formulation.  Analytic,
# for TPU v5e: HIGHEST-precision f32 contractions run at ~1/6 of the
# 197 TFLOP/s bf16 peak (~33 TFLOP/s), and the XLA iteration moves
# ~200 B per token-topic at 819 GB/s (~0.24 ns); 2 * MACs / 33e12 s
# equals that at ~4k MACs.  Not yet calibrated on the chip.
ONEHOT_MACS_MAX = 4096

# (where, shape, resolution, reason) for every dispatch that skipped a
# Pallas kernel, one entry per distinct shape; `note` appends and prints
DISPATCH_LOG: list = []


def note(where: str, shape: dict, resolution: str, reason: str) -> None:
    """Record (and print once) a dispatch that bypassed a Pallas kernel."""
    entry = dict(where=where, shape=shape, resolution=resolution,
                 reason=reason)
    if entry in DISPATCH_LOG:
        return
    DISPATCH_LOG.append(entry)
    dims = " ".join(f"{k}={v}" for k, v in shape.items())
    print(f"[dispatch] {where} {dims} -> {resolution}: {reason}",
          flush=True)


def note_bypass(where: str, shape: dict) -> None:
    """Record that impl='pallas' ran the XLA path by design: the fused
    kernels normalize over the whole topic axis in-kernel, so a
    topic-sharded ("model"-axis) reducer takes the jnp formulation."""
    note(where, shape, "xla",
         "topic-sharded model reducer (kernels need an unsharded K)")


def carry_onehot_macs(P: int, n_docs: int, update_phi: bool = True) -> int:
    """One-hot MACs per token-topic of the carry kernel at LOGICAL shapes:
    phi and mask gathers plus delta and residual scatters over the padded
    P+1 power rows, theta gather and scatter over the documents (serving
    streams phi and scatters a per-doc residual instead)."""
    docs = _pad_to(max(n_docs, 1), 8)
    if not update_phi:
        return 3 * docs
    return 4 * _pad_to(int(P) + 1, 8) + 2 * docs


def carry_vmem_fit(K: int, P: int, n_docs: int,
                   vmem_budget_bytes=None, update_phi: bool = True,
                   k_width: Optional[int] = None) -> bool:
    """Dispatch-side VMEM-fit predicate for the carry kernel.

    Takes LOGICAL shapes (K topics, P power rows, n_docs documents) and
    applies the kernel's padding contract (K to 128 lanes, rows/docs to
    8 sublanes plus the guard row) before asking
    `kernels.power_sweep.kernel.carry_vmem_fits` whether the footprint
    admits a >= 64 token tile within the budget — at the full K, or at
    topic-block width ``k_width`` (the K-blocked kernel's narrowest is
    128).  Serving (``update_phi=False``) streams phi per token, so P
    does not enter its footprint.
    """
    from repro.kernels.power_sweep.kernel import carry_vmem_fits
    width = k_width or _pad_to(max(K, 1), 128)
    rows = _pad_to(int(P) + 1, 8) if update_phi else 0
    return carry_vmem_fits(width, rows, _pad_to(max(n_docs, 1), 8),
                           vmem_budget_bytes, update_phi=update_phi)


def _carry_choice(K: int, P: int, n_docs: int, policy: str, budget: int,
                  update_phi: bool):
    """(resolution, reason) behind `resolve_carry`."""
    macs = carry_onehot_macs(P, n_docs, update_phi)
    if macs > ONEHOT_MACS_MAX:
        return "xla", (f"carry kernel one-hot work {macs} MACs per "
                       f"token-topic > ONEHOT_MACS_MAX={ONEHOT_MACS_MAX}")
    if policy != "kblocked" and carry_vmem_fit(K, P, n_docs, budget,
                                               update_phi):
        return "dense_layout", ""
    if carry_vmem_fit(K, P, n_docs, budget, update_phi, k_width=128):
        return "kblocked", ""
    return "xla", (f"carry tables do not fit the {budget:,} B VMEM budget "
                   f"even at topic block 128")


def resolve_carry(K: int, P: int, n_docs: int, policy: str = "auto",
                  vmem_budget_bytes=None, update_phi: bool = True) -> str:
    """The carry formulation for the pallas impl: 'dense_layout' (the
    one-pass kernel), 'kblocked', or 'xla' when the kernel's one-hot work
    or VMEM footprint rules it out.  ``policy`` 'kblocked' pins the
    K-blocked kernel where it can run at all."""
    from repro.kernels import vmem_budget
    return _carry_choice(K, P, n_docs, policy, vmem_budget(vmem_budget_bytes),
                         update_phi)[0]


def resolve_fold_in(K: int, n_docs: int, policy: str = "auto",
                    vmem_budget_bytes=None) -> str:
    """Serving-side resolution of the Pallas fold-in (update_phi=False):
    same rule as training; an 'xla' result is recorded in the log."""
    from repro.kernels import vmem_budget
    got, why = _carry_choice(K, 0, n_docs, policy,
                             vmem_budget(vmem_budget_bytes), False)
    if got == "xla":
        note("fold_in", dict(K=K, D=n_docs), got, why)
    return got


@functools.lru_cache(maxsize=512)
def _resolve_cached(policy: str, T: int, K: int, Pk: int, P: int,
                    crossover: int, impl: str, n_docs: int,
                    budget: int) -> str:
    if policy == "kblocked" and impl != "pallas":
        # XLA has no VMEM budget: the jnp mirror of kblocked IS the
        # dense-layout formulation (same math, same sync bytes)
        return "dense_layout"
    if policy != "auto":
        return policy
    if impl == "pallas":
        # the carry-resident megakernel IS the dense-layout formulation:
        # one HBM read + one write of the [T, K] carry per iteration, all
        # one-hot work on the MXU (kernels/power_sweep).  When the full-K
        # carry footprint stops admitting a useful token tile, the
        # K-blocked two-pass variant takes over (DESIGN.md §13); when
        # the one-hot work or the tables rule both out, the XLA
        # dense-layout formulation runs, and says so.  The packed kernel
        # path remains reachable via sweep_policy='packed'.
        got, why = _carry_choice(K, P, n_docs, "auto", budget, True)
        if got == "xla":
            note("selective_sweep", dict(T=T, K=K, Pk=Pk, P=P, D=n_docs),
                 got, why)
        return got
    c = measure_coeffs()
    cp = packed_cost(T, K, Pk, P, crossover, c)
    cd = dense_layout_cost(T, K, Pk, P, c)
    return "packed" if cp <= cd else "dense_layout"


def resolve_sweep_policy(cfg, T: int, K: int, Pk: int, P: int,
                         impl: Optional[str] = None,
                         n_docs: Optional[int] = None) -> str:
    """Resolve cfg.sweep_policy to a concrete formulation for this shape.

    Called at trace time (all arguments are static Python ints), cached
    per shape: the same (cfg, shape) always dispatches identically within
    a process, so bucketed streams never retrace on policy flapping.
    ``n_docs`` feeds the pallas VMEM-fit predicate (the theta table is
    grid-resident); callers that don't know it get a conservative
    default that only matters near the budget boundary.
    """
    policy = cfg.sweep_policy
    if policy not in ("auto", "packed", "dense_layout", "kblocked"):
        raise ValueError(f"unknown sweep_policy: {policy!r} (expected "
                         f"auto | packed | dense_layout | kblocked)")
    from repro.kernels import vmem_budget
    budget = vmem_budget(getattr(cfg, "vmem_budget_bytes", None))
    return _resolve_cached(policy, int(T), int(K), int(Pk), int(P),
                           int(cfg.onehot_crossover),
                           cfg.impl if impl is None else impl,
                           int(n_docs) if n_docs is not None else 256,
                           budget)
