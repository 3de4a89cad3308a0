"""Pallas TPU kernels for the paper's compute hot-spots.

The paper's inner loop — the BP message update over non-zero doc-word
entries (Eq. 1) — dominates computation (Table 2: eta*lambda_K*lambda_W*KWDT).
Three kernel packages cover it (DESIGN.md §2/§4):

  - `bp_update`: the t=1 dense sweep — update arithmetic, normalization and
    residual fused into one VMEM-resident token-major pass;
  - `power_sweep`: the t>=2 selective sweep — per-token packed phi gather
    (per-token power-row ids streamed as [TT, 1] int32 blocks),
    mass-conserving renormalization over the [Pk] selected topics, and
    the [P, Pk] delta/residual accumulation, all in one grid pass (the
    packed sync buffers stay VMEM-resident across the whole grid);
  - `power_pack`: the packed gather/scatter of the power submatrix (the
    sync path's memory hot-spot): selected rows move HBM <-> VMEM by DMA,
    the per-row topic selection is a compare-select pass in VMEM.

Kernels are compiled by Mosaic on a TPU backend; on any other backend they
run with ``interpret=True``, which executes the kernel body with XLA ops —
the mode the CPU test suite uses.  Tests that compile for a described TPU
without one attached set ``INTERPRET = False`` themselves.
"""

import os

import jax
import jax.numpy as jnp

# interpret=True everywhere except on a TPU backend
INTERPRET = jax.default_backend() != "tpu"

# ONE VMEM budget for every kernel.  TPU v5e has 128 MiB of VMEM per core
# and Mosaic's default scoped limit is 16 MiB; every pallas_call here
# raises its limit to this budget (`compiler_params`), and every tile
# chooser plans its double-buffered blocks plus in-kernel temporaries
# against the same number, so a tile the chooser accepts is a tile Mosaic
# compiles.  Override per call (LDAConfig.vmem_budget_bytes) or
# process-wide (REPRO_VMEM_BUDGET_BYTES).
DEFAULT_VMEM_BUDGET = 64 * 2**20

# bytes one f32 row of a [rows, 1] block occupies in VMEM: the lane axis
# pads to 128, so per-token scalar blocks (counts, ids) are not free
LANE_ROW_BYTES = 128 * 4


def vmem_budget(override=None) -> int:
    """Resolve the VMEM byte budget: explicit override > env > default."""
    if override is not None:
        return int(override)
    env = os.environ.get("REPRO_VMEM_BUDGET_BYTES", "")
    return int(env) if env else DEFAULT_VMEM_BUDGET


def compiler_params(budget: int):
    """Mosaic parameters carrying the budget as the kernel's VMEM limit."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=int(budget))


def pad_axis(x, axis: int, multiple: int, value=0):
    """Right-pad `axis` of `x` to a multiple of `multiple` with `value`.

    The shared TPU tile-padding contract of every kernel wrapper
    (bp_update / power_pack / power_sweep ops.py).
    """
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)
