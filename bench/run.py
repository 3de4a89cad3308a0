"""The benchmark's one command.

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips JAX finds: set-up
(generated data and weights, compiles, the first minibatches), a
measured window of ``--seconds``, then the comparison that decides
``correct``.  With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
result holds its per-layer metrics and a breakdown.  The last line of
standard output is the result as one JSON object; each compared number
and its limit are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell needs, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap


def prepare(workload: str, seed: int, seconds: float):
    """The cell and a fresh `Run` on checked devices; raises
    `bench.device.NoChip` where the chips are missing."""
    from bench import harness
    from bench.device import check_device
    from bench.peaks import peaks_for
    cell = harness.load_cell(workload)
    harness.use_compile_cache()
    device = check_device(cell.chips)
    run = harness.Run(cell, seed, seconds, device=device,
                      peaks=peaks_for(device["kind"]))
    run.compiles = harness.CompileCounter()
    return run


def execute(run, traced: bool, control: bool = False) -> None:
    """Set-up, window and comparison of one run; with ``traced`` the
    window's trace is reduced into ``run.trace`` and deleted."""
    from bench import harness
    from bench.trace import find_xspace, read_xspace
    trace_dir = harness.TRACE_DIR / run.cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.runner_for(run.cell.traffic["kind"])(run, traced, str(trace_dir),
                                                 control=control)
    if traced:
        path = find_xspace(str(trace_dir))
        if path is None:
            raise RuntimeError(f"the profiler wrote no trace to {trace_dir}")
        run.trace = read_xspace(path)
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from bench import harness
    from bench.device import NoChip
    try:
        run = prepare(args.workload, args.seed, args.seconds)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 3
    execute(run, bool(args.trace))
    line = harness.result_line(run, bool(args.trace))
    harness.print_checks(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
