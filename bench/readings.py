"""Readings that set the correctness limits, on the chip.

  python bench/readings.py --workload <name> --seeds 11,12,... \
      --seconds 3 --control 3 --out chiprun_out/readings.jsonl

Runs the cell once per seed in one process (set-up compiles once), each
with a short window at the cell's own load, and writes one JSON line per
seed: the compared numbers of the program against the reference (the
lower readings; with ``--fault``, of the program with that fault
planted) and, for the first ``--control`` seeds, the same numbers of the
low-precision controls put in the program's place (the upper readings).
The benchmark's own runs never run the controls.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _plain(x):
    import numpy as np
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, np.ndarray):
        return [_plain(v) for v in x.tolist()]
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="serving: offer this rate instead of the cell's "
                         "(the sweep that finds the knee)")
    ap.add_argument("--fault", default=None,
                    help="serving: plant this fault of bench/faults.py in "
                         "the program first")
    args = ap.parse_args(argv)
    from bench.run import execute, prepare
    if args.fault:
        from bench.faults import plant
        plant(args.fault)
    seeds = [int(s) for s in args.seeds.split(",")]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as f:
        for i, seed in enumerate(seeds):
            run = prepare(args.workload, seed, args.seconds)
            if args.rate is not None:
                run.cell.traffic["rate_docs_per_s"] = args.rate
            execute(run, False, control=i < args.control)
            rec = {"workload": args.workload, "seed": seed,
                   "fault": args.fault,
                   "correct": run.correct, "checks": run.checks,
                   "e2e": run.e2e, "rate": run.cell.traffic.get(
                       "rate_docs_per_s"),
                   "counters": {k: run.counters.get(k) for k in (
                       "steps", "distinct_per_doc", "truncated_share",
                       "mean_fold_iters", "occupied_share", "iters")},
                   "readings": run.counters.get("readings"),
                   "control": run.counters.get("control")}
            line = json.dumps(_plain(rec))
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
