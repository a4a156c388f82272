#!/usr/bin/env python3
"""Read the program's gap statistics and the fp8 control's, on the chip,
at a cell's own size, for several seeds in one process.

  python3 chipbench/control.py --workload <cell> --seconds 10 --seeds 1 2 3

Each seed is one run of the cell through the harness, with a window at
the cell's own load; on that run's sample the reference is computed in
float32 (the program's readings: the gaps of the served tokens) and in
fp8 (the control's readings: the gaps of the tokens fp8 ranks first at
each of the same positions), each reduced to `check.STATS` and judged by
the cell's limits. A limit lies above every sound reading of the program
and below every reading of the control. Prints one JSON line per seed
and a summary. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import cells, check, run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.find(cells.load_benchmark(), args.workload)
    devices = run.require_chips(cell.chips)
    run.use_compile_cache()
    rows = []
    for seed in args.seeds:
        out = run.run_cell(cell, seed, args.seconds, False,
                           devices=devices[:cell.chips],
                           t_start=run.serve.clock(), control=True)
        notes = out["_notes"]
        rows.append({"seed": seed, "program": out["gaps"],
                     "control": notes["control_gaps"],
                     "program_correct": out["correct"],
                     "control_correct": notes["control_correct"],
                     "tokens": out["checks"]["tokens_compared"]["value"],
                     "sampled_requests": notes["sampled_requests"],
                     "reference_s": notes["reference_s"],
                     "memory_peak_bytes": out["device"]["memory_peak_bytes"]})
        print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": cell.name, "seeds": args.seeds}
    for k in check.STATS:
        lo = max(r["program"][k] for r in rows)
        hi = min(r["control"][k] for r in rows)
        summary[k] = {"program_largest": lo, "control_smallest": hi,
                      "ratio": hi / lo if lo else None}
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
