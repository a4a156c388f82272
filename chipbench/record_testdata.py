#!/usr/bin/env python3
"""Record the small chip trace that `tests/test_trace_reduce.py` reads.

  python3 chipbench/record_testdata.py [--out chipbench/testdata]

On one TPU: granite-moe-3b-a800m cut to 2 layers at full width, 4 slots
of 512 positions, open-loop traffic of short prompts for half a second,
through the harness with tracing on. Writes `small.xplane.pb.gz`, and
prints its reduction (`trace_reduce.reduce`).
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import cells, run, trace_reduce  # noqa: E402

LAYERS = 2
MIX = {"arrival": "poisson", "rate_rps": 20.0, "block": 10,
       "prompt_classes": {128: 0.5, 256: 0.5},
       "output_classes": {4: 0.5, 8: 0.5}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / "chipbench" / "testdata"))
    args = ap.parse_args(argv)
    devices = run.require_chips(1)
    run.use_compile_cache()
    bench = cells.load_benchmark()
    c = cells.as_run(json.loads((ROOT / next(
        x["file"] for x in bench["configs"]
        if x["name"] == "granite-moe-3b-a800m")).read_text()))
    c = dict(c, num_hidden_layers=LAYERS)
    cfg = dataclasses.replace(run.program_config(
        dict(c, num_hidden_layers=32)), num_layers=LAYERS)
    cell = cells.Cell(
        name="record.small", entry={"chips": 1}, config=c,
        sizing={"slots": 4, "positions": 512,
                "check": {"tokens": 64, "min_tokens": 8, "mean_gap": 0.049}},
        mix=MIX, end_to_end=[], per_layer=[])
    tmp = ROOT / ".chipbench_trace_record"
    out = run.run_cell(cell, 7, 0.5, True, devices=devices[:1], cfg=cfg,
                       t_start=run.serve.clock(), keep_trace=tmp)
    path = trace_reduce.find_trace(tmp)
    dest = Path(args.out)
    dest.mkdir(parents=True, exist_ok=True)
    with open(path, "rb") as f, gzip.open(dest / "small.xplane.pb.gz",
                                          "wb") as g:
        shutil.copyfileobj(f, g)
    red = trace_reduce.reduce(path)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                      "reduction": red}))


if __name__ == "__main__":
    main()
