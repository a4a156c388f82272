#!/usr/bin/env python3
"""Sweep an open-loop cell's arrival rate once, on the chip, to find the
highest rate the program sustains (the knee). The cell's traffic file
then fixes its rate at about four fifths of it.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds 20 \\
      --rates 2 3 4 5 6 8 [--config <config> --traffic <traffic>]

A cell not yet in `BENCHMARK.json` is named with its configuration and
traffic mix; its workload file must be there.

One process builds the cell's engine once and serves its traffic at each
rate in turn for `--seconds`, draining the queue between rates. Per rate
it prints one JSON line: the requests due in the window, the share of
them whose first token reached the host by the close, the requests still
waiting then, the rate of requests admitted, and TTFT's median and 90th
percentile (the cell's own readers). Past the knee the queue grows all
through the window: the admitted rate stops following the offered rate
and the waiting requests and the TTFT tail climb with it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import cells, run, serve  # noqa: E402
from chipbench.metrics import _common  # noqa: E402


def sweep(cell: cells.Cell, seed: int, seconds: float, rates, cfg=None):
    """One JSON-ready row per rate; `cfg` replaces the program's registry
    entry (the CPU tests pass a tiny one)."""
    c, sz = cell.config, cell.sizing
    vocab = c["vocab_size"]
    eng = run.build(c, cfg or run.program_config(c), seed, sz["slots"],
                    sz["positions"])
    rec = serve.Recorder(eng, False)
    serve.warm_up(rec, cell.mix, sz["slots"], vocab, seed)
    for rate in rates:
        mix = dict(cell.mix, rate_rps=rate)
        t_open, t_close = serve.serve_window(rec, mix, sz["slots"], seed,
                                             vocab, seconds)
        w = SimpleNamespace(t_open=t_open, t_close=t_close,
                            window_s=t_close - t_open,
                            requests=list(rec.reqs.values()))
        due = _common.due(w)
        first = [r for r in due if r.tokens and r.tokens[0] <= t_close]
        yield {"rate_rps": rate, "due": len(due),
               "first_token_share": len(first) / max(len(due), 1),
               "waiting_at_close": len(due) - len(first),
               "admitted_rps": len(first) / w.window_s,
               "ttft_p50_ms": cells.reader("ttft_p50_ms")(w),
               "ttft_p90_ms": cells.reader("ttft_p90_ms")(w),
               "itl_p95_ms": cells.reader("itl_p95_ms")(w)}
        while eng.queue or any(eng.live):
            rec.step()
        rec.forget()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--config", help="for a cell not yet listed")
    ap.add_argument("--traffic", help="for a cell not yet listed")
    args = ap.parse_args(argv)
    bench = cells.load_benchmark()
    if args.config:
        cell = cells.make(bench, {"name": args.workload, "chips": 1,
                                  "config": args.config,
                                  "traffic": args.traffic})
    else:
        cell = cells.find(bench, args.workload)
    if cell.mix["arrival"] == "backlog":
        raise SystemExit("chipbench: a backlog cell has no rate to sweep")
    run.require_chips(cell.chips)
    run.use_compile_cache()
    for row in sweep(cell, args.seed, args.seconds, args.rates):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
