#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

In order: check that JAX sees a TPU and as many chips as the cell asks for
(no fallback: otherwise exit non-zero and print no result); make the
weights from the seed on the device (`weights.py`); build the program's
`Engine` at the cell's slots and positions; warm up every shape the
cell's traffic uses; serve the traffic for `--seconds` (`serve.py`); read
the peak memory; free the program's state; compare a sample of what was
served with the plain reference (`check.py`); print the result.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics (the cell's end-to-end metrics with --trace 0, its
per-layer metrics with --trace 1), device, with --trace 1 a breakdown,
the program's gap statistics (`check.stats`, judged or not), and last the
checks, each number compared beside its limit. The checks are also the
last lines of standard error.

JAX's compilation cache is where JAX_COMPILATION_CACHE_DIR says, and
otherwise in `.jax_cache/` at the root of the checkout, so that only the
first run of a cell compiles. The profiler's trace (--trace 1) goes to
`.chipbench_trace/` there and is deleted once read.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402

from chipbench import cells, check, peaks, serve, trace_reduce  # noqa: E402

TRACE_DIR = ROOT / ".chipbench_trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def require_chips(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, but JAX's first device is "
                     f"{devs[0].platform!r} ({devs[0].device_kind}); there "
                     f"is no CPU fallback")
    if len(devs) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips, JAX sees "
                     f"{len(devs)}")
    return devs


def use_compile_cache():
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Programs compiled or loaded from the cache, by JAX's own events."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1


def program_config(c: dict):
    """The program's registry entry, held to what the configuration file
    states: a run that departs from it is no sound run."""
    from repro.configs import get_arch
    cfg = get_arch(c["registry"])
    m = cfg.moe
    want = {
        "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": m.d_expert,
        "num_experts_per_tok": m.experts_per_token,
        "vocab_size": cfg.vocab_size, "tie_word_embeddings": cfg.tie_embeddings,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "capacity_factor": m.capacity_factor, "torch_dtype": cfg.dtype,
    }
    n_exp = c.get("num_experts", c.get("num_local_experts"))
    bad = {k: (c.get(k), v) for k, v in want.items() if c.get(k) != v}
    if n_exp != m.num_experts:
        bad["experts"] = (n_exp, m.num_experts)
    if bad:
        raise SystemExit(f"chipbench: the program's {c['registry']} departs "
                         f"from {c['name']}: (file, program) {bad}")
    return cfg


def build(c: dict, cfg, seed: int, slots: int, positions: int):
    from chipbench import weights
    from repro.models import model as M
    from repro.serving.engine import Engine
    from repro.sharding.plans import null_plan
    like = jax.eval_shape(lambda k: M.init_model(cfg, null_plan("decode"),
                                                 k)[0], jax.random.PRNGKey(0))
    params = weights.program_params(c, seed, like)
    return Engine(cfg, params, max_batch=slots, max_seq=positions, eos_id=-1)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
             devices=None, cfg=None, t_start: float = None,
             keep_trace: Path = None, control: bool = False) -> dict:
    """One run of `cell`; returns the result object. `cfg` replaces the
    program's registry entry (the CPU tests pass a tiny one); the trace
    is kept in `keep_trace` where that is given. With `control` the fp8
    control is read on the same sample and judged by the same limits
    (`control.py`)."""
    t_start = T_START if t_start is None else t_start
    c, sz = cell.config, cell.sizing
    cfg = cfg or program_config(c)
    devices = devices or jax.devices()
    dev = devices[0]
    peak = peaks.peaks(dev.device_kind) if dev.platform == "tpu" else None
    vocab = c["vocab_size"]

    eng = build(c, cfg, seed, sz["slots"], sz["positions"])
    rec = serve.Recorder(eng, trace)
    serve.warm_up(rec, cell.mix, sz["slots"], vocab, seed)
    compiles = CompileCount()
    gc.collect()
    gc.freeze()
    trace_dir = keep_trace or TRACE_DIR
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = serve.clock() - t_start
    t_open, t_close = serve.serve_window(rec, cell.mix, sz["slots"], seed,
                                         vocab, seconds)
    if trace:
        jax.profiler.stop_trace()
    gc.unfreeze()
    compiled_in_window = compiles.n
    stats = dev.memory_stats() or {}
    mem_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices), default=0)

    served = rec.served()
    prompts = {rid: r.prompt for rid, r in rec.reqs.items()}
    lost = [rid for rid, r in rec.reqs.items()
            if rid >= 0 and r.prefill is not None and rid not in served]
    run = SimpleNamespace(
        config=c, cell=cell, peak=peak, setup_s=setup_s, t_open=t_open,
        t_close=t_close, window_s=t_close - t_open,
        requests=list(rec.reqs.values()), waves=list(rec.waves),
        prefills=list(rec.prefills), trace=None)
    rec.eng = eng = None
    del rec
    gc.collect()

    ref_mod = cell.reference()
    chk = sz["check"]
    picked = check.sample(served, prompts, seed, chk["tokens"])
    ref = ref_mod.Reference(c, seed)
    t_ref = serve.clock()
    prog, ctrl = check.gaps(
        ref, [(prompts[r], served[r]) for r in picked], ref_mod, control)
    ref_s = serve.clock() - t_ref
    prog_stats = check.stats(prog)
    correct, checks = check.judge(prog_stats, prog.size, len(lost), chk)

    breakdown = None
    if trace:
        red = trace_reduce.reduce(trace_reduce.find_trace(trace_dir))
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = red
        if red:
            breakdown = {"device_ops": trace_reduce.top(red["ops"]),
                         "idle_gaps": trace_reduce.top(red["idle_by_span"])}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cells.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    due = [r for r in run.requests if r.arrival < t_close]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    if trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    out = {"correct": correct, "attempted": len(due),
           "failed": len(lost), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["gaps"] = prog_stats
    out["checks"] = checks
    out["_notes"] = {
        "compiles_in_window": compiled_in_window, "reference_s": ref_s,
        "sampled_requests": len(picked),
        "bytes_limit": stats.get("bytes_limit")}
    if ctrl is not None:
        out["_notes"]["control_gaps"] = check.stats(ctrl)
        out["_notes"]["control_correct"] = check.judge(
            check.stats(ctrl), ctrl.size, 0, chk)[0]
    return out


def print_result(out: dict):
    notes = out.pop("_notes", {})
    checks = out.pop("checks")
    out["checks"] = checks
    for k, v in notes.items():
        print(f"chipbench: {k} {v}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.find(cells.load_benchmark(), args.workload)
    devices = require_chips(cell.chips)
    use_compile_cache()
    dev = devices[0]
    print(f"chipbench: {cell.name} seed {args.seed} on {dev.platform} "
          f"{dev.device_kind} x{len(devices)}; jax {jax.__version__}; "
          f"compile cache {jax.config.jax_compilation_cache_dir}",
          file=sys.stderr)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices=devices[:cell.chips])
    print_result(out)


if __name__ == "__main__":
    main()
