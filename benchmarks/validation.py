"""Paper Fig 8: end-to-end runtime-estimation validation.

The paper validates against (a) an 8xH100 HGX and (b) the public SGLang
DeepSeek-V3 96xH100 deployment report, with <9.6% / <7.5% relative error.
We have no H100, so this bench validates our roofline-with-efficiency
compute model against the SAME public reference points the paper used:

  SGLang large-scale-EP blog (12x8 H100, PD-disaggregated): decode phase
  ~22.3k output tok/s per node (2787 tok/s/GPU) at ~2000-token contexts,
  decode batch ~256 requests/GPU  -> implied TPOT ~ 92 ms.

We report our model's TPOT at that operating point and the relative error.
The paper's profiled model achieves <7.5%; our unprofiled roofline model is
expected to land within ~2x (all topology COMPARISONS are ratios, which
cancel first-order efficiency error)."""
from __future__ import annotations

from benchmarks.common import save, table
from repro.configs import get_arch
from repro.core import H100, make_cluster
from repro.core.optimizer import iteration_time
from repro.core.workload import ServingPoint

# public reference (SGLang blog, May 2025)
SGLANG = {
    "n_gpus": 96,
    "decode_tok_s_per_gpu": 2787.0,
    "batch_per_gpu": 256,
    "context": 2000,
    "implied_tpot_ms": 256 / 2787.0 * 1e3,     # ~91.9 ms
}


def run(verbose: bool = True):
    cfg = get_arch("deepseek-v3")
    n = SGLANG["n_gpus"]
    cl = make_cluster("scale-out", n, H100)     # their fabric: IB Clos
    p = ServingPoint(batch_global=SGLANG["batch_per_gpu"] * n,
                     context=SGLANG["context"], ep=n, n_devices=n)
    t, ect, tc, tm = iteration_time(cfg, p, cl, dbo=False)
    ours_ms = t * 1e3
    ref_ms = SGLANG["implied_tpot_ms"]
    rel_err = (ours_ms - ref_ms) / ref_ms

    rows = [
        ["TPOT (ms)", f"{ours_ms:.1f}", f"{ref_ms:.1f}",
         f"{rel_err * +100:+.1f}%"],
        ["tok/s/GPU", f"{SGLANG['batch_per_gpu'] / t / 1:.0f}",
         f"{SGLANG['decode_tok_s_per_gpu']:.0f}", ""],
        ["  t_compute (ms)", f"{tc * 1e3:.1f}", "-", ""],
        ["  t_comm (ms)", f"{tm * 1e3:.1f}", "-", ""],
    ]
    out = table(["quantity", "our model", "SGLang 96xH100", "rel err"],
                rows, title="Fig 8 validation — DeepSeek-V3 decode vs "
                            "public trace (paper's profiled model: <7.5%)")
    results = {
        "ours_tpot_ms": ours_ms, "ref_tpot_ms": ref_ms,
        "rel_err": rel_err, "t_compute_ms": tc * 1e3,
        "t_comm_ms": tm * 1e3,
        "within_2x": bool(abs(rel_err) < 1.0),
    }
    if verbose:
        print(out)
        print(f"\nwithin 2x of public trace: {results['within_2x']}")
    save("validation", results)
    return results


if __name__ == "__main__":
    run()
