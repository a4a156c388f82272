"""Paper Fig 5 + Fig 6: DBO mechanics.

Fig 5: per-iteration latency & throughput in two scale-up clusters
(450 vs 150 GB/s link BW), DeepSeek-V3, EP64, global batch 32768 tokens:
DBO lets the low-BW cluster match the high-BW one.

Fig 6: DBO is beneficial only at sufficiently large batch sizes — at small
batch the layers are memory-bandwidth-bound and splitting the batch nearly
doubles compute time."""
from __future__ import annotations

from benchmarks.common import fmt_bw, save, table
from repro.configs import get_arch
from repro.core import H100, make_cluster
from repro.core.optimizer import iteration_time
from repro.core.workload import ServingPoint


def run(verbose: bool = True):
    cfg = get_arch("deepseek-v3")
    results = {"fig5": [], "fig6": []}

    # ---- Fig 5: batch 32768 tokens over 64 XPUs, 2 link BWs ----
    rows = []
    for bw in (450e9, 150e9):
        cl = make_cluster("scale-up", 64, H100, link_bw=bw)
        p = ServingPoint(batch_global=32768, context=512, ep=64, n_devices=64)
        t_no, ect_no, tc, tm = iteration_time(cfg, p, cl, dbo=False)
        t_dbo, ect_dbo, _, _ = iteration_time(cfg, p, cl, dbo=True)
        rows.append([fmt_bw(bw), f"{t_no * 1e3:.1f}", f"{t_dbo * 1e3:.1f}",
                     f"{ect_no * 1e3:.2f}", f"{ect_dbo * 1e3:.2f}",
                     f"{32768 / t_dbo / cl.n_xpus:.0f}"])
        results["fig5"].append({
            "link_bw": bw, "t_noopt_ms": t_no * 1e3, "t_dbo_ms": t_dbo * 1e3,
            "ect_noopt_ms": ect_no * 1e3, "ect_dbo_ms": ect_dbo * 1e3,
            "thpt_dbo_per_xpu": 32768 / t_dbo / cl.n_xpus})
    t5 = table(["link BW", "t no-overlap ms", "t DBO ms", "ECT no ms",
                "ECT DBO ms", "tok/s/XPU (DBO)"], rows,
               title="Fig 5 — DBO closes the 450 vs 150 GB/s gap "
                     "(DeepSeek-V3, EP64, B=32768)")

    # ---- Fig 6: DBO benefit vs batch size ----
    rows6 = []
    cl = make_cluster("scale-up", 64, H100, link_bw=450e9)
    for b in (256, 512, 1024, 4096, 16384, 32768, 65536):
        p = ServingPoint(batch_global=b, context=512, ep=64, n_devices=64)
        t_no, *_ = iteration_time(cfg, p, cl, dbo=False)
        t_dbo, *_ = iteration_time(cfg, p, cl, dbo=True)
        gain = (t_no - t_dbo) / t_no * 100
        rows6.append([b, f"{t_no * 1e3:.2f}", f"{t_dbo * 1e3:.2f}",
                      f"{gain:+.1f}%"])
        results["fig6"].append({"batch": b, "t_noopt_ms": t_no * 1e3,
                                "t_dbo_ms": t_dbo * 1e3,
                                "dbo_gain_pct": gain})
    t6 = table(["batch", "t no-overlap ms", "t DBO ms", "DBO gain"], rows6,
               title="Fig 6 — DBO helps only at large batch (small batch: "
                     "memory-bound, splitting ~doubles compute)")

    if verbose:
        print(t5)
        print()
        print(t6)
    # claims
    small_gain = results["fig6"][0]["dbo_gain_pct"]
    big_gain = results["fig6"][-1]["dbo_gain_pct"]
    hi, lo = results["fig5"]
    # DBO must close most of the BW-induced latency gap (paper: 'a
    # lower-cost network can match the performance of expensive networks';
    # our anomaly-free schedule hides ~75% of the exposed comm)
    gap_no = lo["t_noopt_ms"] - hi["t_noopt_ms"]
    gap_dbo = lo["t_dbo_ms"] - hi["t_dbo_ms"]
    results["claims"] = {
        "dbo_hurts_small_batch": small_gain <= 0.0,
        "dbo_helps_large_batch": big_gain > 0.0,
        "dbo_closes_most_of_bw_gap": gap_dbo < 0.5 * gap_no,
        "dbo_hides_most_ect": lo["ect_dbo_ms"] < 0.35 * lo["ect_noopt_ms"],
    }
    if verbose:
        print("\nclaims:", results["claims"])
    save("fig5_dbo_latency", results)
    return results


if __name__ == "__main__":
    run()
