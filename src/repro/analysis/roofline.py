"""Three-term roofline analysis from compiled dry-run artifacts.

Per (arch x shape x mesh) cell:

  compute term    = per-device HLO FLOPs / peak FLOP/s
  memory term     = per-device HLO bytes / HBM bandwidth
  collective term = per-device collective bytes / ICI link bandwidth

(cost_analysis() reports the PER-DEVICE partitioned program, so no division
by chip count; verified empirically in benchmarks/roofline.py docstring.)

Target hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link.

MODEL_FLOPS references:
  train   6 * N * tokens          (fwd+bwd, dense counting)
  decode  2 * N_active * tokens   (one token per sequence)
  prefill 2 * N_active * tokens
The HLO/MODEL ratio flags remat recompute and redundant work; quadratic
attention FLOPs legitimately push it above 1 at long context.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

from repro.configs import SHAPES, get_arch

PEAK_FLOPS = 197e12           # bf16 / chip
HBM_BW = 819e9                # B/s / chip
LINK_BW = 50e9                # B/s / ICI link


@dataclass(frozen=True)
class Roofline:
    arch: str
    shape: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_per_dev: float
    hlo_flops_per_dev: float

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline-optimistic step time: terms overlap perfectly, so the
        max dominates."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return (self.model_flops_per_dev / self.hlo_flops_per_dev
                if self.hlo_flops_per_dev else 0.0)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step spent on the USEFUL compute roofline:
        (model flops / peak) / step_time — the MFU the compiled program
        would achieve if every term ran at its hardware limit."""
        t_use = self.model_flops_per_dev / PEAK_FLOPS
        return t_use / self.step_time_s if self.step_time_s else 0.0


def model_flops_per_device(arch: str, shape_name: str, n_devices: int
                           ) -> float:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * cfg.active_param_count() * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * cfg.active_param_count() * tokens
    else:  # decode: one token per sequence
        total = 2.0 * cfg.active_param_count() * shape.global_batch
    return total / n_devices


def from_dryrun(res: Dict) -> Optional[Roofline]:
    """Build a Roofline from one dryrun.run_cell result dict.

    Uses the in-place-corrected byte count when present (XLA charges
    dynamic-update-slice for the whole target buffer; the compiled program
    updates KV caches in place — see analysis.hlo.dus_overcount_bytes)."""
    if res.get("status") != "ok":
        return None
    coll = res.get("collectives", {}).get("total_bytes", 0.0)
    nbytes = res.get("bytes_accessed_inplace", res["bytes_accessed"])
    return Roofline(
        arch=res["arch"], shape=res["shape"],
        compute_s=res["flops"] / PEAK_FLOPS,
        memory_s=nbytes / HBM_BW,
        collective_s=coll / LINK_BW,
        model_flops_per_dev=model_flops_per_device(
            res["arch"], res["shape"], res["n_devices"]),
        hlo_flops_per_dev=res["flops"],
    )


def what_would_help(r: Roofline) -> str:
    """One-sentence suggestion for the dominant term."""
    b = r.bottleneck
    if b == "collective":
        return ("reduce collective volume: shrink FSDP all-gather via "
                "better param placement, fuse AG/RS pairs, or move traffic "
                "to a wider mesh axis")
    if b == "memory":
        return ("cut HBM traffic: larger fused blocks (Pallas), fewer "
                "remat recomputes, bf16->fp8 weights, better KV layout")
    return ("raise MXU utilization: bigger per-device tiles (less "
            "sharding on the contracted dim), fewer small ops, avoid "
            "padding waste")
