import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

# Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
# production mesh, record memory/cost analysis + collective bytes for the
# roofline (benchmarks/roofline.py).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-3b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
#
# NOTE: the XLA_FLAGS line above must run before ANY other import (jax locks
# the device count on first init), hence the unusual import order.

import argparse
import json
import time
import traceback

import jax

from repro.configs import ASSIGNED_ARCHS, SHAPES, cell_applicable, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_mod


def _with_shardings(structs, shardings):
    return jax.tree.map(
        lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype, sharding=sh),
        structs, shardings)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             collect_hlo: bool = False, fsdp: bool = True,
             plan_overrides=None, unroll: bool = False,
             unstack: bool = False, plan_kw=None):
    """Lower + compile one cell. Returns a result dict.

    unroll=True unrolls the layer scan so cost_analysis() counts every
    layer (XLA counts a scan body once) — used for exact roofline numbers;
    the default scanned form is what production would run.

    unstack=True additionally gives every layer its OWN parameter/cache
    arrays (period = the full layer list). Without this the stacked cache
    is one array and XLA's fusion cost accounting charges each per-layer
    slice/update fusion for the FULL stacked operand — TB-scale phantom
    bytes on decode cells. unroll+unstack is the exact-accounting mode."""
    cfg = get_arch(arch)
    if unstack:
        cfg = cfg.replace(period=cfg.layer_specs)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    step, structs, shardings, plan = steps_mod.build_cell(
        cfg, shape, mesh, fsdp=fsdp, unroll=unroll, plan_kw=plan_kw)
    if plan_overrides:
        import dataclasses
        plan = dataclasses.replace(plan, **plan_overrides)
    args = _with_shardings(structs, shardings)
    with mesh:
        lowered = step.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    res = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "unroll": unroll, "unstack": unstack,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "n_devices": int(mesh.devices.size),
        "plan": {"attn_mode": plan.attn_mode, "ep_axis": plan.ep_axis,
                 "batch_axes": plan.batch_axes, "seq_axis": plan.seq_axis,
                 "kv_axis": plan.kv_axis, "fsdp_axis": plan.fsdp_axis,
                 "ffn_2d": plan.ffn_2d},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
    }
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                res[f"mem_{k}"] = int(v)
    # collective bytes + in-place DUS correction from the compiled
    # (post-SPMD-partitioning) HLO
    from repro.analysis.hlo import collective_bytes, dus_overcount_bytes
    try:
        hlo = compiled.as_text()
        res["collectives"] = collective_bytes(hlo)
        res["dus_overcount_bytes"] = dus_overcount_bytes(hlo)
        res["bytes_accessed_inplace"] = max(
            res["bytes_accessed"] - res["dus_overcount_bytes"], 0.0)
        if collect_hlo:
            res["hlo_len"] = len(hlo)
    except Exception as e:  # pragma: no cover
        res["collectives_error"] = str(e)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer scan for exact cost accounting")
    ap.add_argument("--unstack", action="store_true",
                    help="per-layer cache/param arrays (exact accounting)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        print(f"=== {arch} x {shape} (multi_pod={args.multi_pod}) ===",
              flush=True)
        try:
            res = run_cell(arch, shape, multi_pod=args.multi_pod,
                           unroll=args.unroll, unstack=args.unstack)
        except Exception as e:
            res = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        print(json.dumps({k: v for k, v in res.items() if k != "trace"},
                         default=str), flush=True)
        results.append(res)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1, default=str)

    n_ok = sum(1 for r in results if r["status"] == "ok")
    n_skip = sum(1 for r in results if r["status"] == "skipped")
    n_err = len(results) - n_ok - n_skip
    print(f"DONE ok={n_ok} skipped={n_skip} errors={n_err}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
