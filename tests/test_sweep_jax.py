"""Backend parity: the jitted sweep engine vs the NumPy reference.

The two-backend contract (docs/sweep_engine.md): the NumPy path is the
reference — held to 1e-9 against the scalar optimizer elsewhere — and the
jax path must agree with it to <= 1e-6 relative on every grid cell, with
identical argmax winners on the committed figures. These tests pin that
contract deterministically:

  1. grid parity across all four Table-3 topologies, dbo on/off,
  2. grid parity across (tp, pp, ep) mappings, including pp > 1 (the
     three-lane schedule's send/recv lane),
  3. end-to-end OperatingPoint equality for the full search entry points
     (sweep_max_throughput, degraded_max_throughput under faults,
     sweep_prefill chunked/disagg) — equality is EXACT, not approximate:
     the jax path re-derives each winner through the scalar optimizer, so
     whenever the argmax agrees the OperatingPoint is byte-identical,
  4. argmax-winner pins against the committed fig10 JSON and the Table-3
     topology comparison under backend="jax",
  5. backend-seam plumbing (set_default_backend, validation, env default).

Randomized cross-products of the same axes live in
tests/test_sweep_jax_props.py (hypothesis, skipped when not installed).
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.configs import get_arch
from repro.core import H100, Scenario, make_cluster
from repro.core import optable, sweep, sweep_jax
from repro.core.topology import FaultSet, TOPOLOGIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-6          # the jax-vs-numpy acceptance bar (observed ~1e-12)
BATCHES = np.array([1, 4, 64, 512, 4096, 32768])


@pytest.fixture(scope="module")
def dsv3_small():
    return get_arch("deepseek-v3").replace(num_layers=8)


def _tpots(cfg, tp, pp, topo, *, dbo, faults=None, sd=None):
    """(numpy, jax) TPOT grids for one mapping on one topology."""
    n = 64
    ep = max(n // (tp * pp), 1)
    table = optable.op_table(cfg, tp, ep, n, "fp8", pp=pp)
    cl = make_cluster(topo, n, H100)
    if faults is not None:
        cl = cl.with_faults(faults)
    scs = [Scenario(25.0, 512), Scenario(60.0, 8192)]
    out = []
    for backend in ("numpy", "jax"):
        ev = sweep.GridEval(table, [cl], scs, BATCHES, backend=backend)
        out.append(ev.tpot(dbo=dbo, sd=sd))
    return out


# ---------------------------------------------------------------------------
# 1-2. grid parity: topology x (tp, pp, ep) x dbo x faults
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", TOPOLOGIES)
@pytest.mark.parametrize("dbo", [False, True])
def test_grid_parity_topologies(dsv3_small, topo, dbo):
    ref, got = _tpots(dsv3_small, 2, 1, topo, dbo=dbo)
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=0.0)


@pytest.mark.parametrize("tp,pp", [(1, 1), (4, 1), (1, 4), (2, 2)])
def test_grid_parity_mappings(dsv3_small, tp, pp):
    """pp > 1 exercises stage_scale and the dedicated pp send/recv lane
    inside the jitted (max,+) makespan."""
    ref, got = _tpots(dsv3_small, tp, pp, "fullmesh", dbo=True)
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=0.0)


def test_grid_parity_faulted_fabric(dsv3_small):
    """Link faults derate the comm menus per cluster; the jax lowering
    must pick the derated alphas up from Cluster.comm_spec unchanged."""
    fs = FaultSet(mesh_links=(2, 1, 0))
    ref, got = _tpots(dsv3_small, 2, 1, "torus", dbo=True, faults=fs)
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=0.0)


def test_comm_lowering_matches_numpy_menus(dsv3_small):
    """The padded (A, Mc, Bt) menu tensors are exactly the per-cluster
    alpha-beta coefficients the NumPy path uses (same Table-3 collective
    algorithms, same association) — parity starts at the lowering."""
    table = optable.op_table(dsv3_small, 2, 32, 64, "fp8")
    clusters = [make_cluster(t, 64, H100) for t in TOPOLOGIES]
    A, Mc, Bt = sweep_jax.lower_comm_menus(table, clusters)
    for oi in range(table.n_ops):
        for ci, cl in enumerate(clusters):
            if table.is_compute[oi]:
                assert np.all(np.isinf(A[oi, ci]))      # inert under min
                continue
            algs = sweep._comm_menu_coeffs(cl, int(table.kind[oi]),
                                           int(table.group[oi]),
                                           table.tp, table.pp)
            k = len(algs)
            want = np.array(algs)                       # (k, 3) triples
            assert np.array_equal(A[oi, ci, :k], want[:, 0])
            assert np.array_equal(Mc[oi, ci, :k], want[:, 1])
            assert np.array_equal(Bt[oi, ci, :k], want[:, 2])
            assert np.all(np.isinf(A[oi, ci, k:]))      # padding is inert


# ---------------------------------------------------------------------------
# 3. end-to-end searches: EXACT OperatingPoint equality
# ---------------------------------------------------------------------------

def test_sweep_max_throughput_exact(dsv3_small):
    clusters = [make_cluster("scale-up", 64, H100),
                make_cluster("torus", 64, H100)]
    scs = [Scenario(25.0, 1024), Scenario(60.0, 4096)]
    ref = sweep.sweep_max_throughput(clusters, dsv3_small, scs, tp=2,
                                     dbo=True, backend="numpy")
    got = sweep.sweep_max_throughput(clusters, dsv3_small, scs, tp=2,
                                     dbo=True, backend="jax")
    assert got == ref


def test_degraded_max_throughput_exact(dsv3_small):
    cl = make_cluster("torus", 64, H100)
    fs = FaultSet(mesh_links=(2, 1, 0), xpus=1)
    sc = Scenario(40.0, 4096)
    ref = sweep.degraded_max_throughput(cl, dsv3_small, sc, faults=fs,
                                        dbo=True, backend="numpy")
    got = sweep.degraded_max_throughput(cl, dsv3_small, sc, faults=fs,
                                        dbo=True, backend="jax")
    assert got == ref and got is not None


@pytest.mark.parametrize("mode", ["chunked", "disagg"])
def test_sweep_prefill_exact(dsv3_small, mode):
    clusters = [make_cluster("scale-up", 64, H100)]
    sc = Scenario(40.0, 4096, prompt_len=2048, ttft_ms=2000.0)
    ref = sweep.sweep_prefill(clusters, dsv3_small, [sc], mode=mode,
                              tp=2, dbo=True, backend="numpy")
    got = sweep.sweep_prefill(clusters, dsv3_small, [sc], mode=mode,
                              tp=2, dbo=True, backend="jax")
    assert got == ref and got[0][0] is not None


def test_prefill_chunk_times_parity(dsv3_small):
    """The prefill chunk-duration kernel (uneven causal halves, dbo)."""
    ptable = optable.prefill_op_table(dsv3_small, 2, 16, 64, pp=2)
    cl = make_cluster("fullmesh", 64, H100)
    sizes = np.array([1, 128, 513, 4096])
    offsets = np.array([0, 0, 512, 8192])
    for dbo in (False, True):
        ref = sweep._prefill_chunk_times(ptable, cl, 256, sizes, offsets,
                                         dbo=dbo, backend="numpy")
        got = sweep._prefill_chunk_times(ptable, cl, 256, sizes, offsets,
                                         dbo=dbo, backend="jax")
        np.testing.assert_allclose(got, ref, rtol=RTOL, atol=0.0)


# ---------------------------------------------------------------------------
# 4. committed-figure argmax pins under backend="jax"
# ---------------------------------------------------------------------------

def test_fig10_winners_pinned_under_jax():
    """Recompute fig10 cells with backend="jax" and require the winners
    to match the committed PR-1 JSON — the jitted argmax must not move the
    committed figures. Batch sizes are exact; throughput is held to 1e-12
    relative, because the last bit moves with the numpy release."""
    with open(os.path.join(ROOT, "bench_results",
                           "fig10_scenarios.json")) as f:
        committed = json.load(f)
    cfg = get_arch("deepseek-v3")
    clusters = [make_cluster("scale-up", 64, H100, link_bw=bw)
                for bw in (450e9, 150e9)]
    scenarios = [Scenario(40.0, 512), Scenario(15.0, 4096),
                 Scenario(100.0, 512)]
    ops = sweep.sweep_max_throughput(clusters, cfg, scenarios,
                                     backend="jax")
    for ci, bw in enumerate((450, 150)):
        for si, sc in enumerate(scenarios):
            want = next(r for r in committed[f"ctx{sc.context}/bw{bw}"]
                        if r["tpot_ms"] == sc.tpot_ms)
            op = ops[ci][si]
            got = ({"thpt_per_xpu": 0.0, "batch": 0} if op is None else
                   {"thpt_per_xpu": op.throughput / 64, "batch": op.batch})
            assert got["thpt_per_xpu"] == pytest.approx(
                want["thpt_per_xpu"], rel=1e-12, abs=0.0), (bw, sc)
            assert got["batch"] == want["batch"], (bw, sc)


def test_table3_topology_winner_pinned_under_jax(dsv3_small):
    """The Table-3 topology comparison (same XPUs, four fabrics) must
    crown the same winner on both backends, with identical points."""
    scs = [Scenario(20.0, 4096)]
    by_backend = {}
    for backend in ("numpy", "jax"):
        pts = {t: sweep.sweep_max_throughput(
                   [make_cluster(t, 64, H100)], dsv3_small, scs, tp=2,
                   backend=backend)[0][0] for t in TOPOLOGIES}
        assert all(p is not None for p in pts.values())
        by_backend[backend] = pts
    assert by_backend["numpy"] == by_backend["jax"]
    win = {b: max(p, key=lambda t: p[t].throughput)
           for b, p in by_backend.items()}
    assert win["numpy"] == win["jax"]


# ---------------------------------------------------------------------------
# 5. backend seam plumbing
# ---------------------------------------------------------------------------

def test_backend_validation_and_default(dsv3_small):
    with pytest.raises(ValueError, match="unknown sweep backend"):
        sweep.set_default_backend("cuda")
    table = optable.op_table(dsv3_small, 1, 64, 64, "fp8")
    with pytest.raises(ValueError, match="unknown sweep backend"):
        sweep.GridEval(table, [make_cluster("scale-up", 64, H100)],
                       [Scenario(40.0, 512)], BATCHES, backend="tpu")
    prev = sweep.set_default_backend("jax")
    try:
        assert prev == "numpy"      # repo default: NumPy is the reference
        ev = sweep.GridEval(table, [make_cluster("scale-up", 64, H100)],
                            [Scenario(40.0, 512)], BATCHES)
        assert ev.backend == "jax"  # backend=None picks up module default
    finally:
        sweep.set_default_backend(prev)

