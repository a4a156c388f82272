"""Prefill-aware analytics validation.

Mirrors the decode sweep's guarantee layers (tests/test_sweep.py):

  1. the prefill op table's closed forms reproduce
     `workload.prefill_iteration` at random (batch, chunk, context) points,
  2. the batched chunked-prefill TPOT/TTFT matches the scalar
     `optimizer.chunked_prefill_tpot` (1e-9 relative) on a seeded sample,
  3. decode-only results stay byte-identical to the PR-1 outputs (the
     committed fig10 JSON is the regression anchor),

plus the serving-mode search invariants, the single-request KV guard, and
the roofline benchmark's clean-skip path on a fresh checkout.
"""
import json
import os
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import (H100, Scenario, SearchSpec, make_cluster,
                        solve)
from repro.core import optable, optimizer, sweep, workload
from repro.core.workload import ServingPoint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def dsv3_small():
    return get_arch("deepseek-v3").replace(num_layers=8)


# ---------------------------------------------------------------------------
# 1. prefill op table vs prefill_iteration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,tp,ep", [
    ("deepseek-v3", 1, 64),       # MLA + MoE + shared expert
    ("olmoe-1b-7b", 1, 16),       # GQA + MoE
    ("starcoder2-3b", 2, 1),      # dense GQA with TP all-reduces
    ("jamba-v0.1-52b", 1, 8),     # mamba/attn hybrid + MoE
])
def test_prefill_optable_matches_iteration(arch, tp, ep):
    cfg = get_arch(arch)
    if cfg.moe is None:
        ep = 1
    n = 64
    table = optable.prefill_op_table(cfg, tp, ep, n)
    rng = np.random.default_rng(7)
    for _ in range(8):
        bg = int(rng.integers(1, 257))
        chunk = int(rng.integers(1, 4096))
        ctx = int(rng.integers(0, 16384))
        p = ServingPoint(batch_global=bg, context=ctx, tp=tp, ep=ep,
                         n_devices=n)
        ops = workload.prefill_iteration(cfg, p, chunk)
        assert tuple(o.name for o in ops) == table.names
        c = np.array([chunk], float)
        o_arr = np.array([ctx], float)
        for got, want in (
                (table.flops(bg, c, o_arr)[:, 0], [o.flops for o in ops]),
                (table.op_bytes(bg, c, o_arr)[:, 0],
                 [o.bytes for o in ops]),
                (table.m_bytes(bg, c)[:, 0], [o.m_bytes for o in ops])):
            np.testing.assert_allclose(got, np.array(want), rtol=1e-9,
                                       atol=1e-6)


def test_prefill_drops_lm_head_and_keeps_shapes(dsv3_small):
    p = ServingPoint(batch_global=64, context=0, ep=64, n_devices=64)
    dec = workload.decode_iteration(cfg=dsv3_small, p=replace(p, q_len=128))
    pre = workload.prefill_iteration(dsv3_small, p, 128)
    assert [o.name for o in dec if o.name != "lm_head"] \
        == [o.name for o in pre]


def test_prefill_attention_quadratic_in_chunk(dsv3_small):
    """Doubling the chunk must MORE than double the attention-core FLOPs
    (causal intra-chunk term), while GEMM FLOPs scale exactly linearly."""
    p = ServingPoint(batch_global=64, context=0, ep=64, n_devices=64)
    by_name = {}
    for chunk in (512, 1024):
        for o in workload.prefill_iteration(dsv3_small, p, chunk):
            by_name.setdefault(o.name, []).append(o.flops)
    core = by_name["L0.mla_core"]
    assert core[1] > 2 * core[0]
    gemm = by_name["L0.expert_ffn"]
    assert gemm[1] == pytest.approx(2 * gemm[0], rel=1e-12)


def test_chunk_schedule_covers_prompt():
    sizes, offsets = workload.chunk_schedule(1000, 256)
    assert sum(sizes) == 1000
    assert offsets == [0, 256, 512, 768]
    assert sizes[-1] == 232
    with pytest.raises(ValueError):
        workload.chunk_schedule(0, 256)


# ---------------------------------------------------------------------------
# 2. chunked TPOT/TTFT: batched vs scalar (1e-9 relative)
# ---------------------------------------------------------------------------

def test_chunked_tpot_ttft_batched_vs_scalar(dsv3_small):
    rng = np.random.default_rng(42)
    topos = ("scale-up", "scale-out", "torus", "fullmesh")
    n = 64
    table = optable.op_table(dsv3_small, 1, n, n)
    ptable = optable.prefill_op_table(dsv3_small, 1, n, n)
    for _ in range(12):
        topo = topos[rng.integers(len(topos))]
        cl = make_cluster(topo, n, H100,
                          link_bw=float(rng.choice([150e9, 450e9])))
        prompt = int(rng.choice([300, 1024, 4096]))
        chunk = int(rng.choice([128, 512, 1024]))
        sc = Scenario(40.0, prompt + 512, prompt_len=prompt,
                      ttft_ms=float(rng.choice([500.0, 2000.0])))
        batches = np.sort(rng.integers(1, 1 << 14, size=3))
        got_tpot, got_ttft = sweep.batched_chunked_tpot_ttft(
            table, ptable, [cl], batches, sc, chunk)
        for bi, b in enumerate(batches):
            p = ServingPoint(batch_global=int(b), context=sc.context, ep=n,
                             n_devices=n)
            want_tpot, want_ttft = optimizer.chunked_prefill_tpot(
                dsv3_small, p, cl, sc, chunk)
            np.testing.assert_allclose(got_tpot[0, bi], want_tpot,
                                       rtol=1e-9)
            np.testing.assert_allclose(got_ttft[0, bi], want_ttft,
                                       rtol=1e-9)


# ---------------------------------------------------------------------------
# 3. decode-only results byte-identical to PR 1
# ---------------------------------------------------------------------------

def test_scenario_decode_only_unchanged():
    """Prefill fields default inert: same name (JSON keys), same grid key
    semantics, gen_len derived from context = prompt + gen/2."""
    sc = Scenario(40.0, 512)
    assert sc.name == "tpot40ms_ctx512"
    assert sc.mem_context == 512
    pre = Scenario(40.0, 4608, prompt_len=4096, ttft_ms=1500.0)
    assert pre.name == "tpot40ms_ctx4608_p4096_ttft1500ms"
    assert pre.gen_len == 1024
    assert pre.mem_context == 4096 + 4608


def test_decode_only_byte_identical_to_committed_fig10():
    """Recompute two fig10 cells and compare against the committed PR-1
    JSON — the decode path must not move under the prefill refactor.
    Batch sizes are exact; throughput is held to 1e-12 relative, because
    the last bit of a float64 sum moves with the numpy release."""
    path = os.path.join(ROOT, "bench_results", "fig10_scenarios.json")
    with open(path) as f:
        committed = json.load(f)
    cfg = get_arch("deepseek-v3")
    clusters = [make_cluster("scale-up", 64, H100, link_bw=bw)
                for bw in (450e9, 150e9)]
    scenarios = [Scenario(40.0, 512), Scenario(15.0, 4096)]
    ops = sweep.sweep_max_throughput(clusters, cfg, scenarios)
    for ci, bw in enumerate((450, 150)):
        for sc in scenarios:
            want = next(r for r in committed[f"ctx{sc.context}/bw{bw}"]
                        if r["tpot_ms"] == sc.tpot_ms)
            op = ops[ci][scenarios.index(sc)]
            got = ({"thpt_per_xpu": 0.0, "batch": 0} if op is None else
                   {"thpt_per_xpu": op.throughput / 64, "batch": op.batch})
            assert got["thpt_per_xpu"] == pytest.approx(
                want["thpt_per_xpu"], rel=1e-12, abs=0.0)
            assert got["batch"] == want["batch"]


# ---------------------------------------------------------------------------
# 2b. DBO inside the prefill modes (three-lane (max,+) schedule)
# ---------------------------------------------------------------------------

def test_chunked_dbo_batched_vs_scalar_all_topologies(dsv3_small):
    """Chunked-prefill DBO: batched == scalar at 1e-9 on all four Table-3
    topologies at pp > 1 (the acceptance bar) — decode iterations split
    into B/2 microbatches, chunks into causal half-chunks, pp hops on the
    dedicated send/recv lane on both paths."""
    tp, pp = 2, 2
    ep = 64 // (tp * pp)
    table = optable.op_table(dsv3_small, tp, ep, 64, pp=pp)
    ptable = optable.prefill_op_table(dsv3_small, tp, ep, 64, pp=pp)
    sc = Scenario(40.0, 2048 + 512, prompt_len=2048, ttft_ms=2000.0)
    batches = np.array([64, 1024, 8192])
    for topo in ("scale-up", "scale-out", "torus", "fullmesh"):
        cl = make_cluster(topo, 64, H100)
        for chunk in (128, 512, 999):       # odd chunk: uneven causal halves
            got_tpot, got_ttft = sweep.batched_chunked_tpot_ttft(
                table, ptable, [cl], batches, sc, chunk, dbo=True)
            for bi, b in enumerate(batches):
                p = ServingPoint(batch_global=int(b), context=sc.context,
                                 tp=tp, ep=ep, n_devices=64, pp=pp)
                want_tpot, want_ttft = optimizer.chunked_prefill_tpot(
                    dsv3_small, p, cl, sc, chunk, dbo=True)
                np.testing.assert_allclose(got_tpot[0, bi], want_tpot,
                                           rtol=1e-9,
                                           err_msg=f"{topo} c{chunk}")
                np.testing.assert_allclose(got_ttft[0, bi], want_ttft,
                                           rtol=1e-9,
                                           err_msg=f"{topo} c{chunk}")


def test_chunked_dbo_never_worse_than_no_overlap(dsv3_small):
    """DBO TPOT <= no-overlap TPOT on EVERY (cluster, batch, chunk) cell:
    each component is best-of(no-overlap, monotone (max,+) schedule), so
    overlap can only help."""
    sc = Scenario(40.0, 4096 + 512, prompt_len=4096, ttft_ms=0.0)
    table = optable.op_table(dsv3_small, 1, 64, 64)
    ptable = optable.prefill_op_table(dsv3_small, 1, 64, 64)
    batches = np.array([1, 64, 1024, 16384])
    for topo in ("scale-up", "scale-out", "torus", "fullmesh"):
        cl = make_cluster(topo, 64, H100)
        for chunk in (1, 128, 2048):
            t0, f0 = sweep.batched_chunked_tpot_ttft(table, ptable, [cl],
                                                     batches, sc, chunk)
            t1, f1 = sweep.batched_chunked_tpot_ttft(table, ptable, [cl],
                                                     batches, sc, chunk,
                                                     dbo=True)
            assert (t1 <= t0 + 1e-15).all(), (topo, chunk)
            assert (f1 <= f0 + 1e-15).all(), (topo, chunk)


def test_prefill_dbo_gains_on_bandwidth_constrained_fabric(dsv3_small):
    """The motivating trend: on a bandwidth-constrained fabric the chunk's
    A2A hides under the half-chunks' GEMMs, so DBO strictly improves the
    chunked TPOT; the searched operating point is never worse in any
    mode."""
    cl = make_cluster("scale-out", 64, H100)
    sc = Scenario(40.0, 4608, prompt_len=4096, ttft_ms=2000.0)
    table = optable.op_table(dsv3_small, 1, 64, 64)
    ptable = optable.prefill_op_table(dsv3_small, 1, 64, 64)
    batches = np.array([4096])
    t0, _ = sweep.batched_chunked_tpot_ttft(table, ptable, [cl], batches,
                                            sc, 512)
    t1, _ = sweep.batched_chunked_tpot_ttft(table, ptable, [cl], batches,
                                            sc, 512, dbo=True)
    assert t1[0, 0] < t0[0, 0]
    for mode in ("decode", "chunked", "disagg"):
        a = sweep.sweep_prefill([cl], dsv3_small, [sc], mode=mode)[0][0]
        b = sweep.sweep_prefill([cl], dsv3_small, [sc], mode=mode,
                                dbo=True)[0][0]
        assert a is not None and b is not None
        assert b.throughput >= a.throughput - 1e-12, mode
        assert b.used_dbo and not a.used_dbo


def test_decode_dbo_pinned_to_committed_fig11():
    """Decode-path DBO numbers must not move under the three-lane
    generalization: at pp = 1 the sendrecv lane is empty and the schedule
    must reproduce the committed fig11 'dbo' curve (throughput to 1e-12
    relative, the DBO choice exactly)."""
    path = os.path.join(ROOT, "bench_results", "fig11_sw_opts.json")
    with open(path) as f:
        committed = json.load(f)
    cfg = get_arch("deepseek-v3")
    cl = make_cluster("scale-up", 64, H100, link_bw=150e9)
    for want in committed["dbo/bw150"]:
        if want["thpt_per_xpu"] == 0.0:
            continue
        op = solve(cfg, cl, Scenario(want["tpot_ms"], 512),
                   SearchSpec(opts="dbo")).point
        assert op.throughput / 64 == pytest.approx(want["thpt_per_xpu"],
                                                   rel=1e-12, abs=0.0)
        assert op.used_dbo == want["used_dbo"]


# ---------------------------------------------------------------------------
# disagg KV-handoff alpha (pool-local latency regime)
# ---------------------------------------------------------------------------

def test_disagg_kv_handoff_uses_pool_alpha(dsv3_small):
    """Regression (ISSUE 5 satellite): the KV-handoff alpha must come from
    the PREFILL POOL (`cl_p._ab()`), not the whole cluster — an 8-XPU pool
    sits inside one node and pays intra-node latencies. Pins the corrected
    TTFT against the closed form."""
    from repro.core.alphabeta import CLUSTER, INTRA_NODE

    cl = make_cluster("torus", 64, H100)
    sc = Scenario(40.0, 4608, prompt_len=4096, ttft_ms=2000.0)
    op = sweep.sweep_prefill([cl], dsv3_small, [sc], mode="disagg",
                             split_fracs=(0.125,))[0][0]
    assert op is not None and op.n_prefill_xpus == 8
    cl_p = sweep._subcluster(cl, 8)
    assert cl_p._ab() is INTRA_NODE
    ptable = optable.prefill_op_table(dsv3_small, op.tp_prefill,
                                      op.ep_prefill, 8, pp=op.pp_prefill)
    domains = 8 // op.tp_prefill
    t_p = float(sweep._prefill_chunk_times(ptable, cl_p, domains,
                                           [sc.prompt_len], [0])[0])
    kv = workload.kv_cache_bytes_per_request(dsv3_small, sc.prompt_len)
    want = t_p + INTRA_NODE.alpha0 + kv / (INTRA_NODE.link_utilization
                                           * cl.link_bw)
    wrong = t_p + CLUSTER.alpha0 + kv / (CLUSTER.link_utilization
                                         * cl.link_bw)
    assert op.ttft == pytest.approx(want, rel=1e-12)
    assert op.ttft != pytest.approx(wrong, rel=1e-9)


# ---------------------------------------------------------------------------
# serving-mode search
# ---------------------------------------------------------------------------

def test_sweep_prefill_modes(dsv3_small):
    sc = Scenario(40.0, 4608, prompt_len=4096, ttft_ms=2000.0)
    for topo in ("scale-up", "torus"):
        cl = make_cluster(topo, 64, H100)
        dec = solve(dsv3_small, cl, sc,
                    SearchSpec(mode="decode")).prefill_point
        chk = solve(dsv3_small, cl, sc,
                    SearchSpec(mode="chunked")).prefill_point
        dis = solve(dsv3_small, cl, sc,
                    SearchSpec(mode="disagg")).prefill_point
        # decode mode wraps the seed search byte-identically
        ref = solve(dsv3_small, cl, sc).point
        assert (dec.batch, dec.tpot, dec.throughput) \
            == (ref.batch, ref.tpot, ref.throughput)
        for op in (chk, dis):
            assert op is not None, topo
            assert op.tpot <= sc.tpot_ms * 1e-3 * (1 + 1e-9)
            assert 0.0 < op.ttft <= sc.ttft_ms * 1e-3 * (1 + 1e-9)
            # modeling prefill can only cost throughput
            assert op.throughput <= dec.throughput
        assert chk.chunk >= 1
        assert dis.n_prefill_xpus + dis.n_decode_xpus == cl.n_xpus


def test_sweep_prefill_rejects_bad_input(dsv3_small):
    cl = make_cluster("scale-up", 64, H100)
    with pytest.raises(ValueError, match="prompt_len"):
        sweep.sweep_prefill([cl], dsv3_small, [Scenario(40.0, 512)],
                            mode="chunked")
    with pytest.raises(ValueError, match="unknown prefill mode"):
        sweep.sweep_prefill([cl], dsv3_small,
                            [Scenario(40.0, 512, prompt_len=256)],
                            mode="hybrid")
    # context is the AVERAGE decode KV (prompt + gen/2): a prompt at or
    # past it means gen_len <= 0 and must be rejected, not clamped
    with pytest.raises(ValueError, match="must exceed prompt_len"):
        sweep.sweep_prefill([cl], dsv3_small,
                            [Scenario(40.0, 512, prompt_len=8192)],
                            mode="chunked")


# ---------------------------------------------------------------------------
# single-request KV guard
# ---------------------------------------------------------------------------

def test_memory_guard_rejects_oversized_context(dsv3_small):
    cl = make_cluster("scale-up", 64, H100)
    huge = Scenario(10_000.0, 50_000_000)
    p = ServingPoint(batch_global=1, context=huge.context, ep=64,
                     n_devices=64)
    assert not workload.single_request_fits(dsv3_small, p, cl.xpu.hbm_cap)
    assert solve(dsv3_small, cl, huge).point is None
    assert optimizer.max_throughput_scalar(cl, dsv3_small, huge) is None
    # a prompt that pushes context + prompt_len past HBM is rejected too,
    # in every serving mode
    huge_prompt = Scenario(10_000.0, 30_000_000, prompt_len=25_000_000,
                           ttft_ms=0.0)
    for mode in ("decode", "chunked", "disagg"):
        assert sweep.sweep_prefill([cl], dsv3_small, [huge_prompt],
                                   mode=mode)[0][0] is None


def test_memory_guard_keeps_feasible_scenarios(dsv3_small):
    cl = make_cluster("scale-up", 64, H100)
    p = ServingPoint(batch_global=1, context=4096, ep=64, n_devices=64)
    assert workload.single_request_fits(dsv3_small, p, cl.xpu.hbm_cap)
    assert solve(dsv3_small, cl, Scenario(40.0, 4096)).point is not None


# ---------------------------------------------------------------------------
# roofline benchmark: clean skip on fresh checkouts
# ---------------------------------------------------------------------------

def test_roofline_skips_cleanly_without_dryrun(tmp_path, monkeypatch):
    from benchmarks import common, roofline
    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(roofline, "CANDIDATES", [])
    out = roofline.run(verbose=False)
    assert out["status"] == "skipped"
    assert "dry-run" in out["reason"]
    saved = json.load(open(tmp_path / "roofline.json"))
    assert saved["status"] == "skipped"


def test_roofline_runs_as_script(tmp_path):
    """`python benchmarks/roofline.py` from a fresh checkout must exit 0
    (regression: ModuleNotFoundError without PYTHONPATH, bare StopIteration
    without dry-run JSONs)."""
    env = dict(os.environ, BENCH_OUT=str(tmp_path))
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "roofline.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
