"""JAX-jitted sweep backend: the product-grid engine behind
`sweep.GridEval(backend="jax")`.

The NumPy engine broadcasts the whole batch-grid x scenario x cluster
search as array programs, but it materializes (n_ops, n_clusters,
n_scenarios, n_batches) temporaries and walks the comm menus and the
(max,+) lane recurrence in Python — at the 10^6-10^7-point product grids
of Fig 18-style studies (link-bw x cluster-size x XPU-generation x
scenario) that is both out of memory and out of time. This module lowers
one `optable.OpTable` + cluster list into a pytree of stacked arrays
(`optable.OpTable.coeff_pytree` columns + per-cluster collective (alpha,
m_coeff, beta) menus + XPU roofline peaks) and evaluates the grid as ONE
jitted device program:

  compute + comm  a `lax.scan` over the op axis accumulates the roofline
                  and best-algorithm collective times without ever
                  materializing the (n_ops, grid) tensor — peak memory is
                  a handful of (n_clusters, n_scenarios, n_batches) blocks
  DBO             the three-lane (max,+) recurrence of
                  `sweep._lane_makespan` as a `lax.scan` over the merged
                  (op, microbatch) order, `vmap`-ed over the static
                  stagger candidates
  prefill         the chunk-polynomial duration rows and the causal
                  half-chunk DBO makespan of `sweep._prefill_chunk_times`
  skew            expert-load factors (`sweep.op_load_factors`) ride in as
                  two extra per-op leaves (lf, cf) consumed by dedicated
                  `*_skew` kernel variants whose comm accumulator carries a
                  scenario axis; uniform grids (load=None) keep the
                  scenario-free factored kernels untouched — the >= 10x
                  product-grid speedup and the byte-identity path never
                  see the skew code

Numerics contract (docs/sweep_engine.md): every kernel runs under
`jax.enable_x64` (float64, same associations as the NumPy
path wherever practical), and the NumPy engine remains the 1e-9-vs-scalar
REFERENCE — this backend is held to <= 1e-6 relative against it
(tests/test_sweep_jax.py; in practice the agreement is ~1e-12). All public
functions take and return NumPy arrays; JAX never leaks to callers.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64, lax

from repro.core import optable
from repro.core.compute_model import (EFF_MEMORY, GEMM_SMALL_TOKENS,
                                      T_LAUNCH)
from repro.core.overlap import LANES, MAX_STAGGER

# keys of the per-op leaves every kernel scans over (leading axis n_ops)
_PER_OP_KEYS = ("kind", "stage_scale", "eff", "eff_small", "flop_row",
                "flop_row_ctx", "flop_row_chunk", "bytes_const",
                "bytes_row", "bytes_ctx", "m_row", "A", "Mc", "Bt")
# the skew kernels additionally scan the expert-load leaves
_PER_OP_KEYS_SKEW = _PER_OP_KEYS + ("lf", "cf")


# ---------------------------------------------------------------------------
# lowering: table + clusters -> pytree of stacked arrays
# ---------------------------------------------------------------------------

def lower_comm_menus(table, clusters) -> Tuple[np.ndarray, np.ndarray,
                                               np.ndarray]:
    """Per-op collective menus as stacked arrays (n_ops, n_cl, n_alg):
    t_comm(op, cl) = min_alg(A + (Mc * m_bytes) * Bt) — exactly the
    association `sweep._comm_times` evaluates, so the jitted times match
    the NumPy ones to float rounding. Missing algorithm slots (menus have
    different sizes) and compute ops pad with A=+inf, which can never win
    the min and is masked off by the op-kind switch downstream."""
    from repro.core.sweep import _comm_menu_coeffs

    kind = np.asarray(table.kind)
    group = np.asarray(table.group)
    pairs = sorted({(int(k), int(g)) for k, g in zip(kind, group)
                    if int(k) != optable.KIND_COMPUTE})
    menus = {(ci, kg): _comm_menu_coeffs(cl, kg[0], kg[1], table.tp,
                                         table.pp)
             for ci, cl in enumerate(clusters) for kg in pairs}
    n_alg = max((len(m) for m in menus.values()), default=1)
    n_cl = len(clusters)
    A = np.full((table.n_ops, n_cl, n_alg), np.inf)
    Mc = np.zeros((table.n_ops, n_cl, n_alg))
    Bt = np.zeros((table.n_ops, n_cl, n_alg))
    for kg in pairs:
        sel = (kind == kg[0]) & (group == kg[1])
        for ci in range(n_cl):
            for j, (a, mc, bt) in enumerate(menus[ci, kg]):
                A[sel, ci, j] = a
                Mc[sel, ci, j] = mc
                Bt[sel, ci, j] = bt
    return A, Mc, Bt


def lower_grid(table, clusters) -> Dict[str, np.ndarray]:
    """One (op table, cluster list) lowered to the flat pytree the jitted
    kernels consume: the table's `coeff_pytree` columns, the stacked comm
    menus, and the per-cluster XPU roofline constants. All leaves are
    NumPy float64/int arrays — they cross into jax at call time, under the
    caller's `enable_x64` scope."""
    lw = table.coeff_pytree()
    lw["A"], lw["Mc"], lw["Bt"] = lower_comm_menus(table, clusters)
    # roofline constants per UNIQUE XPU + a cluster -> xpu gather index:
    # a link-bw x topology product grid shares a handful of XPU specs
    # across hundreds of clusters, and the roofline only depends on the
    # spec — the same dedup `GridEval._durations` does with comp_by_xpu
    fp8 = table.dtype == "fp8"
    xpu_of: Dict[int, int] = {}
    peak, hbm, idx = [], [], []
    for cl in clusters:
        key = id(cl.xpu)
        if key not in xpu_of:
            xpu_of[key] = len(peak)
            peak.append(cl.xpu.flops_fp8 if fp8 else cl.xpu.flops_bf16)
            hbm.append(cl.xpu.hbm_bw)
        idx.append(xpu_of[key])
    lw["peak"] = np.array(peak, np.float64)
    lw["hbm"] = np.array(hbm, np.float64)
    lw["xpu_idx"] = np.array(idx, np.int32)
    return lw


@lru_cache(maxsize=None)
def _stagger_orders(n_ops: int) -> Tuple[np.ndarray, np.ndarray]:
    """The merged (op, microbatch) execution orders of every static
    stagger candidate, as gather-index arrays (n_staggers, 2 * n_ops) —
    the same orders `sweep._lane_makespan` walks in Python."""
    s_max = min(MAX_STAGGER, max(n_ops - 1, 0))
    ks = np.empty((s_max + 1, 2 * n_ops), np.int32)
    mbs = np.empty_like(ks)
    for s in range(s_max + 1):
        order = sorted(((k, mb) for mb in (0, 1) for k in range(n_ops)),
                       key=lambda km: (km[0] + (s if km[1] else 0), km[1]))
        ks[s] = [k for k, _ in order]
        mbs[s] = [mb for _, mb in order]
    return ks, mbs


# ---------------------------------------------------------------------------
# jitted kernels (decode grid: rows x scenarios outer product)
# ---------------------------------------------------------------------------

def _op_factors(op, peak, hbm, rows, bpd, ctx, knee):
    """(comp, comm) of ONE op in FACTORED form — the jnp twin of
    `GridEval._durations`' per-op row: roofline with the thin-GEMM
    efficiency knee, best-algorithm alpha-beta comm time, pipeline
    `stage_scale` on both. The roofline only depends on the cluster
    through its XPU spec and the comm time is scenario-free, so the
    factors stay small — comp is (n_xpu, n_sc, n_b), comm is (n_cl, n_b)
    — and the expansion to the full (n_cl, n_sc, n_b) grid happens ONCE
    on the summed results (or per-op in `_dur_kernel`), not per op. That
    factorization is what makes the seq path >= 10x the NumPy engine: the
    hot loop touches n_xpu + n_cl rows, not n_cl * n_sc."""
    f = op["flop_row"] * rows[None, :] \
        + (op["flop_row_ctx"] * rows)[None, :] * ctx[:, None]
    by = (op["bytes_const"] + op["bytes_row"] * rows)[None, :] \
        + (op["bytes_ctx"] * bpd)[None, :] * ctx[:, None]
    eff = jnp.where(knee, op["eff_small"], op["eff"])          # (n_b,)
    t_c = f[None] / (peak[:, None, None] * eff[None, None, :])
    t_m = by[None] / (hbm[:, None, None] * EFF_MEMORY)
    comp = (jnp.maximum(t_c, t_m) + T_LAUNCH) * op["stage_scale"]
    m = op["m_row"] * rows                                     # (n_b,)
    alg = op["A"][:, :, None] \
        + (op["Mc"][:, :, None] * m[None, None, :]) * op["Bt"][:, :, None]
    comm = alg.min(axis=1) * op["stage_scale"]                 # (n_cl, n_b)
    return comp, comm, op["kind"] == optable.KIND_COMPUTE


@jax.jit
def _seq_kernel(lw, rows, bpd, ctx):
    """(t_compute, t_comm) sums over the op axis, each (n_cl, n_sc, n_b).
    A `lax.scan` accumulation over the factored per-op forms: nothing of
    shape (n_ops, grid) — or even (n_cl, n_sc, n_b) — exists inside the
    loop, so grids of 10^6+ cells evaluate in-cache."""
    peak, hbm = lw["peak"], lw["hbm"]
    knee = rows < GEMM_SMALL_TOKENS
    per_op = {k: lw[k] for k in _PER_OP_KEYS}

    def step(carry, op):
        comp, comm, is_comp = _op_factors(op, peak, hbm, rows, bpd, ctx,
                                          knee)
        tc, tm = carry
        return (tc + jnp.where(is_comp, comp, 0.0),
                tm + jnp.where(is_comp, 0.0, comm)), None

    z_c = jnp.zeros((peak.shape[0], ctx.shape[0], rows.shape[0]),
                    rows.dtype)
    z_m = jnp.zeros((lw["A"].shape[1], rows.shape[0]), rows.dtype)
    (tc, tm), _ = lax.scan(step, (z_c, z_m), per_op)
    tc_full = tc[lw["xpu_idx"]]                    # (n_cl, n_sc, n_b)
    return tc_full, jnp.broadcast_to(tm[:, None, :], tc_full.shape)


def _op_factors_skew(op, peak, hbm, rows, bpd, ctx, knee):
    """(comp, comm) of ONE op under expert skew — `_op_factors` with the
    per-scenario load factor lf on the row-linear flops / bytes / payload
    terms and the hosting factor cf on the weight-stream bytes_const (the
    same associations as `GridEval._durations`' skew branch, so numpy and
    jax agree to float rounding). The payload now depends on the
    scenario, so comm is (n_cl, n_sc, n_b) — the scenario-free
    factorization is lost, which is why uniform grids keep the plain
    kernels."""
    lf = op["lf"]                                              # (n_sc,)
    f = (op["flop_row"] * rows)[None, :] * lf[:, None] \
        + (op["flop_row_ctx"] * rows)[None, :] * ctx[:, None]
    by = op["bytes_const"] * op["cf"] \
        + (op["bytes_row"] * rows)[None, :] * lf[:, None] \
        + (op["bytes_ctx"] * bpd)[None, :] * ctx[:, None]
    eff = jnp.where(knee, op["eff_small"], op["eff"])          # (n_b,)
    t_c = f[None] / (peak[:, None, None] * eff[None, None, :])
    t_m = by[None] / (hbm[:, None, None] * EFF_MEMORY)
    comp = (jnp.maximum(t_c, t_m) + T_LAUNCH) * op["stage_scale"]
    m = (op["m_row"] * rows)[None, :] * lf[:, None]            # (n_sc, n_b)
    alg = op["A"][:, :, None, None] \
        + (op["Mc"][:, :, None, None] * m[None, None]) \
        * op["Bt"][:, :, None, None]
    comm = alg.min(axis=1) * op["stage_scale"]         # (n_cl, n_sc, n_b)
    return comp, comm, op["kind"] == optable.KIND_COMPUTE


@jax.jit
def _seq_kernel_skew(lw, rows, bpd, ctx):
    """`_seq_kernel` for skewed grids: same scan, scenario-carrying comm
    accumulator (n_cl, n_sc, n_b)."""
    peak, hbm = lw["peak"], lw["hbm"]
    knee = rows < GEMM_SMALL_TOKENS
    per_op = {k: lw[k] for k in _PER_OP_KEYS_SKEW}

    def step(carry, op):
        comp, comm, is_comp = _op_factors_skew(op, peak, hbm, rows, bpd,
                                               ctx, knee)
        tc, tm = carry
        return (tc + jnp.where(is_comp, comp, 0.0),
                tm + jnp.where(is_comp, 0.0, comm)), None

    z_c = jnp.zeros((peak.shape[0], ctx.shape[0], rows.shape[0]),
                    rows.dtype)
    z_m = jnp.zeros((lw["A"].shape[1], ctx.shape[0], rows.shape[0]),
                    rows.dtype)
    (tc, tm), _ = lax.scan(step, (z_c, z_m), per_op)
    return tc[lw["xpu_idx"]], tm


@jax.jit
def _dur_kernel_skew(lw, rows, bpd, ctx):
    """`_dur_kernel` for skewed grids (per-op durations for the DBO
    makespan, full (n_ops, n_cl, n_sc, n_b))."""
    peak, hbm = lw["peak"], lw["hbm"]
    knee = rows < GEMM_SMALL_TOKENS
    per_op = {k: lw[k] for k in _PER_OP_KEYS_SKEW}

    def step(carry, op):
        comp, comm, is_comp = _op_factors_skew(op, peak, hbm, rows, bpd,
                                               ctx, knee)
        d = jnp.where(is_comp, comp[lw["xpu_idx"]], comm)
        return carry, d

    _, dur = lax.scan(step, 0, per_op)
    return dur


@jax.jit
def _dur_kernel(lw, rows, bpd, ctx):
    """Per-op duration tensor (n_ops, n_cl, n_sc, n_b) — the DBO makespan
    needs the individual rows (each op is gathered once per merged-order
    position), so this one does materialize the full grid per op; DBO
    callers chunk the cluster axis accordingly."""
    peak, hbm = lw["peak"], lw["hbm"]
    knee = rows < GEMM_SMALL_TOKENS
    per_op = {k: lw[k] for k in _PER_OP_KEYS}

    def step(carry, op):
        comp, comm, is_comp = _op_factors(op, peak, hbm, rows, bpd, ctx,
                                          knee)
        d = jnp.where(is_comp, comp[lw["xpu_idx"]], comm[:, None, :])
        return carry, d

    _, dur = lax.scan(step, 0, per_op)
    return dur


@jax.jit
def _makespan_kernel(lane, dur_a, dur_b, ks, mbs):
    """Best-stagger makespan of the fixed-order three-lane schedule —
    `sweep._lane_makespan` as a (max,+) `lax.scan` over the merged order,
    `vmap`-ed over the stagger candidates (ks/mbs: (n_staggers, 2*n_ops)
    gather indices from `_stagger_orders`). dur_a/dur_b are the two
    microbatches' (n_ops, *tail) duration tensors (equal for decode DBO,
    causal halves for prefill chunks)."""
    dur = jnp.stack([dur_a, dur_b])                 # (2, n_ops, *tail)
    tail = dur_a.shape[1:]

    def one_stagger(order):
        ks_s, mbs_s = order

        def step(carry, x):
            ready, free = carry
            k, mb = x
            end = jnp.maximum(jnp.where(mb == 0, ready[0], ready[1]),
                              free[lane[k]]) + dur[mb, k]
            ready = lax.dynamic_update_index_in_dim(ready, end, mb, 0)
            free = lax.dynamic_update_index_in_dim(free, end, lane[k], 0)
            return (ready, free), None

        init = (jnp.zeros((2,) + tail, dur.dtype),
                jnp.zeros((len(LANES),) + tail, dur.dtype))
        (ready, _), _ = lax.scan(step, init, (ks_s, mbs_s))
        return jnp.maximum(ready[0], ready[1])

    return jax.vmap(one_stagger)((ks, mbs)).min(axis=0)


# ---------------------------------------------------------------------------
# jitted kernels (prefill chunks: sizes/offsets aligned vectors)
# ---------------------------------------------------------------------------

@jax.jit
def _prefill_dur_kernel(lw, rows, bpd, chunk, ctx):
    """Per-op per-chunk durations (n_ops, n_chunks) of one chunk schedule
    on one cluster — the jnp twin of `sweep._prefill_chunk_durations`
    (comp and comm merged into one tensor; their supports are disjoint).
    `chunk`/`ctx` are ALIGNED vectors (one entry per chunk of the
    schedule), not an outer product, and the flop polynomial carries the
    quadratic-in-chunk `flop_row_chunk` attention term."""
    peak, hbm = lw["peak"][0], lw["hbm"][0]
    knee = rows < GEMM_SMALL_TOKENS
    per_op = {k: lw[k] for k in _PER_OP_KEYS}

    def step(carry, op):
        f = op["flop_row"] * rows + op["flop_row_ctx"] * (rows * ctx) \
            + op["flop_row_chunk"] * (rows * chunk)
        by = op["bytes_const"] + op["bytes_row"] * rows \
            + op["bytes_ctx"] * (bpd * ctx)
        eff = jnp.where(knee, op["eff_small"], op["eff"])
        comp = jnp.maximum(f / (peak * eff), by / (hbm * EFF_MEMORY)) \
            + T_LAUNCH
        m = op["m_row"] * rows
        alg = op["A"][0][:, None] \
            + (op["Mc"][0][:, None] * m[None, :]) * op["Bt"][0][:, None]
        is_comp = op["kind"] == optable.KIND_COMPUTE
        d = jnp.where(is_comp, comp, alg.min(axis=0)) * op["stage_scale"]
        return carry, d

    _, dur = lax.scan(step, 0, per_op)
    return dur


def prefill_chunk_times(ptable, cluster, batch_global: int,
                        sizes: Sequence[int], offsets: Sequence[int], *,
                        dbo: bool = False) -> np.ndarray:
    """Jitted `sweep._prefill_chunk_times`: per-chunk prefill iteration
    times, (n_chunks,). dbo=True takes best-of(no-overlap, three-lane DBO
    over the causal ceil/floor half-chunk split) per chunk."""
    lw = lower_grid(ptable, [cluster])
    s_arr = np.asarray(sizes, np.float64)
    o_arr = np.asarray(offsets, np.float64)
    bpd = float(batch_global) * ptable.tp / ptable.n

    def dur(sz, off):
        return _prefill_dur_kernel(lw, bpd * sz, bpd, sz, off)

    with enable_x64():
        seq = np.asarray(dur(s_arr, o_arr).sum(axis=0))
        if not dbo:
            return seq
        h2 = np.floor(s_arr / 2)
        h1 = s_arr - h2
        mk = _makespan_kernel(np.asarray(ptable.lane, np.int32),
                              dur(h1, o_arr), dur(h2, o_arr + h1),
                              *_stagger_orders(ptable.n_ops))
        return np.where(s_arr >= 2, np.minimum(seq, np.asarray(mk)), seq)


# ---------------------------------------------------------------------------
# decode-grid engine (the jax twin of GridEval's heavy primitives)
# ---------------------------------------------------------------------------

class JaxGridEngine:
    """Jitted evaluator for one (table, clusters, scenarios, batches) grid.

    `sweep.GridEval(backend="jax")` delegates its two heavy primitives —
    the no-overlap duration sums and the DBO makespan — here; selection,
    SD combination, and the scalar winner re-derivation stay in
    `GridEval`, identical across backends. Methods return NumPy arrays of
    shape (n_clusters, n_scenarios, n_batches)."""

    def __init__(self, table, clusters, scenarios,
                 batches: np.ndarray, half: np.ndarray, load=None):
        self.table = table
        self.lw = lower_grid(table, clusters)
        self.skew = load is not None
        if self.skew:
            # expert-load leaves (sweep.op_load_factors) ride the same
            # pytree; the plain kernels never select them
            self.lw["lf"] = np.asarray(load[0], np.float64)
            self.lw["cf"] = np.asarray(load[1], np.float64)
        self.ctx = np.array([sc.context for sc in scenarios], np.float64)
        self.batches = np.asarray(batches, np.float64)
        self.half = np.asarray(half, np.float64)

    def _rows(self, q: int, half: bool):
        b = self.half if half else self.batches
        bpd = b * self.table.tp / self.table.n
        return bpd * q, bpd

    def seq_components(self, q: int, half: bool = False):
        rows, bpd = self._rows(q, half)
        kernel = _seq_kernel_skew if self.skew else _seq_kernel
        with enable_x64():
            tc, tm = kernel(self.lw, rows, bpd, self.ctx)
        return np.asarray(tc), np.asarray(tm)

    def dbo_makespan(self, q: int) -> np.ndarray:
        rows, bpd = self._rows(q, half=True)
        kernel = _dur_kernel_skew if self.skew else _dur_kernel
        with enable_x64():
            dur = kernel(self.lw, rows, bpd, self.ctx)
            mk = _makespan_kernel(np.asarray(self.table.lane, np.int32),
                                  dur, dur,
                                  *_stagger_orders(self.table.n_ops))
        return np.asarray(mk)
