"""Shared layer primitives: norms, RoPE, Megatron-SP dense FFN,
vocab-parallel embedding / cross-entropy.

All functions take the triple (plan, dist) and run identically under a real
shard_map (local shards) or NullDist (full arrays). Weight layout convention:
matmul weights are stored [in, out]; sharded dims noted per init fn.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.dist import Dist
from repro.sharding.plans import ShardingPlan, pad_to, VOCAB_PAD


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def init_rms_norm(d: int, dtype) -> Tuple[dict, dict]:
    return {"scale": jnp.zeros((d,), dtype)}, {"scale": P(None)}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: [..., S] (broadcastable int32)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Megatron-SP dense FFN (SwiGLU)
#   train/prefill: tokens seq-sharded -> all-gather(seq) .. reduce-scatter(seq)
#   decode:        tokens replicated over tp -> partial matmul .. psum
# ---------------------------------------------------------------------------

def init_dense_ffn(cfg, plan: ShardingPlan, key, d_ff: Optional[int] = None):
    """Global shapes; shard_map in_specs slice the d_ff dim over tp.
    Gate/up stored separately so column-slicing stays head^Wdim-aligned."""
    d, dff = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = dtype_of(cfg)
    params = {
        "w_gate": jax.random.normal(k1, (d, dff), dt) * (d ** -0.5),
        "w_up": jax.random.normal(k2, (d, dff), dt) * (d ** -0.5),
        "w_out": jax.random.normal(k3, (dff, d), dt) * (dff ** -0.5),
    }
    ax = plan.ffn_axes
    specs = {
        "w_gate": P(None, ax),
        "w_up": P(None, ax),
        "w_out": P(ax, None),
    }
    return params, specs


def fp8_all_gather(x, axis, dist: Dist, dim: int):
    """All-gather with an fp8(e4m3) wire format + per-row f32 scales
    (`plan.ag_fp8`). Halves collective bytes vs bf16 —
    and pins the wire width against XLA hoisting a widening convert ahead
    of the collective (observed: f32-width gathers on the CPU lowering).
    The dequantized result returns in x.dtype."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)     # e4m3 max normal
    q = (xf / scale).astype(jnp.float8_e4m3fn)
    # gather the raw bytes: XLA promotes f8 collectives to f16 (observed)
    # and hoists widening converts ahead of collectives — a uint8 bitcast
    # pins the 1-byte wire format on every backend
    qb = jax.lax.bitcast_convert_type(q, jnp.uint8)
    qg = dist.all_gather(qb, axis, dim=dim)
    sg = dist.all_gather(scale, axis, dim=dim)
    qg = jax.lax.bitcast_convert_type(
        jax.lax.optimization_barrier(qg), jnp.float8_e4m3fn)
    return (qg.astype(jnp.float32) * sg).astype(x.dtype)


def dense_ffn(params, x, plan: ShardingPlan, dist: Dist):
    """x: [B, S_loc, D] (seq-sharded) or [B, T, D] (replicated over tp).

    Decode ffn_2d path: weights column-sharded over
    (data x model); the handful of decode tokens all-gathers over `data`
    (cheap: B*D bytes), every device computes with a 16x thinner weight
    shard, and the partial outputs reduce-scatter back to the batch shard.
    Trades ~B*D*2 collective bytes per layer for a (dp-1)/dp cut in FFN
    weight streaming — decode is weight-bound, so this wins whenever
    B*D << ffn_params/dp."""
    seq_sharded = plan.seq_axis is not None and dist.size(plan.seq_axis) > 1
    if seq_sharded:
        if plan.ag_fp8:
            x = fp8_all_gather(x, plan.seq_axis, dist, dim=1)
        else:
            x = dist.all_gather(x, plan.seq_axis, dim=1)
    ffn_2d = plan.ffn_2d and dist.size("data") > 1
    if ffn_2d:
        x = dist.all_gather(x, "data", dim=0)
    gate = jax.nn.silu((x @ params["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    h = gate * (x @ params["w_up"])
    y = h @ params["w_out"]
    if seq_sharded:
        return dist.reduce_scatter(y, plan.seq_axis, dim=1)
    if ffn_2d:
        y = dist.reduce_scatter(y, "data", dim=0)
        return dist.psum(y, plan.tp_axis)
    return dist.psum(y, plan.tp_axis)


# ---------------------------------------------------------------------------
# vocab-parallel embedding + cross-entropy (Megatron-style)
# ---------------------------------------------------------------------------

def padded_vocab(cfg) -> int:
    return pad_to(cfg.vocab_size, VOCAB_PAD)


def init_embedding(cfg, plan: ShardingPlan, key):
    """Global shapes (padded vocab); sliced over the vocab axis by in_specs."""
    v = padded_vocab(cfg)
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    params = {"table": jax.random.normal(k1, (v, cfg.d_model), dt) * 0.02}
    specs = {"table": P(plan.vocab_axis, None)}
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(k2, (cfg.d_model, v), dt) * 0.02
        specs["head"] = P(None, plan.vocab_axis)
    return params, specs


def embed(params, tokens, cfg, plan: ShardingPlan, dist: Dist):
    """tokens: [B, S_loc] int32 -> [B, S_loc, D]. Vocab-sharded table:
    each rank embeds the ids it owns, psum over the vocab axis.

    Where the sequence is sharded over the vocab axis too (train and
    prefill plans), the ranks of that axis hold different tokens: each
    embeds the whole sequence, and one reduce-scatter sums the vocab
    partials and hands each rank its own sequence chunk."""
    seq_over_vocab = (plan.seq_axis is not None
                      and plan.seq_axis == plan.vocab_axis
                      and dist.size(plan.seq_axis) > 1)
    if seq_over_vocab:
        tokens = dist.all_gather(tokens, plan.seq_axis, dim=1)
    table = params["table"]
    v_loc = table.shape[0]
    r = dist.index(plan.vocab_axis)
    local = tokens - r * v_loc
    in_range = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    out = jnp.take(table, safe, axis=0)
    out = jnp.where(in_range[..., None], out, jnp.zeros_like(out))
    if seq_over_vocab:
        return dist.reduce_scatter(out, plan.vocab_axis, dim=1)
    return dist.psum(out, plan.vocab_axis)


def lm_logits(params, x, cfg, plan: ShardingPlan, dist: Dist):
    """x: [B, T, D] -> logits [B, T, V_loc] (vocab-sharded, padded ids
    masked)."""
    w = params["table"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ w).astype(jnp.float32)
    v_loc = w.shape[-1]
    r = dist.index(plan.vocab_axis)
    ids = r * v_loc + jnp.arange(v_loc)
    return jnp.where(ids < cfg.vocab_size, logits, -jnp.inf)


def vocab_parallel_xent(logits, labels, cfg, plan: ShardingPlan, dist: Dist):
    """Cross entropy without materializing full-vocab logits on any rank.

    logits: [B, T, V_loc] fp32 (vocab-sharded); labels: [B, T] global ids.
    Returns mean loss (scalar, replicated)."""
    v_loc = logits.shape[-1]
    r = dist.index(plan.vocab_axis)
    m = dist.pmax(jax.lax.stop_gradient(jnp.max(logits, axis=-1)),
                  plan.vocab_axis)                                   # [B, T]
    sumexp = dist.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                       plan.vocab_axis)                              # [B, T]
    local = labels - r * v_loc
    in_range = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_range, picked, 0.0)
    label_logit = dist.psum(picked, plan.vocab_axis)                 # [B, T]
    loss = jnp.log(sumexp) + m - label_logit
    return jnp.mean(loss)


def greedy_sample(logits, cfg, plan: ShardingPlan, dist: Dist):
    """Global argmax over the sharded vocab: [B, T, V_loc] -> [B, T] int32."""
    v_loc = logits.shape[-1]
    r = dist.index(plan.vocab_axis)
    local_idx = jnp.argmax(logits, axis=-1)
    local_val = jnp.max(logits, axis=-1)
    vmax = dist.pmax(local_val, plan.vocab_axis)
    global_idx = r * v_loc + local_idx
    cand = jnp.where(local_val >= vmax, global_idx, jnp.iinfo(jnp.int32).max)
    return (-dist.pmax(-cand.astype(jnp.int32), plan.vocab_axis)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# FSDP helpers
# ---------------------------------------------------------------------------

def fsdp_spec(shape, base_spec: P, plan: ShardingPlan) -> P:
    """Extend a param spec with FSDP sharding over plan.fsdp_axis on the
    first dimension that is divisible and not already sharded."""
    if plan.fsdp_axis is None:
        return base_spec
    n = plan.axis_size(plan.fsdp_axis)
    entries = list(base_spec) + [None] * (len(shape) - len(base_spec))
    for i, (dim, e) in enumerate(zip(shape, entries)):
        if e is None and dim % n == 0 and dim >= n:
            entries[i] = plan.fsdp_axis
            return P(*entries)
    return base_spec


def fsdp_gather(params, specs, plan: ShardingPlan, dist: Dist):
    """All-gather FSDP-sharded leaves back to TP-only sharding for use in a
    layer body. Autodiff of the tiled all-gather produces the matching
    reduce-scatter on the gradient."""
    if plan.fsdp_axis is None or dist.size(plan.fsdp_axis) == 1:
        return params

    def gather(p, spec):
        if spec is None:
            return p
        entries = list(spec)
        for dim, e in enumerate(entries):
            if e == plan.fsdp_axis:
                return dist.all_gather(p, plan.fsdp_axis, dim=dim)
            if isinstance(e, tuple) and plan.fsdp_axis in e:
                return dist.all_gather(p, plan.fsdp_axis, dim=dim)
        return p

    return jax.tree.map(gather, params, specs,
                        is_leaf=lambda x: x is None or isinstance(x, P))
