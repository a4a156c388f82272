"""Multi-head Latent Attention (DeepSeek-V2/V3) — the paper's workload.

The KV cache stores only the compressed latent (kv_lora_rank + rope_head_dim
per token, e.g. 576 for V3 vs 2*128*128 for vanilla MHA), which is why the
paper's Fig. 10 KV-capacity analysis uses MLA. Naive (non-absorbed) decode
decompresses K/V from the latent each step; the absorbed variant, which
reads only the latent, is not written yet.

Replicated-weight distribution only (deepseek-v3 is the analysis workload,
not a dry-run grid arch); the latent cache is small enough to replicate over
`model` while batch shards over `data`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers.common import apply_rope, dtype_of
from repro.models.layers.attention import flash_attn, NEG_INF
from repro.sharding.dist import Dist
from repro.sharding.plans import ShardingPlan


def init_mla(cfg, plan: ShardingPlan, key):
    d, H, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    r, qr, rp = cfg.mla_kv_lora_rank, cfg.mla_q_lora_rank, cfg.mla_rope_head_dim
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 8)
    sc = d ** -0.5
    params = {
        "w_dq": jax.random.normal(ks[0], (d, qr), dt) * sc,
        "w_uq": jax.random.normal(ks[1], (qr, H * (hd + rp)), dt) * (qr ** -0.5),
        "w_dkv": jax.random.normal(ks[2], (d, r), dt) * sc,
        "w_kr": jax.random.normal(ks[3], (d, rp), dt) * sc,
        "w_uk": jax.random.normal(ks[4], (r, H * hd), dt) * (r ** -0.5),
        "w_uv": jax.random.normal(ks[5], (r, H * hd), dt) * (r ** -0.5),
        "w_o": jax.random.normal(ks[6], (H * hd, d), dt) * ((H * hd) ** -0.5),
        "q_norm": jnp.zeros((qr,), dt),
        "kv_norm": jnp.zeros((r,), dt),
    }
    specs = {k: P(*([None] * v.ndim)) for k, v in params.items()}
    return params, specs


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _qkv(params, x, cfg, positions):
    """x: [B, S, D] -> q_n [B,S,H,hd], q_r [B,S,H,rp], c_kv [B,S,r],
    k_r [B,S,rp] (roped)."""
    H, hd, rp = cfg.num_heads, cfg.head_dim, cfg.mla_rope_head_dim
    B, S, _ = x.shape
    cq = _rms(x @ params["w_dq"], params["q_norm"])
    q = (cq @ params["w_uq"]).reshape(B, S, H, hd + rp)
    q_n, q_r = q[..., :hd], q[..., hd:]
    q_r = apply_rope(q_r, positions, cfg.rope_theta)
    c_kv = _rms(x @ params["w_dkv"], params["kv_norm"])
    k_r = apply_rope((x @ params["w_kr"])[:, :, None, :], positions,
                     cfg.rope_theta)[:, :, 0]
    return q_n, q_r, c_kv, k_r


def _decompress(params, c_kv, cfg):
    H, hd = cfg.num_heads, cfg.head_dim
    B, S, _ = c_kv.shape
    k = (c_kv @ params["w_uk"]).reshape(B, S, H, hd)
    v = (c_kv @ params["w_uv"]).reshape(B, S, H, hd)
    return k, v


def mla_fwd(params, x, cfg, plan: ShardingPlan, dist: Dist, *,
            causal: bool = True, make_cache: bool = False):
    """x: [B, S_loc, D]. Latent-cache MLA; weights replicated."""
    seq_ax = plan.seq_axis
    B, s_loc, _ = x.shape
    r_seq = dist.index(seq_ax)
    pos = r_seq * s_loc + jnp.arange(s_loc)
    q_n, q_r, c_kv, k_r = _qkv(params, x, cfg, pos)

    c_kv_g = dist.all_gather(c_kv, seq_ax, dim=1)
    k_r_g = dist.all_gather(k_r, seq_ax, dim=1)
    k, v = _decompress(params, c_kv_g, cfg)
    # fold the shared rope key into the per-head attention by augmenting dims
    q_aug = jnp.concatenate([q_n, q_r], axis=-1)
    k_aug = jnp.concatenate(
        [k, jnp.broadcast_to(k_r_g[:, :, None], k.shape[:3] + (k_r_g.shape[-1],))],
        axis=-1)
    o = flash_attn(q_aug, k_aug,
                   jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, q_r.shape[-1]))),
                   causal=causal, q_offset=r_seq * s_loc)
    o = o[..., :cfg.head_dim]
    y = o.reshape(B, s_loc, -1) @ params["w_o"]
    cache = {"c_kv": c_kv, "k_rope": k_r} if make_cache else None
    return y, cache


def mla_decode(params, x, cache, pos, cfg, plan: ShardingPlan, dist: Dist):
    """x: [B, 1, D]; cache: c_kv [B, S, r], k_rope [B, S, rp] (replicated
    over model, batch over data); pos: [B] int32, position of each row's
    incoming token."""
    hd = cfg.head_dim
    B = x.shape[0]
    q_n, q_r, c_new, kr_new = _qkv(params, x, cfg, pos[:, None])
    # each row writes its own position; a dead slot whose position ran
    # past the cache drops the write
    rows = jnp.arange(B)
    c_kv = cache["c_kv"].at[rows, pos].set(c_new[:, 0], mode="drop")
    k_rope = cache["k_rope"].at[rows, pos].set(kr_new[:, 0], mode="drop")
    k, v = _decompress(params, c_kv, cfg)                    # [B, S, H, hd]
    S = k.shape[1]
    scale = 1.0 / math.sqrt(hd + q_r.shape[-1])
    s = (jnp.einsum("bhd,bshd->bhs", q_n[:, 0].astype(jnp.float32),
                    k.astype(jnp.float32))
         + jnp.einsum("bhr,bsr->bhs", q_r[:, 0].astype(jnp.float32),
                      k_rope.astype(jnp.float32))) * scale
    valid = jnp.arange(S) <= pos[:, None]                      # [B, S]
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    y = o.reshape(B, -1).astype(x.dtype) @ params["w_o"]
    return y[:, None], {"c_kv": c_kv, "k_rope": k_rope}
